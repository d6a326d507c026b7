"""Process-level JAX set-up shared by every entry point.

Three decisions that must be made the same way wherever a process starts
compiling, so they live in one module:

* :func:`virtual_cpu_devices` — the N-device virtual CPU mesh of a
  ``JAX_PLATFORMS=cpu`` process (tests, CPU rehearsals of sharded runs);
* :func:`configure_compile_cache` — the persistent compilation cache at
  a path that can be placed from outside and never moves on its own, so
  a restarted worker finds what its predecessor compiled;
* :func:`pallas_interpret` — the one rule for when a Pallas kernel runs
  in interpret mode.
"""

import os
import sys

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def virtual_cpu_devices(n: int) -> None:
    """Give a ``JAX_PLATFORMS=cpu`` process ``n`` virtual CPU devices.

    Must run before the first backend use.  No-op on any other platform
    setting (a real accelerator's device count is not ours to choose).
    """
    if n <= 0 or os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return
    import jax

    if jax.config.jax_num_cpu_devices != n:
        jax.config.update("jax_num_cpu_devices", n)


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives for this process:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment places it, else
    ``<checkout>/.jax_cache``.  The path is part of what makes a cache
    entry findable, so it is never derived from a pid, the time or a
    temporary directory."""
    return os.environ.get(COMPILE_CACHE_ENV) or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`compile_cache_dir` and export the choice, so every process
    this one spawns (the agent copies its environment into workers)
    shares the directory.  Returns the directory.

    Never imports JAX itself: JAX reads the variable when it is first
    imported, and a process that already imported it gets its config
    updated — so an agent that must stay off JAX can call this too."""
    path = compile_cache_dir()
    os.environ[COMPILE_CACHE_ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def pallas_interpret() -> bool:
    """Pallas kernels compile for the TPU and run in interpret mode
    everywhere else (the CPU tests' path)."""
    import jax

    return jax.default_backend() != "tpu"
