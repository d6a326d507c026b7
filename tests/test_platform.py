"""Process-level JAX set-up (common/platform.py): the virtual CPU mesh of a
``JAX_PLATFORMS=cpu`` process and the one rule for Pallas interpret mode.
The compile-cache helper's cases are in tests/test_chip_smoke.py."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestVirtualCpuDevices:
    def test_noop_on_a_live_backend_that_already_matches(self, devices8):
        """conftest's process holds a CPU backend whose device objects
        session fixtures keep: asking for what it has changes nothing."""
        import jax

        from dlrover_tpu.common.platform import virtual_cpu_devices

        before = jax.devices()
        virtual_cpu_devices(jax.config.jax_num_cpu_devices)
        assert jax.devices()[0] is before[0]

    def test_noop_when_the_platform_is_not_cpu(self, monkeypatch):
        """A real accelerator's device count is not ours to choose."""
        import jax

        from dlrover_tpu.common.platform import virtual_cpu_devices

        before = jax.config.jax_num_cpu_devices
        for value in (None, "tpu"):
            if value is None:
                monkeypatch.delenv("JAX_PLATFORMS", raising=False)
            else:
                monkeypatch.setenv("JAX_PLATFORMS", value)
            virtual_cpu_devices(99)  # must not apply
            assert jax.config.jax_num_cpu_devices == before

    def test_fresh_process_gets_the_requested_mesh(self):
        """A plain interpreter: the environment picks the platform, the
        helper the device count."""
        code = (
            "from dlrover_tpu.common.platform import virtual_cpu_devices\n"
            "virtual_cpu_devices(3)\n"
            "import jax\n"
            "devs = jax.devices()\n"
            "print(devs[0].platform, len(devs))\n"
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # count must come from the helper
        out = subprocess.run(
            [sys.executable, "-c", code],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-800:]
        assert out.stdout.split() == ["cpu", "3"], out.stdout


def test_pallas_interpret_is_decided_by_the_backend(monkeypatch):
    """One rule, in one place, for every kernel file: compiled on a TPU,
    interpret mode everywhere else."""
    import jax

    from dlrover_tpu.common.platform import pallas_interpret
    from dlrover_tpu.ops import (
        flash_attention,
        quantize_pallas,
        splash_attention,
    )

    for module in (flash_attention, splash_attention, quantize_pallas):
        assert module.pallas_interpret is pallas_interpret
    assert pallas_interpret() is True  # the tests run on the CPU
    for backend, want in (("tpu", False), ("gpu", True), ("cpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert pallas_interpret() is want
