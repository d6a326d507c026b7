"""Worker entrypoints.

Two roles in one module:

* ``run()`` — actor-based platforms (Ray).  Reference parity:
  ``dlrover/python/scheduler/ray.py`` ``RayWorker`` — the callable a Ray
  actor wraps.  It boots the elastic agent against the job master
  exactly like a pod's ``tpurun`` would.

* ``main()`` (``python -m dlrover_tpu.launch.worker script.py ...``) —
  the per-process training entrypoint the elastic agent spawns.  It
  consumes the ``NodeEnv`` JAX triple: ``runtime.bootstrap_world()``
  forms the ``jax.distributed`` world (idempotent, retried), verifies it
  with a cross-process barrier + consistency check, THEN hands control
  to the user's training script.  This is what turns the agent's
  published ``(coordinator, num_processes, process_id)`` into a live
  distributed world on the production path.
"""

import os
import runpy
import sys
from typing import List, Optional

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import logger


def run(
    job_name: str = "job",
    node_type: str = "worker",
    node_id: int = 0,
    master_addr: str = "",
    entrypoint: Optional[List[str]] = None,
):
    """Boot an elastic agent inside this process (one per actor)."""
    os.environ[NodeEnv.JOB_NAME] = job_name
    os.environ[NodeEnv.NODE_TYPE] = node_type
    os.environ[NodeEnv.NODE_ID] = str(node_id)
    if master_addr:
        os.environ[NodeEnv.MASTER_ADDR] = master_addr
    logger.info(
        "ray worker %s/%s-%d starting", job_name, node_type, node_id
    )
    if not entrypoint:
        # The scaler/submitter thread the training command through
        # DLROVER_TRAINING_CMD (JSON list) when relaunching workers.
        import json

        raw = os.environ.get("DLROVER_TRAINING_CMD", "")
        entrypoint = json.loads(raw) if raw else None
    if not entrypoint:
        raise ValueError(
            "no training entrypoint: pass entrypoint=[...] or set "
            "DLROVER_TRAINING_CMD to a JSON list of argv"
        )
    from dlrover_tpu.launch.elastic_run import main as elastic_main

    args = ["--nnodes", "1", "--node_rank", str(node_id)]
    args += list(entrypoint)
    return elastic_main(args)


def bootstrap(spec=None):
    """Form the distributed world this process belongs to and verify it.

    Must run before any other JAX API pins the backend.  Returns the
    bootstrapped ``WorldSpec``.  Single-process specs (no coordinator in
    env) skip distributed init entirely, so local/dev runs pay nothing.
    """
    from dlrover_tpu.runtime import (
        bootstrap_world,
        check_world_consistency,
        world_barrier,
    )

    spec = bootstrap_world(spec)
    if spec.is_multiprocess:
        world_barrier(
            f"bootstrap/{spec.restart_count}", spec, timeout_s=120.0
        )
        check_world_consistency(spec, timeout_s=120.0)
    return spec


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m dlrover_tpu.launch.worker train.py [args...]`` —
    bootstrap the world, then run the training script as ``__main__``."""
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        raise SystemExit(
            "usage: python -m dlrover_tpu.launch.worker <script.py> [args]"
        )
    script, script_args = argv[0], argv[1:]
    from dlrover_tpu.telemetry import events as tevents

    tevents.emit("process_start", entrypoint=os.path.basename(script))
    from dlrover_tpu.common.platform import configure_compile_cache

    # A restarted incarnation must find what its predecessor compiled.
    configure_compile_cache()
    spec = bootstrap()
    tevents.emit(
        "world_init",
        num_processes=spec.num_processes,
        process_id=spec.process_id,
    )
    from dlrover_tpu.common.preemption import (
        install_preemption_handler,
        install_stack_dump_handler,
    )

    # SIGUSR1 -> faulthandler traceback of every thread: the agent's hang
    # watchdog uses this for the "where is it stuck" stage of escalation.
    install_stack_dump_handler()
    # SIGTERM -> run grace callbacks (the trainer registers its flash-
    # checkpoint flush via preemption.register_grace_callback), tell the
    # master this host is dying, exit 143.
    try:
        from dlrover_tpu.agent.master_client import MasterClient

        _client = (
            MasterClient.singleton_instance()
            if os.getenv(NodeEnv.MASTER_ADDR)
            else None
        )
    except Exception:  # noqa: BLE001 — grace must not block startup
        _client = None
    install_preemption_handler(
        master_client=_client, node_rank=spec.node_rank
    )
    logger.info(
        "worker process %s/%s bootstrapped; running %s",
        spec.process_id, spec.num_processes, script,
    )
    sys.argv = [script, *script_args]
    code = 1
    try:
        runpy.run_path(script, run_name="__main__")
        code = 0
        return 0
    finally:
        tevents.emit("exit", code=code)
        from dlrover_tpu.runtime import shutdown_world

        shutdown_world()


if __name__ == "__main__":
    sys.exit(main())
