"""``tpurun`` — the elastic launcher CLI (torchrun-analog for JAX/TPU).

Reference parity: ``dlrover/trainer/torch/elastic_run.py`` (parse_args:124,
elastic_launch:182, _launch_dlrover_local_master:230, run:322).  Same
contract: a superset launcher that (a) forks an in-process local master on
the first node when no managed master exists, (b) wires the MasterClient,
and (c) hands off to the elastic agent which supervises the real training
processes.  ``tpurun --network-check --node_unit 4 train.py ...``.
"""

import argparse
import os
import socket
import sys
import time
from typing import List, Optional, Tuple

from dlrover_tpu.agent.master_client import MasterClient, build_master_client
from dlrover_tpu.agent.training_agent import (
    ElasticLaunchConfig,
    WorkerState,
    launch_agent,
)
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import logger


def parse_args(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(
        prog="tpurun",
        description="Elastic JAX/TPU launcher with master-backed "
        "fault tolerance",
    )
    p.add_argument("--nnodes", type=str, default="1",
                   help="N or MIN:MAX node range")
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--node_rank", type=int,
                   default=int(os.getenv(NodeEnv.NODE_RANK, "0")))
    # Default None (not the env value) so run() can tell a CLI-supplied
    # address apart from an env-provided one even when both are set.
    p.add_argument("--master-addr", type=str, default=None,
                   help="dlrover master addr; absent => fork local master")
    p.add_argument("--network-check", action="store_true",
                   help="run pre-flight node health checks")
    p.add_argument("--exclude-straggler", action="store_true")
    p.add_argument("--node_unit", type=int, default=1,
                   help="admitted world is rounded to a multiple of this")
    p.add_argument("--auto-config", action="store_true",
                   help="derive node counts from scheduler env")
    p.add_argument("--auto-tunning", "--auto-tuning", dest="auto_tunning",
                   action="store_true",
                   help="poll the master's parallel-config auto-tuner "
                   "(dataloader batch size / workers) into the trainer "
                   "at runtime")
    p.add_argument("--save_at_breakpoint", action="store_true",
                   help="persist shm checkpoint before worker restarts")
    p.add_argument("--hot-standby", action="store_true",
                   help="pre-warm the next worker incarnation so failure "
                   "recovery skips imports/compile (single-node)")
    p.add_argument("--max-restarts", type=int, default=3)
    p.add_argument("--rdzv-timeout", type=float, default=600)
    p.add_argument("--monitor-interval", type=float, default=3.0)
    p.add_argument("--log-dir", type=str, default="")
    p.add_argument("--accelerator", type=str, default="tpu",
                   choices=["tpu", "cpu"])
    p.add_argument("--no-world-bootstrap", action="store_true",
                   help="spawn the training script directly instead of "
                   "through the world-bootstrap wrapper (the script must "
                   "then call jax.distributed.initialize itself)")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _parse_nnodes(spec: str) -> Tuple[int, int]:
    if ":" in spec:
        lo, hi = spec.split(":")
        return int(lo), int(hi)
    n = int(spec)
    return n, n


def _master_reachable(addr: str, timeout: float = 3.0) -> bool:
    """Reference ``_check_to_use_dlrover_run:306`` (TCP connect probe)."""
    try:
        host, port = addr.rsplit(":", 1)
        with socket.create_connection((host, int(port)), timeout=timeout):
            return True
    except (OSError, ValueError):
        return False


def _launch_local_master(node_num: int):
    """Reference ``_launch_dlrover_local_master:230``: rank-0 embeds a
    LocalJobMaster thread instead of forking a separate process — same
    isolation boundary as the reference's subprocess (agents still talk to
    it over localhost RPC) with less supervision machinery."""
    from dlrover_tpu.master.local_master import start_local_master

    master = start_local_master(port=0, node_num=node_num)
    logger.info("local master listening at %s", master.addr)
    return master


def _config_from_args(args) -> ElasticLaunchConfig:
    min_nodes, max_nodes = _parse_nnodes(args.nnodes)
    return ElasticLaunchConfig(
        min_nodes=min_nodes,
        max_nodes=max_nodes,
        nproc_per_node=args.nproc_per_node,
        node_rank=args.node_rank,
        node_id=args.node_rank,
        rdzv_timeout=args.rdzv_timeout,
        node_unit=args.node_unit,
        max_restarts=args.max_restarts,
        monitor_interval=args.monitor_interval,
        network_check=args.network_check,
        exclude_straggler=args.exclude_straggler,
        save_at_breakpoint=args.save_at_breakpoint,
        auto_config=args.auto_config,
        auto_tunning=args.auto_tunning,
        accelerator=args.accelerator,
        log_dir=args.log_dir,
        hot_standby=args.hot_standby,
    )


def run(args) -> WorkerState:
    config = _config_from_args(args)  # refuses bad layouts before any start
    master = None
    explicit = args.master_addr is not None
    master_addr = (
        args.master_addr
        if explicit
        else os.getenv(NodeEnv.MASTER_ADDR, "")
    )
    if master_addr and not _master_reachable(master_addr):
        if explicit or args.node_rank != 0:
            # An explicitly requested master that never comes up is fatal:
            # silently falling back to a private local master would split-
            # brain a multi-node job. Retry for a grace period first.
            deadline = time.time() + 60
            while time.time() < deadline:
                if _master_reachable(master_addr):
                    break
                time.sleep(2)
            else:
                raise RuntimeError(
                    f"master {master_addr} unreachable after 60s"
                )
        else:
            logger.warning(
                "env-provided master %s unreachable; falling back to a "
                "local master", master_addr,
            )
            master_addr = ""
    if not master_addr:
        if args.node_rank != 0:
            raise RuntimeError(
                "no master address and not node rank 0; in multi-node "
                "standalone mode point --master-addr at rank 0's master"
            )
        min_nodes, max_nodes = _parse_nnodes(args.nnodes)
        if max_nodes == 1:
            # Auth-by-default, but ONLY single-node standalone: generate
            # a job token before the transport starts; workers inherit
            # it via env.  Multi-node standalone cannot self-generate —
            # other nodes would have no way to learn the secret and
            # every RPC of theirs would be rejected; they must share
            # DLROVER_JOB_TOKEN via the scheduler env.
            import uuid as _uuid

            from dlrover_tpu.rpc.transport import TOKEN_ENV

            os.environ.setdefault(TOKEN_ENV, _uuid.uuid4().hex)
        master = _launch_local_master(min_nodes)
        master_addr = master.addr
    os.environ[NodeEnv.MASTER_ADDR] = master_addr

    client = build_master_client(
        master_addr, node_id=args.node_rank, node_type="worker"
    )
    if args.no_world_bootstrap:
        entrypoint = [sys.executable, args.training_script]
    else:
        # Spawn through the bootstrap wrapper: every worker process
        # consumes the NodeEnv triple (jax.distributed.initialize +
        # barrier + consistency check) BEFORE user code runs — the
        # rendezvous result becomes a live distributed world.
        entrypoint = [
            sys.executable, "-m", "dlrover_tpu.launch.worker",
            args.training_script,
        ]
    entrypoint += list(args.training_script_args or [])
    config.manage_world_bootstrap = not args.no_world_bootstrap
    # Namespace the job's IPC (flash-checkpoint factory queue, shm locks)
    # by run id: two jobs co-hosted on one machine must never unlink each
    # other's sockets (multi_process._sock_path reads this env).
    os.environ.setdefault("DLROVER_JOB_UID", config.run_id)
    try:
        return launch_agent(config, entrypoint, client=client)
    finally:
        if master is not None:
            master.stop()
        MasterClient._reset_singleton()


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    state = run(args)
    return 0 if state == WorkerState.SUCCEEDED else 1


if __name__ == "__main__":
    sys.exit(main())
