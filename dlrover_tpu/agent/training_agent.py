"""Per-node elastic training agent.

Reference parity: ``dlrover/python/elastic_agent/torch/training.py``
(ElasticLaunchConfig:112, MasterRendezvousHandler:170,
ElasticTrainingAgent:350 with _invoke_run:551 / _restart_workers:675 /
_membership_changed:682, NodeCheckElasticAgent:816, launch_agent:705).

TPU re-design: torch-elastic's C10d store + process-group bootstrap is
replaced by the JAX distributed triple — the rendezvous produces a world
``{node_rank: local_world_size}`` from the master, rank 0 publishes a
coordinator address through the master KV store, and every worker process
receives ``(coordinator, num_processes, process_id)`` through the
``NodeEnv`` contract so it can call ``jax.distributed.initialize``.  A JAX
process cannot drop out of a compiled SPMD program, so elasticity is
restart-world-and-resume: on failure or membership change the agent kills
worker processes, re-rendezvouses (node_unit-rounded world), and respawns;
workers resume from the Flash Checkpoint shm/storage state.
"""

import os
import signal
import subprocess
import sys
import time
import uuid
from dataclasses import dataclass, field
from enum import Enum
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common.constants import (
    DefaultValues,
    JobConstant,
    NodeEnv,
    NodeExitReason,
    RendezvousName,
    TrainingExceptionLevel,
)
from dlrover_tpu.common.log import logger


class WorkerState(str, Enum):
    INIT = "INIT"
    HEALTHY = "HEALTHY"
    FAILED = "FAILED"
    SUCCEEDED = "SUCCEEDED"
    STOPPED = "STOPPED"


# Exit codes classified as machine trouble: the node itself should be
# replaced, not just the process restarted (reference training.py:357-361).
HARDWARE_ERROR_CODES = {-signal.SIGBUS, -signal.SIGSEGV, 134}


@dataclass
class ElasticLaunchConfig:
    """Launch configuration (reference ElasticLaunchConfig:112)."""

    min_nodes: int = 1
    max_nodes: int = 1
    nproc_per_node: int = 1
    node_rank: int = 0
    node_id: int = 0
    rdzv_timeout: float = 600.0
    waiting_timeout: float = 5.0
    node_unit: int = 1
    max_restarts: int = 3
    monitor_interval: float = 3.0
    # Heartbeat + CPU/mem/TPU usage report period (0 disables the monitor).
    resource_monitor_interval: float = 15.0
    network_check: bool = False
    exclude_straggler: bool = False
    save_at_breakpoint: bool = False
    auto_config: bool = False
    # Master-driven runtime tuning (reference --auto_tunning): run the
    # ParalConfigTuner thread so the master's ParallelConfig reaches the
    # trainer's dataloader through the well-known JSON file.
    auto_tunning: bool = False
    accelerator: str = "tpu"
    log_dir: str = ""
    # Warm-standby worker: pre-spawn the next incarnation so recovery
    # skips imports/compile (agent/standby.py).  Single-node worlds only.
    hot_standby: bool = False
    # After a promotion, wait this long before re-warming the next
    # standby: its boot (imports + compile) competes for host CPU with
    # the just-promoted worker's first steps.
    standby_respawn_delay: float = 10.0
    # Workers are spawned through the world-bootstrap wrapper
    # (launch/worker.py main): the agent then VERIFIES the published
    # triple was consumed — coordinator endpoint live = worker 0 called
    # jax.distributed.initialize — and restarts the world if it never
    # forms within world_bootstrap_timeout.
    manage_world_bootstrap: bool = False
    world_bootstrap_timeout: float = 300.0
    # Hang/straggler watchdog: workers publish per-step progress files
    # (agent/monitor/progress.py); the agent escalates a stalled step as
    # warn -> stack-dump signal -> restart-world (agent/watchdog.py).
    hang_watchdog: bool = False
    hang_warn_after: float = DefaultValues.HANG_WARN_AFTER
    hang_dump_after: float = DefaultValues.HANG_DUMP_AFTER
    hang_restart_after: float = DefaultValues.HANG_RESTART_AFTER
    # SIGTERM grace: flush the flash checkpoint and deregister from the
    # master before the preemption deadline (common/preemption.py).
    preemption_grace: bool = True
    # Debug bundles: on worker crash / watchdog restart / nonzero job
    # exit, archive event logs + log tails + goodput + env fingerprint
    # into bundle_<run>_<attempt>.tar.gz (telemetry/bundle.py).
    debug_bundles: bool = True
    bundle_dir: str = ""  # default: the run's telemetry dir
    run_id: str = field(default_factory=lambda: uuid.uuid4().hex[:8])

    def __post_init__(self):
        if self.accelerator == "tpu" and self.nproc_per_node > 1:
            # Nothing gives a worker its own chips: every process would
            # see all of them, and a chip belongs to one process.
            raise ValueError(
                f"--accelerator tpu with --nproc_per_node "
                f"{self.nproc_per_node}: one worker process drives all "
                f"local chips (its mesh spans them); use "
                f"--nproc_per_node 1"
            )

    def auto_configure_from_env(self):
        """Fill node counts from the scheduler-provided env (reference
        ``training.py:144``): under a managed job the operator exports
        NODE_NUM; standalone defaults to a single node."""
        if self.auto_config:
            num = int(os.getenv(NodeEnv.NODE_NUM, "1"))
            self.min_nodes = self.max_nodes = num


class RendezvousOutcome:
    """The resolved world of one rendezvous round."""

    def __init__(
        self,
        rdzv_round: int,
        world: Dict[int, int],
        node_rank: int,
    ):
        self.round = rdzv_round
        # Preserve the master's dict order verbatim: it IS the topology-
        # aware rank order (same-slice hosts contiguous; see
        # master/elastic_training/net_topology.py) — re-sorting by node
        # rank would undo it and push collectives onto DCN.
        self.world = dict(world)
        self.node_rank = node_rank

    @property
    def num_nodes(self) -> int:
        return len(self.world)

    @property
    def world_size(self) -> int:
        return sum(self.world.values())

    @property
    def rank_offset(self) -> int:
        """Global rank of this node's first local worker."""
        offset = 0
        for r, lws in self.world.items():
            if r == self.node_rank:
                return offset
            offset += lws
        raise RuntimeError(
            f"node rank {self.node_rank} not in world {self.world}"
        )


class MasterRendezvousHandler:
    """Agent side of the master rendezvous (reference :170).

    ``next_rendezvous`` joins the master's waiting set then polls
    ``get_comm_world`` until the round completes; the master applies
    min/max/timeout/node_unit policy (rdzv_manager.py analog).
    """

    def __init__(
        self,
        name: str,
        node_rank: int,
        local_world_size: int,
        client: MasterClient,
        join_timeout: float = JobConstant.RDZV_JOIN_TIMEOUT_DEFAULT,
        poll_interval: float = 0.2,
    ):
        self._name = name
        self._node_rank = node_rank
        self._local_world_size = local_world_size
        self._client = client
        self._join_timeout = join_timeout
        self._poll_interval = poll_interval

    @staticmethod
    def _annotated_ip() -> str:
        """ip[@slice[@pod]] — the topology hint EnvTopologyQuerier reads
        master-side (slice id from the multislice runtime env)."""
        ip = _host_ip()
        slice_id = os.getenv(
            "MEGASCALE_SLICE_ID", os.getenv("DLROVER_SLICE_ID", "")
        )
        return f"{ip}@{slice_id}" if slice_id else ip

    def next_rendezvous(self) -> RendezvousOutcome:
        start = time.time()
        self._client.join_rendezvous(
            self._node_rank, self._local_world_size, self._name,
            node_ip=self._annotated_ip(),
        )
        while True:
            rdzv_round, world = self._client.get_comm_world(
                self._name, self._node_rank
            )
            if world:
                if self._node_rank not in world:
                    # Rounded out by node_unit policy; wait for next round.
                    logger.info(
                        "node %s not admitted in round %s; re-joining",
                        self._node_rank, rdzv_round,
                    )
                    self._client.join_rendezvous(
                        self._node_rank, self._local_world_size, self._name,
                        node_ip=self._annotated_ip(),
                    )
                else:
                    return RendezvousOutcome(
                        rdzv_round, world, self._node_rank
                    )
            if time.time() - start > self._join_timeout:
                raise TimeoutError(
                    f"rendezvous {self._name} timed out after "
                    f"{self._join_timeout}s (world={world})"
                )
            time.sleep(self._poll_interval)

    def num_nodes_waiting(self) -> int:
        return self._client.num_nodes_waiting(self._name)


class WorkerProcess:
    def __init__(
        self, local_rank: int, proc: subprocess.Popen, log_handle=None
    ):
        self.local_rank = local_rank
        self.proc = proc
        self.log_handle = log_handle

    def poll(self) -> Optional[int]:
        return self.proc.poll()

    def close_log(self):
        if self.log_handle is not None:
            try:
                self.log_handle.close()
            except OSError:
                pass
            self.log_handle = None


class WorkerGroup:
    """Local worker subprocesses of one agent (one per local chip-group)."""

    def __init__(self):
        self.workers: List[WorkerProcess] = []
        self.state = WorkerState.INIT
        self.restart_count = 0

    def spawn(
        self,
        entrypoint: List[str],
        base_env: Dict[str, str],
        nproc: int,
        rank_offset: int,
        log_dir: str = "",
    ):
        self.workers = []
        for local_rank in range(nproc):
            env = dict(base_env)
            env[NodeEnv.PROCESS_ID] = str(rank_offset + local_rank)
            env[NodeEnv.LOCAL_PROCESS_ID] = str(local_rank)
            stdout = stderr = None
            if log_dir:
                os.makedirs(log_dir, exist_ok=True)
                path = os.path.join(log_dir, f"worker_{local_rank}.log")
                stdout = open(path, "ab")  # noqa: SIM115 — proc lifetime
                stderr = subprocess.STDOUT
            proc = subprocess.Popen(  # noqa: S603 — the training command
                entrypoint,
                env=env,
                stdout=stdout,
                stderr=stderr,
                start_new_session=True,
            )
            self.workers.append(
                WorkerProcess(
                    local_rank, proc,
                    log_handle=stdout if log_dir else None,
                )
            )
        self.state = WorkerState.HEALTHY

    def monitor(self) -> Tuple[WorkerState, Dict[int, int]]:
        """Poll workers; return (state, {local_rank: exitcode} for exited)."""
        if not self.workers:
            return self.state, {}
        exited: Dict[int, int] = {}
        for w in self.workers:
            code = w.poll()
            if code is not None:
                exited[w.local_rank] = code
        if not exited:
            return WorkerState.HEALTHY, {}
        if any(code != 0 for code in exited.values()):
            return WorkerState.FAILED, exited
        if len(exited) == len(self.workers):
            return WorkerState.SUCCEEDED, exited
        return WorkerState.HEALTHY, exited

    def stop(self, timeout: float = 10.0):
        for w in self.workers:
            if w.poll() is None:
                try:
                    os.killpg(os.getpgid(w.proc.pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        deadline = time.time() + timeout
        for w in self.workers:
            remain = max(0.1, deadline - time.time())
            try:
                w.proc.wait(timeout=remain)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(w.proc.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                w.proc.wait()
        for w in self.workers:
            w.close_log()
        self.state = WorkerState.STOPPED


class ElasticTrainingAgent:
    """Supervision loop for one node's workers (reference :350).

    Lifecycle per incarnation: rendezvous → publish/fetch coordinator →
    spawn workers with the JAX env triple → monitor; on FAILED report to
    master, optionally persist the shm checkpoint, and restart; on a
    membership change (num_nodes_waiting > 0) restart into the new world.
    """

    def __init__(
        self,
        config: ElasticLaunchConfig,
        entrypoint: List[str],
        client: MasterClient,
        coordinator_port: int = 0,
        ckpt_saver=None,
    ):
        self._config = config
        self._entrypoint = entrypoint
        self._client = client
        self._coordinator_port = coordinator_port
        self._ckpt_saver = ckpt_saver
        self._rdzv_handler = MasterRendezvousHandler(
            RendezvousName.TRAINING,
            config.node_rank,
            config.nproc_per_node,
            client,
            join_timeout=config.rdzv_timeout,
        )
        self._worker_group = WorkerGroup()
        self._remaining_restarts = config.max_restarts
        self._stopped = False
        self._last_outcome: Optional[RendezvousOutcome] = None
        import threading as _threading

        self._standby = None
        self._standby_timer = None
        self._standby_log = None
        self._standby_deaths = 0
        self._coordinator = ""
        self._election = None
        # World-bootstrap verification (consume, don't just publish):
        # armed at spawn for multi-process worlds, cleared once the
        # coordinator endpoint goes live.
        self._world_verified = True
        self._world_verify_deadline = 0.0
        # Serializes spawn/stop/promote across the monitor loop and the
        # delayed-respawn timer thread (double-spawn would leak a parked
        # jax process on a dead fifo).
        self._standby_lock = _threading.Lock()
        if config.hot_standby:
            from dlrover_tpu.agent.standby import StandbyManager

            self._standby = StandbyManager(
                os.path.join(
                    "/tmp", f"dlrover_standby_{config.run_id}"
                )
            )
        self._resource_monitor = None
        self._paral_tuner = None
        if config.resource_monitor_interval > 0:
            from dlrover_tpu.agent.monitor import resource as res_mon

            # Namespace the chip-metrics dir by run id so co-hosted jobs
            # never merge (or clear) each other's snapshots.  Exported to
            # os.environ so spawned workers inherit the same directory.
            os.environ.setdefault(
                "DLROVER_TPU_METRICS_DIR",
                os.path.join(
                    res_mon.DEFAULT_METRICS_DIR, config.run_id
                ),
            )
            self._resource_monitor = res_mon.ResourceMonitor(
                client=client, interval=config.resource_monitor_interval
            )
        # Telemetry: same run-id namespacing as the chip-metrics dir so
        # co-hosted jobs keep separate event logs; workers inherit the
        # directory through os.environ.  The agent's own events go to an
        # "agent" stream (visible in the trace, excluded from goodput).
        from dlrover_tpu.telemetry import events as tevents

        os.environ.setdefault(
            tevents.ENV_TELEMETRY_DIR,
            os.path.join(tevents.DEFAULT_TELEMETRY_DIR, config.run_id),
        )
        tevents.configure(role="agent", rank=config.node_id)
        self._event_shipper = tevents.EventShipper(
            tevents.telemetry_dir()
        )
        self._last_ship = 0.0
        self._last_bundle = 0.0
        self._watchdog = None
        if config.hang_watchdog:
            from dlrover_tpu.agent.watchdog import HangWatchdog

            self._watchdog = HangWatchdog(
                warn_after=config.hang_warn_after,
                dump_after=config.hang_dump_after,
                restart_after=config.hang_restart_after,
            )

    # -- world bootstrap ---------------------------------------------------
    def _resolve_coordinator(self, outcome: RendezvousOutcome) -> str:
        """Elect the coordinator endpoint for this round through the
        master KV store (the single source of truth that survives node
        loss): the first admitted node publishes ``ip:port``, everyone
        else polls; on host loss the next rank re-elects under a bumped
        epoch (runtime/coordinator.py)."""
        from dlrover_tpu.runtime.coordinator import CoordinatorElection

        self._election = CoordinatorElection(
            self._client,
            self._config.run_id,
            outcome.round,
            outcome.world,
            outcome.node_rank,
            port=self._coordinator_port,
            timeout_s=self._config.rdzv_timeout,
            rdzv_name=RendezvousName.TRAINING,
        )
        addr, epoch = self._election.resolve()
        if epoch > 0:
            logger.warning(
                "joined re-elected coordinator %s (epoch %s)", addr, epoch
            )
        return addr

    def _worker_env(self, outcome: RendezvousOutcome, coordinator: str):
        env = dict(os.environ)
        env.update(
            {
                NodeEnv.NODE_ID: str(self._config.node_id),
                NodeEnv.NODE_RANK: str(outcome.node_rank),
                NodeEnv.NODE_NUM: str(outcome.num_nodes),
                NodeEnv.COORDINATOR_ADDR: coordinator,
                NodeEnv.NUM_PROCESSES: str(outcome.world_size),
                NodeEnv.LOCAL_NUM_PROCESSES: str(
                    outcome.world[outcome.node_rank]
                ),
                NodeEnv.RESTART_COUNT: str(
                    self._worker_group.restart_count
                ),
                NodeEnv.MASTER_ADDR: getattr(self._client, "_addr", ""),
            }
        )
        if self._config.accelerator == "cpu":
            # CPU mode (tests / local dry runs): keep workers off the TPU
            # runtime so they start fast and never contend for chips.
            env["JAX_PLATFORMS"] = "cpu"
        return env

    # -- lifecycle ---------------------------------------------------------
    def _initialize_workers(self):
        if self._resource_monitor:
            # Snapshots from previous worker pids must not double-count.
            from dlrover_tpu.agent.monitor.resource import clear_tpu_metrics

            clear_tpu_metrics()
        if self._watchdog is not None:
            # Stale progress files from dead pids would mask a hang in
            # the fresh incarnation (or report phantom progress).
            from dlrover_tpu.agent.monitor.progress import clear_progress

            clear_progress()
            self._watchdog.reset()
        outcome = self._rdzv_handler.next_rendezvous()
        self._last_outcome = outcome
        from dlrover_tpu.telemetry import events as tevents

        tevents.emit(
            "rendezvous",
            round=outcome.round,
            world_size=outcome.world_size,
            num_nodes=outcome.num_nodes,
        )
        coordinator = self._resolve_coordinator(outcome)
        self._coordinator = coordinator  # standby spawns reuse it
        env = self._worker_env(outcome, coordinator)
        log_dir = ""
        if self._config.log_dir:
            log_dir = os.path.join(
                self._config.log_dir,
                f"node_{outcome.node_rank}_restart_"
                f"{self._worker_group.restart_count}",
            )
        self._worker_group.spawn(
            self._entrypoint,
            env,
            outcome.world[outcome.node_rank],
            outcome.rank_offset,
            log_dir=log_dir,
        )
        logger.info(
            "node %s started %s workers (round %s, world_size %s, "
            "coordinator %s)",
            outcome.node_rank,
            outcome.world[outcome.node_rank],
            outcome.round,
            outcome.world_size,
            coordinator,
        )
        # Arm the bootstrap watchdog: a multi-process world is only real
        # once worker process 0 binds the coordinator port by calling
        # jax.distributed.initialize.
        self._world_verified = not (
            self._config.manage_world_bootstrap and outcome.world_size > 1
        )
        self._world_verify_deadline = (
            time.time() + self._config.world_bootstrap_timeout
        )

    def _check_world_formed(self) -> bool:
        """Monitor-loop tick of the bootstrap watchdog.  Returns False
        when the world failed to form in time (caller restarts)."""
        if self._world_verified:
            return True
        from dlrover_tpu.runtime.coordinator import probe

        if probe(self._coordinator, timeout_s=1.0):
            self._world_verified = True
            logger.info(
                "distributed world formed: coordinator %s is live",
                self._coordinator,
            )
            return True
        if time.time() > self._world_verify_deadline:
            logger.error(
                "world never formed: coordinator %s not live within %ss",
                self._coordinator,
                self._config.world_bootstrap_timeout,
            )
            return False
        return True

    def _standby_supported(self) -> bool:
        """Warm standby replaces a dead worker WITHOUT re-rendezvous, so
        it is only sound when the world cannot change shape under it:
        one node, one worker process."""
        return (
            self._standby is not None
            and self._last_outcome is not None
            and self._last_outcome.num_nodes == 1
            and self._config.nproc_per_node == 1
        )

    # Disable the standby after this many consecutive warmup deaths —
    # a standby that cannot boot must not burn a CPU core re-importing
    # jax every monitor tick.
    _MAX_STANDBY_DEATHS = 3

    def _spawn_standby(self):
        with self._standby_lock:
            self._spawn_standby_locked()

    def _spawn_standby_locked(self):
        if not self._standby_supported():
            return
        if self._standby_deaths >= self._MAX_STANDBY_DEATHS:
            return
        outcome = self._last_outcome
        env = self._worker_env(outcome, self._coordinator)
        env[NodeEnv.PROCESS_ID] = str(outcome.rank_offset)
        env[NodeEnv.LOCAL_PROCESS_ID] = "0"

        def spawn_fn(entrypoint, senv):
            stdout = stderr = None
            if self._config.log_dir:
                sdir = os.path.join(self._config.log_dir, "standby")
                os.makedirs(sdir, exist_ok=True)
                if self._standby_log is not None:
                    try:
                        self._standby_log.close()
                    except OSError:
                        pass
                stdout = open(  # noqa: SIM115 — proc lifetime
                    os.path.join(sdir, "standby.log"), "ab"
                )
                self._standby_log = stdout
                stderr = subprocess.STDOUT

            def _deprioritize():
                # Warmup (imports + XLA compile) must not steal cycles
                # from the ACTIVE worker's training steps.
                try:
                    os.nice(10)
                except OSError:
                    pass

            return subprocess.Popen(  # noqa: S603 — the training command
                entrypoint, env=senv, stdout=stdout, stderr=stderr,
                start_new_session=True, preexec_fn=_deprioritize,
            )

        # Deliberate hold: Popen returns in milliseconds (the slow
        # warmup happens in the child), and _standby_lock is exactly
        # what makes spawn/promote/teardown mutually exclusive — a
        # promote must never observe a half-spawned standby.
        self._standby.spawn(self._entrypoint, env, spawn_fn)  # dlr: lock-held
        logger.info("warm standby spawned")

    def _promote_standby(self) -> bool:
        """Swap a ready standby in for the dead worker.  Returns False
        when no warm standby is available (caller falls back to the cold
        restart path)."""
        if not self._standby_supported() or not self._standby.ready():
            return False
        self._worker_group.stop(timeout=2)
        with self._standby_lock:
            proc = self._standby.activate(
                {
                    "restart_count": self._worker_group.restart_count + 1,
                    "env": {
                        NodeEnv.RESTART_COUNT: str(
                            self._worker_group.restart_count + 1
                        ),
                    },
                }
            )
        if proc is None:
            logger.warning(
                "standby died between ready() and activation; falling "
                "back to cold restart"
            )
            return False
        self._worker_group.restart_count += 1
        self._worker_group.workers = [WorkerProcess(0, proc)]
        self._worker_group.state = WorkerState.HEALTHY
        self._standby_deaths = 0  # a working standby resets the fuse
        try:
            # The standby ran nice'd; the ACTIVE worker must not.  The
            # worker also tries from its side — whichever has the
            # privilege wins (raising priority needs CAP_SYS_NICE).
            os.setpriority(os.PRIO_PROCESS, proc.pid, 0)
        except (OSError, AttributeError):
            logger.warning(
                "cannot restore promoted worker priority (CAP_SYS_NICE "
                "missing); it stays at nice 10 — standby warmups will "
                "compete with it equally"
            )
        logger.info(
            "promoted warm standby (restart %s) — cold start skipped",
            self._worker_group.restart_count,
        )
        from dlrover_tpu.telemetry import events as tevents

        tevents.emit(
            "reform",
            restart_count=self._worker_group.restart_count,
            standby=True,
        )
        # Re-warm the NEXT standby after a grace delay so its boot does
        # not contend with the promoted worker's first steps.  (A second
        # failure inside the delay falls back to the cold-restart path.)
        import threading

        def _respawn_later():
            # A cold restart in the meantime may already have re-warmed
            # one (double-failure inside the delay) — don't leak it.
            with self._standby_lock:
                if not self._stopped and self._standby.vacant():
                    self._spawn_standby_locked()

        if self._standby_timer is not None:
            self._standby_timer.cancel()
        self._standby_timer = threading.Timer(
            max(self._config.standby_respawn_delay, 0.0), _respawn_later
        )
        self._standby_timer.daemon = True
        self._standby_timer.start()
        return True

    def _membership_changed(self) -> bool:
        """New nodes are waiting to join → restart into a bigger world
        (reference :682)."""
        try:
            return self._rdzv_handler.num_nodes_waiting() > 0
        except Exception:  # noqa: BLE001 — master briefly unreachable
            return False

    def _restart_workers(self):
        from dlrover_tpu.telemetry import events as tevents

        tevents.emit(
            "reform", restart_count=self._worker_group.restart_count + 1
        )
        self._worker_group.stop()
        self._worker_group.restart_count += 1
        self._initialize_workers()
        if self._standby is not None:
            # The old standby's spawn-time world env may be stale after a
            # re-rendezvous; warm a fresh one for the new world.
            with self._standby_lock:
                self._standby.stop()
                self._spawn_standby_locked()

    def _report_failure(self, exited: Dict[int, int]):
        from dlrover_tpu.telemetry import events as tevents

        tevents.emit(
            "exit",
            codes={str(r): c for r, c in exited.items()},
            restart_count=self._worker_group.restart_count,
        )
        err = ";".join(f"local_rank {r}: exit {c}" for r, c in exited.items())
        level = (
            TrainingExceptionLevel.NODE_ERROR
            if any(c in HARDWARE_ERROR_CODES for c in exited.values())
            else TrainingExceptionLevel.PROCESS_ERROR
        )
        # Attach WHY: log failure signatures + last chip metrics so the
        # master's diagnosis sees the root cause, not just the exit code.
        try:
            import json as _json

            from dlrover_tpu.agent.datacollector import (
                collect_failure_context,
            )

            context = collect_failure_context(self._config.log_dir)
            if context:
                err = f"{err} | context: {_json.dumps(context)[:2000]}"
        except Exception:  # noqa: BLE001 - diagnosis data is best-effort
            pass
        try:
            self._client.report_failure(
                err,
                restart_count=self._worker_group.restart_count,
                level=level,
            )
        except Exception:  # noqa: BLE001
            logger.warning("could not report failure to master: %s", err)
        self._collect_debug_bundle("worker_crash")

    # Minimum seconds between bundle captures: a crash storm must not
    # turn the agent into a tar factory; successive captures of the same
    # attempt overwrite one bundle file anyway.
    _BUNDLE_MIN_INTERVAL = 10.0

    def _collect_debug_bundle(self, reason: str):
        """Best-effort crash-bundle capture; throttled, never raises."""
        if not self._config.debug_bundles:
            return None
        now = time.time()
        if now - self._last_bundle < self._BUNDLE_MIN_INTERVAL:
            return None
        self._last_bundle = now
        try:
            import glob as _glob

            from dlrover_tpu.telemetry import bundle as _bundle
            from dlrover_tpu.telemetry import events as tevents
            from dlrover_tpu.telemetry import httpd as _httpd

            log_paths = []
            if self._config.log_dir:
                log_paths = sorted(
                    _glob.glob(
                        os.path.join(self._config.log_dir, "**", "*.log"),
                        recursive=True,
                    )
                )
            return _bundle.collect_bundle(
                reason=reason,
                out_dir=(
                    self._config.bundle_dir or tevents.telemetry_dir()
                ),
                telemetry_dir=tevents.telemetry_dir(),
                log_paths=log_paths,
                goodput=_httpd.last_goodput() or None,
                run_id=self._config.run_id,
                attempt=self._worker_group.restart_count,
            )
        except Exception:  # noqa: BLE001 — crash handlers don't crash
            logger.warning("debug bundle hook failed", exc_info=True)
            return None

    # Minimum seconds between telemetry ship RPCs — the monitor loop may
    # tick sub-second, but event volume is step-dominated and the master
    # recomputes attribution per /goodput.json hit, not per batch.
    _SHIP_MIN_INTERVAL = 2.0

    def _ship_telemetry(self, force: bool = False):
        """Drain new telemetry events (this agent's + its workers') to
        the master's goodput accountant; throttled, never raises."""
        now = time.time()
        if not force and now - self._last_ship < self._SHIP_MIN_INTERVAL:
            return
        self._last_ship = now
        from dlrover_tpu.telemetry import events as tevents

        try:
            tevents.ship_events(self._event_shipper, self._client)
        except Exception:  # noqa: BLE001 — telemetry must never kill us
            logger.warning("telemetry ship tick failed", exc_info=True)

    def _save_shm_at_breakpoint(self):
        """Persist the latest shm checkpoint before a restart (reference
        ``_save_ckpt_to_storage:636``) so no training progress is lost."""
        saver = self._ckpt_saver
        if saver is None:
            from dlrover_tpu.checkpoint.ckpt_saver import (
                AsyncCheckpointSaver,
            )

            saver = AsyncCheckpointSaver.get_ckpt_saver()
        if saver is not None:
            try:
                saver.save_shm_to_storage()
            except Exception as e:  # noqa: BLE001
                logger.warning("breakpoint shm save failed: %s", e)

    def run(self) -> WorkerState:
        """The supervision loop (reference ``_invoke_run:551``).

        Rendezvous failures (e.g. peers hung in a collective never re-join)
        surface as a clean FAILED result, never an agent crash — ``tpurun``'s
        exit-code contract depends on it.
        """
        try:
            if self._resource_monitor:
                self._resource_monitor.start()
            if self._config.auto_tunning:
                # Start BEFORE worker spawn: the tuner exports the config
                # path env, which _worker_env snapshots for the workers.
                from dlrover_tpu.agent.config.paral_config_tuner import (
                    ParalConfigTuner,
                )

                from dlrover_tpu.common.constants import ConfigPath

                self._paral_tuner = ParalConfigTuner(
                    client=self._client,
                    config_path=os.path.join(
                        os.path.dirname(ConfigPath.PARAL_CONFIG),
                        f"paral_config_{self._config.run_id}.json",
                    ),
                )
                self._paral_tuner.start()
                logger.info(
                    "auto-tunning on: ParalConfigTuner -> %s",
                    self._paral_tuner.config_path,
                )
            self._initialize_workers()
            self._spawn_standby()
            while not self._stopped:
                time.sleep(self._config.monitor_interval)
                self._ship_telemetry()
                action = ""
                if self._resource_monitor:
                    action = self._resource_monitor.last_action
                    self._resource_monitor.last_action = ""
                if action == "stop":
                    logger.info("master ordered stop via heartbeat")
                    self._worker_group.stop()
                    return WorkerState.SUCCEEDED
                if action == "restart":
                    logger.info("master ordered restart via heartbeat")
                    if self._config.save_at_breakpoint:
                        self._save_shm_at_breakpoint()
                    self._restart_workers()
                    continue
                if self._standby is not None and self._standby.died():
                    # The standby itself died during warmup/parking (its
                    # own crash or an external kill): re-warm one so the
                    # next failure still recovers fast — but give up
                    # after repeated deaths (a standby that cannot boot
                    # must not re-pay jax import every tick forever).
                    self._standby_deaths += 1
                    with self._standby_lock:
                        self._standby.stop()
                        if (
                            self._standby_deaths
                            >= self._MAX_STANDBY_DEATHS
                        ):
                            logger.error(
                                "warm standby died %s times; disabling "
                                "it (cold restarts only from here)",
                                self._standby_deaths,
                            )
                        else:
                            logger.warning(
                                "warm standby died; respawning"
                            )
                            self._spawn_standby_locked()
                if not self._check_world_formed():
                    # Workers are up but the triple was never consumed
                    # (hung import, unroutable coordinator addr): restart
                    # the world rather than supervise a zombie job.
                    try:
                        self._client.report_failure(
                            f"world bootstrap timeout: coordinator "
                            f"{self._coordinator} never came live",
                            restart_count=self._worker_group.restart_count,
                            level=TrainingExceptionLevel.RDZV_ERROR,
                        )
                    except Exception:  # noqa: BLE001
                        pass
                    if self._remaining_restarts > 0:
                        self._remaining_restarts -= 1
                        self._restart_workers()
                        continue
                    self._worker_group.stop()
                    return WorkerState.FAILED
                if self._watchdog is not None:
                    verdict = self._watchdog.check(
                        [
                            w.proc.pid
                            for w in self._worker_group.workers
                            if w.poll() is None
                        ]
                    )
                    if verdict in ("warn", "restart"):
                        from dlrover_tpu.telemetry import events as tevents

                        tevents.emit(
                            "stall",
                            verdict=verdict,
                            stalled_s=round(
                                self._watchdog.stalled_for(time.time()), 1
                            ),
                        )
                    if verdict == "restart":
                        stalled = self._watchdog.stalled_for(time.time())
                        try:
                            self._client.report_failure(
                                f"training hang: no step progress for "
                                f"{stalled:.0f}s",
                                restart_count=(
                                    self._worker_group.restart_count
                                ),
                                level=TrainingExceptionLevel.PROCESS_ERROR,
                            )
                        except Exception:  # noqa: BLE001
                            pass
                        self._collect_debug_bundle("watchdog_restart")
                        if self._config.save_at_breakpoint:
                            self._save_shm_at_breakpoint()
                        if self._remaining_restarts > 0:
                            self._remaining_restarts -= 1
                            logger.error(
                                "hang watchdog restarting world "
                                "(%s retries left)",
                                self._remaining_restarts,
                            )
                            self._restart_workers()
                            continue
                        logger.error(
                            "hang watchdog: retries exhausted"
                        )
                        self._worker_group.stop()
                        return WorkerState.FAILED
                state, exited = self._worker_group.monitor()
                if state == WorkerState.SUCCEEDED:
                    logger.info("all workers finished successfully")
                    self._worker_group.stop()
                    return state
                if state == WorkerState.FAILED:
                    self._report_failure(exited)
                    if self._config.save_at_breakpoint:
                        self._save_shm_at_breakpoint()
                    if self._remaining_restarts > 0:
                        self._remaining_restarts -= 1
                        if self._promote_standby():
                            continue
                        logger.info(
                            "workers failed (%s); restarting "
                            "(%s retries left)",
                            exited, self._remaining_restarts,
                        )
                        self._restart_workers()
                    else:
                        logger.error("workers failed; retries exhausted")
                        self._worker_group.stop()
                        return state
                elif self._membership_changed():
                    logger.info("membership changed; restarting workers")
                    if self._config.save_at_breakpoint:
                        self._save_shm_at_breakpoint()
                    self._restart_workers()
        except Exception as e:  # noqa: BLE001 — supervision fault barrier
            logger.exception("agent supervision failed: %s", e)
            try:
                self._client.report_failure(
                    f"agent error: {e}",
                    restart_count=self._worker_group.restart_count,
                    level=TrainingExceptionLevel.RDZV_ERROR,
                )
            except Exception:  # noqa: BLE001
                pass
            self._worker_group.stop()
            return WorkerState.FAILED
        finally:
            if self._resource_monitor:
                self._resource_monitor.stop()
            if self._paral_tuner is not None:
                self._paral_tuner.stop()
            self._teardown_standby()
            # Final ship: the master is still up (elastic_run stops it
            # after the agent returns) — drain the tail of every stream
            # so the online goodput sees the run's last events.
            self._ship_telemetry(force=True)
        self._worker_group.stop()
        return self._worker_group.state

    def _teardown_standby(self):
        self._stopped = True  # a pending respawn timer must not fire
        if self._standby_timer is not None:
            self._standby_timer.cancel()
            self._standby_timer = None
        if self._standby is not None:
            with self._standby_lock:
                self._standby.stop()
        if self._standby_log is not None:
            try:
                self._standby_log.close()
            except OSError:
                pass
            self._standby_log = None

    def stop(self):
        self._stopped = True
        self._worker_group.stop()
        self._teardown_standby()


class NodeCheckElasticAgent:
    """Pre-flight node health check (reference NodeCheckElasticAgent:816).

    Runs the node-check workload (matmul + collective micro-benchmark,
    ``dlrover_tpu.trainer.node_check``) in sub-processes through the
    network-check rendezvous, reports elapsed time / success to the master,
    then asks the master for the fault + straggler verdicts.  Returns False
    if THIS node should be excluded.
    """

    def __init__(
        self,
        config: ElasticLaunchConfig,
        client: MasterClient,
        check_entrypoint: Optional[List[str]] = None,
        check_timeout: float = JobConstant.NODE_CHECK_TIMEOUT,
    ):
        self._config = config
        self._client = client
        self._check_timeout = check_timeout
        self._entrypoint = check_entrypoint or [
            sys.executable, "-m", "dlrover_tpu.trainer.node_check",
        ]
        self._rdzv_handler = MasterRendezvousHandler(
            RendezvousName.NETWORK_CHECK,
            config.node_rank,
            config.nproc_per_node,
            client,
            join_timeout=config.rdzv_timeout,
        )

    def _run_one_round(self) -> Tuple[bool, float]:
        outcome = self._rdzv_handler.next_rendezvous()
        env = dict(os.environ)
        result_path = os.path.join(
            "/tmp", f"dlrover_tpu_check_{os.getpid()}_{outcome.round}.json"
        )
        env["DLROVER_CHECK_RESULT_PATH"] = result_path
        env[NodeEnv.NODE_RANK] = str(outcome.node_rank)
        start = time.time()
        try:
            subprocess.run(  # noqa: S603
                self._entrypoint,
                env=env,
                timeout=self._check_timeout,
                check=True,
            )
            elapsed = time.time() - start
            if os.path.exists(result_path):
                import json

                with open(result_path) as f:
                    elapsed = float(json.load(f).get("elapsed", elapsed))
                os.remove(result_path)
            return True, elapsed
        except (subprocess.CalledProcessError, subprocess.TimeoutExpired):
            return False, time.time() - start

    def run(self, rounds: int = 2) -> bool:
        """Two verification rounds mirror the master's pairing algorithm:
        round 1 pairs arbitrarily; round 2 re-pairs abnormal nodes with
        proven-normal partners so double-failure convicts the node."""
        from dlrover_tpu.common.constants import NetworkFailureReason

        fault_nodes: List[int] = []
        reason = ""
        for _ in range(rounds):
            ok, elapsed = self._run_one_round()
            self._client.report_network_check_result(
                self._config.node_rank, ok, elapsed
            )
            fault_nodes, reason = self._poll_verdict()
            if not fault_nodes and reason != NetworkFailureReason.WAITING_NODE:
                break
        if reason == NetworkFailureReason.WAITING_NODE:
            # No verdict ever arrived — fail safe: an unverified node must
            # not be admitted (a hung master would otherwise wave
            # genuinely faulty hardware into the job).
            logger.error(
                "node %s: network-check verdict timed out; excluding",
                self._config.node_rank,
            )
            return False
        if self._config.node_rank in fault_nodes:
            logger.error(
                "node %s failed the network check; excluding",
                self._config.node_rank,
            )
            return False
        if self._config.exclude_straggler:
            stragglers, _ = self._client.check_straggler()
            if self._config.node_rank in stragglers:
                logger.error(
                    "node %s is a straggler; excluding",
                    self._config.node_rank,
                )
                return False
        return True

    def _poll_verdict(self, timeout: float = 60.0):
        from dlrover_tpu.common.constants import NetworkFailureReason

        deadline = time.time() + timeout
        while time.time() < deadline:
            nodes, reason = self._client.check_fault_node()
            if reason != NetworkFailureReason.WAITING_NODE:
                return nodes, reason
            time.sleep(0.5)
        return [], NetworkFailureReason.WAITING_NODE


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _host_ip() -> str:
    import socket

    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"


def launch_agent(
    config: ElasticLaunchConfig,
    entrypoint: List[str],
    client: Optional[MasterClient] = None,
    ckpt_saver=None,
) -> WorkerState:
    """Reference ``launch_agent:705``: wire the client, push rendezvous
    params, optionally run the pre-flight node check, then supervise."""
    client = client or MasterClient.singleton_instance()
    if client is None:
        raise RuntimeError(
            "no master address; set DLROVER_MASTER_ADDR or use tpurun"
        )
    config.auto_configure_from_env()
    # Start the Flash-Checkpoint saver factory in THIS (long-lived) agent
    # process so trainers' CheckpointEngines have a serving factory queue
    # (reference: start_async_saving_ckpt inside _invoke_run).
    from dlrover_tpu.checkpoint.ckpt_saver import AsyncCheckpointSaver

    AsyncCheckpointSaver.start_async_saving_ckpt()
    if config.preemption_grace:
        # SIGTERM (scheduler preemption notice) -> flush shm checkpoint
        # to storage, deregister from the master so the next rendezvous
        # round skips this host, then exit 143.  Main thread only.
        from dlrover_tpu.common.preemption import (
            install_preemption_handler,
            register_grace_callback,
        )

        def _flush_ckpt():
            saver = AsyncCheckpointSaver.get_ckpt_saver()
            if saver is not None:
                saver.save_shm_to_storage()

        register_grace_callback(_flush_ckpt)
        install_preemption_handler(
            master_client=client, node_rank=config.node_rank
        )
    client.report_rdzv_params(
        config.min_nodes,
        config.max_nodes,
        config.waiting_timeout,
        config.node_unit,
        config.rdzv_timeout,
    )
    if config.network_check:
        checker = NodeCheckElasticAgent(config, client)
        if not checker.run():
            return WorkerState.FAILED
    agent = ElasticTrainingAgent(
        config, entrypoint, client, ckpt_saver=ckpt_saver
    )
    result = agent.run()
    if result != WorkerState.SUCCEEDED:
        # Nonzero job exit: whatever per-crash bundles exist, capture a
        # final one covering the run's terminal state (the throttle in
        # _collect_debug_bundle dedups against a crash seconds ago).
        agent._collect_debug_bundle("job_failed")
    return result
