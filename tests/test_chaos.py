"""Chaos-ready elasticity: every detection→recovery chain under
deterministic fault injection (ISSUE 2 acceptance scenarios).

Scenario coverage:

* kill-at-barrier   — a member SIGKILLed entering a barrier; the world
  reforms and resumes from the checkpoint (fault armed via the env
  channel, ``r0`` qualifier proves no re-fire after recovery).
* stalled-rank      — a worker wedges mid-step; the agent's HangWatchdog
  escalates warn → stack dump → restart-world and the job succeeds.
* SIGTERM-grace     — a preempted worker writes an emergency checkpoint
  inside the grace window, exits 143, and the reformed world restores it.
* master-RPC blackout — injected ``drop`` faults on the client's retry
  barrier: transient blackouts are retried through, permanent ones fail
  within the wall-time budget, and the job resumes once faults clear.

Plus unit tiers for the fault grammar (zero-cost, seeded replay,
qualifiers, hit windows), the watchdog ladder, the preemption grace
path, the master-side stall verdict and rendezvous preemption bar, and
the coordinator re-election edges.
"""

import json
import logging
import os
import signal
import socket
import subprocess
import sys
import textwrap
import time

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.monitor.progress import (
    clear_progress,
    max_progress_step,
    publish_progress,
    read_progress,
)
from dlrover_tpu.agent.watchdog import HangWatchdog, dump_worker_stacks
from dlrover_tpu.common import faults
from dlrover_tpu.common.constants import (
    JobConstant,
    NodeEnv,
    RendezvousName,
)
from dlrover_tpu.common.faults import FaultInjectedError, fault_point
from dlrover_tpu.common import preemption
from dlrover_tpu.master.local_master import LocalJobMaster
from dlrover_tpu.runtime.coordinator import (
    CoordinatorElection,
    _next_poll,
    host_ip,
)
from dlrover_tpu.runtime.harness import MultiProcessWorldHarness

CHAOS_WORKER = os.path.join(os.path.dirname(__file__), "_chaos_worker.py")

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Every test leaves the registry on the zero-cost path."""
    yield
    faults.reset()


@pytest.fixture()
def log_records():
    """Capture "dlrover_tpu" records — the logger does not propagate, so
    plain caplog never sees agent/watchdog output."""
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    lg = logging.getLogger("dlrover_tpu")
    handler = _Capture(level=logging.DEBUG)
    old_level = lg.level
    lg.addHandler(handler)
    lg.setLevel(logging.INFO)
    yield records
    lg.removeHandler(handler)
    lg.setLevel(old_level)


@pytest.fixture()
def master():
    m = LocalJobMaster(port=0, node_num=1)
    m.run(blocking=False)
    yield m
    m.stop()


@pytest.fixture()
def client(master):
    c = MasterClient(master.addr, node_id=0, node_type="worker")
    assert c.ready(10)
    return c


# -- unit: the fault grammar --------------------------------------------------


class TestFaultRegistry:
    def test_parses_the_canonical_spec_string(self):
        specs = faults.parse_specs(
            "barrier_enter:p2:kill, rpc:master:drop@3, step:5:stall=30"
        )
        assert [(s.point, s.atoms, s.action) for s in specs] == [
            ("barrier_enter", ["p2"], "kill"),
            ("rpc", ["master"], "drop"),
            ("step", ["5"], "stall"),
        ]
        assert specs[1].hit_from == specs[1].hit_to == 3
        assert specs[2].value == "30"

    def test_zero_cost_when_disarmed(self, monkeypatch):
        """Provably zero-cost: the slow path is never entered — only one
        module-level boolean stands between a hot step loop and return."""
        assert not faults.is_active()

        def _boom(*a, **k):
            raise AssertionError("_fire reached while disarmed")

        monkeypatch.setattr(faults, "_fire", _boom)
        assert fault_point("step", step=123) is None
        monkeypatch.undo()
        faults.install("step:*:noop")
        assert fault_point("step", step=123) == "noop"

    def test_process_and_restart_qualifiers(self):
        faults.install("x:p1+r0:noop")
        assert fault_point("x", process_id=0, restart=0) is None
        assert fault_point("x", process_id=1, restart=1) is None
        assert fault_point("x", process_id=1, restart=0) == "noop"

    def test_step_and_substring_qualifiers(self):
        faults.install("step:5:noop, barrier_enter:chaos:noop")
        assert fault_point("step", step=4) is None
        assert fault_point("step", step=5) == "noop"
        assert fault_point("barrier_enter", name="bootstrap/0") is None
        assert fault_point("barrier_enter", name="chaos/0") == "noop"

    def test_hit_windows(self):
        faults.install("a:*:noop@2-3, b:*:noop@3+, c:*:noop@2")
        assert [fault_point("a") for _ in range(5)] == [
            None, "noop", "noop", None, None,
        ]
        assert [fault_point("b") for _ in range(5)] == [
            None, None, "noop", "noop", "noop",
        ]
        assert [fault_point("c") for _ in range(4)] == [
            None, "noop", None, None,
        ]

    def test_drop_raises_connection_error(self):
        faults.install("rpc:master:drop=blackout")
        with pytest.raises(FaultInjectedError, match="blackout") as ei:
            fault_point("rpc", target="master")
        assert isinstance(ei.value, ConnectionError)

    def test_first_matching_spec_wins(self):
        faults.install("x:*:noop, x:*:drop")
        assert fault_point("x") == "noop"  # never reaches the drop

    def test_seeded_probability_replays_exactly(self):
        def run(seed):
            faults.install("x:*:noop~0.5", seed=seed)
            return [fault_point("x") is not None for _ in range(40)]

        first = run("seed-a")
        assert run("seed-a") == first  # exact replay
        assert True in first and False in first  # it IS probabilistic
        assert run("seed-b") != first  # seed actually feeds the draw

    def test_malformed_specs_raise(self):
        with pytest.raises(ValueError):
            faults.parse_specs("justapoint")
        with pytest.raises(ValueError):
            faults.parse_specs("a:b:c:d")
        with pytest.raises(ValueError):
            faults.parse_specs("x:explode")

    def test_fired_records_are_observable(self):
        faults.install("x:p0:noop")
        fault_point("x", process_id=0)
        fault_point("x", process_id=1)
        recs = faults.fired()
        assert len(recs) == 1
        assert recs[0]["point"] == "x"
        assert recs[0]["ctx"]["process_id"] == 0


# -- unit: progress channel + watchdog ladder ---------------------------------


class TestProgressChannel:
    def test_publish_read_clear(self, tmp_path):
        d = str(tmp_path)
        assert max_progress_step(d) == -1
        publish_progress(3, directory=d)
        prog = read_progress(d)
        assert prog[os.getpid()]["step"] == 3
        assert max_progress_step(d) == 3
        clear_progress(d)
        assert read_progress(d) == {}

    def test_publish_is_the_step_fault_point(self, tmp_path):
        faults.install("step:2:drop")
        publish_progress(1, directory=str(tmp_path))
        with pytest.raises(FaultInjectedError):
            publish_progress(2, directory=str(tmp_path))
        # step 1 was published before the fault wedged step 2
        assert max_progress_step(str(tmp_path)) == 1


class TestHangWatchdog:
    def test_escalation_ladder(self, tmp_path, log_records):
        d = str(tmp_path)
        wd = HangWatchdog(
            warn_after=10, dump_after=20, restart_after=30, directory=d
        )
        assert wd.check([], now=100.0) == ""  # unarmed: no progress yet
        publish_progress(1, directory=d)
        assert wd.check([], now=100.0) == ""  # arms on first snapshot
        assert wd.check([], now=105.0) == ""
        assert wd.check([], now=111.0) == "warn"
        assert wd.check([], now=112.0) == ""  # one warn per episode
        assert wd.check([], now=121.0) == "dump"
        assert wd.check([], now=125.0) == ""
        assert wd.check([], now=131.0) == "restart"
        assert wd.stalled_for(131.0) == pytest.approx(31.0)
        publish_progress(2, directory=d)
        assert wd.check([], now=132.0) == ""  # advance resets the episode
        msgs = [r.getMessage() for r in log_records]
        assert any("escalating if it persists" in m for m in msgs)
        assert any("stack dump signalled" in m for m in msgs)
        assert any("ordering restart-world" in m for m in msgs)

    def test_dump_skips_dead_pids(self):
        dead = subprocess.Popen([sys.executable, "-c", "pass"])
        dead.wait()
        assert dump_worker_stacks([dead.pid], sig=0) == []
        assert dump_worker_stacks([os.getpid()], sig=0) == [os.getpid()]


# -- unit: preemption grace path ----------------------------------------------


class TestPreemptionGrace:
    def test_grace_callbacks_best_effort(self):
        ran = []
        preemption.clear_grace_callbacks()
        preemption.register_grace_callback(lambda: ran.append("ckpt"))
        preemption.register_grace_callback(
            lambda: (_ for _ in ()).throw(RuntimeError("late"))
        )
        preemption.register_grace_callback(lambda: ran.append("dereg"))
        try:
            assert preemption.run_grace_callbacks() == 2
            assert ran == ["ckpt", "dereg"]  # FIFO, failure skipped
        finally:
            preemption.clear_grace_callbacks()

    def test_sigterm_runs_grace_then_exits(self):
        ran = []
        old = signal.getsignal(signal.SIGTERM)
        preemption.clear_grace_callbacks()
        preemption.register_grace_callback(lambda: ran.append(1))
        try:
            assert preemption.install_preemption_handler(
                exit_code=77, hard_exit=False
            )
            with pytest.raises(SystemExit) as ei:
                os.kill(os.getpid(), signal.SIGTERM)
                time.sleep(2)  # handler fires at a bytecode boundary
            assert ei.value.code == 77
            assert ran == [1]
        finally:
            signal.signal(signal.SIGTERM, old)
            preemption.clear_grace_callbacks()

    def test_stack_dump_handler_dumps_all_threads(self, capfd):
        import faulthandler

        assert preemption.install_stack_dump_handler()
        try:
            os.kill(os.getpid(), signal.SIGUSR1)
            time.sleep(0.5)
        finally:
            faulthandler.unregister(signal.SIGUSR1)
        err = capfd.readouterr().err
        assert "Current thread" in err or "Thread 0x" in err


# -- satellite: retry_rpc jitter + wall cap, RPC blackout scenario ------------


class TestMasterRpcBlackout:
    def test_transient_blackout_retried_through(
        self, master, client, monkeypatch
    ):
        """drop@1-2: the first two attempts lose the RPC, the third lands
        — detection is the retry barrier, recovery is transparent."""
        import dlrover_tpu.agent.master_client as mc

        monkeypatch.setattr(mc, "_retry_delay", lambda i: 0.01)
        faults.install("rpc:master:drop@1-2")
        assert client.kv_store_set("blackout-key", b"v") is True
        drops = [r for r in faults.fired() if r["action"] == "drop"]
        assert len(drops) == 2
        faults.reset()
        # The job resumes: the channel is clean again.
        assert client.kv_store_get("blackout-key") == b"v"

    def test_permanent_blackout_fails_after_retries(
        self, master, client, monkeypatch
    ):
        import dlrover_tpu.agent.master_client as mc

        monkeypatch.setattr(mc, "_retry_delay", lambda i: 0.01)
        faults.install("rpc:master:drop")
        with pytest.raises(RuntimeError, match="kv_store_set failed"):
            client.kv_store_set("k", b"v")
        assert (
            len(faults.fired()) == JobConstant.MASTER_CLIENT_MAX_RETRY
        )
        faults.reset()
        assert client.kv_store_set("k", b"v") is True  # resumes

    def test_wall_time_cap_bounds_total_retry(
        self, master, client, monkeypatch
    ):
        """A worker whose master is gone fails fast: total sleep is
        capped by the wall budget, not retry_count * max_backoff."""
        import dlrover_tpu.agent.master_client as mc

        monkeypatch.setattr(mc, "_retry_delay", lambda i: 100.0)
        monkeypatch.setattr(
            JobConstant, "MASTER_CLIENT_RETRY_WALL_TIME", 0.2
        )
        faults.install("rpc:master:drop")
        start = time.time()
        with pytest.raises(RuntimeError):
            client.kv_store_set("k", b"v")
        assert time.time() - start < 5.0
        # Budget exhaustion broke the loop before the attempt cap.
        assert (
            len(faults.fired()) < JobConstant.MASTER_CLIENT_MAX_RETRY
        )

    def test_retry_delay_is_jittered_exponential(self):
        from dlrover_tpu.agent.master_client import _retry_delay

        for attempt, base in ((0, 1), (2, 4), (5, 8)):
            samples = [_retry_delay(attempt) for _ in range(50)]
            assert all(0.5 * base <= s <= 1.5 * base for s in samples)
            assert len(set(samples)) > 1  # actually jittered


# -- satellite: master-side stall verdict + rendezvous preemption bar ---------


class TestSpeedMonitorStall:
    def test_stall_verdict_escalates(self):
        from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

        sm = SpeedMonitor()
        # Before training starts the verdict is silent: slow imports and
        # compilation are the bootstrap watchdog's problem.
        assert sm.stall_verdict(10, 20, now=time.time() + 100) == ""
        sm.collect_global_step(5, time.time())
        t0 = sm._last_progress_ts
        assert sm.stall_verdict(10, 20, now=t0 + 5) == ""
        assert sm.stall_verdict(10, 20, now=t0 + 15) == "warn"
        assert sm.stall_verdict(10, 20, now=t0 + 16) == "warn"
        assert sm.stall_verdict(10, 20, now=t0 + 25) == "restart"
        # Re-reporting the SAME step is not progress ...
        sm.collect_global_step(5, time.time())
        assert sm.stall_verdict(10, 20, now=t0 + 25) == "restart"
        # ... but an advanced step resets the clock.
        sm.collect_global_step(6, time.time())
        assert sm.seconds_since_progress() < 5
        assert sm.stall_verdict(10, 20) == ""


class TestRendezvousPreemption:
    def test_preempted_rank_barred_until_next_round(self, master, client):
        mgr = master.rdzv_managers[RendezvousName.TRAINING]
        mgr.update_rdzv_params(1, 1, 0.5, 1)
        assert client.report_preemption(node_rank=0) is True
        assert mgr.preempted_ranks() == [0]
        # The dying host's late join is refused.
        mgr.join_rendezvous(node_id=0, node_rank=0, local_world_size=1)
        assert mgr.num_nodes_waiting() == 0
        # A healthy node forms the next round without it ...
        mgr.join_rendezvous(node_id=1, node_rank=1, local_world_size=1)
        rdzv_round, _, world = mgr.get_comm_world(1)
        assert world == {1: 1}
        # ... and completion lifts the bar (a replacement may reuse rank 0).
        assert mgr.preempted_ranks() == []

    def test_preemption_deregisters_node(self, master, client):
        assert 0 in master.job_manager.get_alive_node_ids()
        client.report_preemption(node_rank=0)
        assert 0 not in master.job_manager.get_alive_node_ids()

    def test_local_manager_action_channel(self):
        from dlrover_tpu.master.node.local_job_manager import (
            LocalJobManager,
        )

        mgr = LocalJobManager(node_num=2)
        mgr.start()
        mgr.order_workers_action("restart")
        assert mgr.collect_node_heart_beat("worker", 0, 0.0) == "restart"
        assert mgr.collect_node_heart_beat("worker", 0, 0.0) == ""  # one-shot
        assert mgr.collect_node_heart_beat("worker", 1, 0.0) == "restart"


# -- satellite: coordinator re-election edges ---------------------------------


class _FakeKV:
    def __init__(self):
        self.kv = {}
        self.gets = 0

    def kv_store_set(self, key, value):
        self.kv[key] = value
        return True

    def kv_store_get(self, key):
        self.gets += 1
        return self.kv.get(key, b"")


class TestCoordinatorEdges:
    def _election(self, kv, node_rank, timeout_s=5.0):
        return CoordinatorElection(
            kv, "chaosrun", 0, {0: 1, 1: 1}, node_rank,
            timeout_s=timeout_s,
        )

    def test_reelect_chain_exhaustion_raises(self):
        e = self._election(_FakeKV(), node_rank=0)
        with pytest.raises(RuntimeError, match="chain exhausted"):
            e.reelect(e.MAX_EPOCHS - 1)

    def test_resolve_live_follows_dead_head_to_successor(self):
        kv = _FakeKV()
        with socket.socket() as live:
            live.bind(("127.0.0.1", 0))
            live.listen(1)
            live_addr = f"127.0.0.1:{live.getsockname()[1]}"
            # Epoch 0's host is dead (port 1 never listens); epoch 1 is
            # the successor someone already elected.
            kv.kv_store_set(
                "rdzv/chaosrun/0/coordinator/0", b"127.0.0.1:1@0"
            )
            kv.kv_store_set(
                "rdzv/chaosrun/0/coordinator/1",
                f"{live_addr}@1".encode(),
            )
            e = self._election(kv, node_rank=0)
            assert e.resolve_live() == (live_addr, 1)

    def test_reelect_claimant_publishes_successor(self):
        kv = _FakeKV()
        kv.kv_store_set("rdzv/chaosrun/0/coordinator/0", b"127.0.0.1:1@0")
        # Epoch 1's designated claimant is rank 1 (rotation by epoch).
        e = self._election(kv, node_rank=1)
        addr, epoch = e.reelect(0)
        assert epoch == 1 and addr
        assert kv.kv["rdzv/chaosrun/0/coordinator/1"].decode().endswith("@1")
        # Everyone now resolves to the successor.
        assert self._election(kv, node_rank=0).resolve() == (addr, 1)

    def test_resolve_backoff_bounds_kv_load(self):
        """The non-claimant's wait is a backoff, not a 10Hz busy-poll."""
        kv = _FakeKV()
        e = self._election(kv, node_rank=1, timeout_s=0.8)
        with pytest.raises(TimeoutError):
            e.resolve()
        assert kv.gets <= 12  # growing delays, bounded KV traffic
        assert _next_poll(0.05) == pytest.approx(0.075)
        assert _next_poll(10.0) == 2.0  # capped

    def test_host_ip_honors_published_node_ip(self, monkeypatch):
        monkeypatch.setenv(NodeEnv.NODE_IP, "10.9.8.7")
        assert host_ip() == "10.9.8.7"
        monkeypatch.delenv(NodeEnv.NODE_IP)
        assert host_ip() != "10.9.8.7"


# -- satellite: harness forensics ---------------------------------------------


class TestHarnessForensics:
    def test_nonzero_exit_dumps_log_tails(self, tmp_path, log_records):
        script = tmp_path / "boom.py"
        script.write_text(
            "import sys\nprint('BOOM-MARKER')\nsys.exit(3)\n"
        )
        h = MultiProcessWorldHarness(
            str(script), 1, workdir=str(tmp_path / "w")
        )
        h.start()
        assert h.wait(timeout_s=60.0) == {0: 3}
        msgs = [r.getMessage() for r in log_records]
        assert any(
            "log tail" in m and "BOOM-MARKER" in m for m in msgs
        ), msgs

    def test_faults_env_reaches_workers(self, tmp_path):
        h = MultiProcessWorldHarness(
            "unused.py", 1, workdir=str(tmp_path),
            faults="barrier_enter:p0:kill",
        )
        assert h._env_for(0)[NodeEnv.FAULTS] == "barrier_enter:p0:kill"
        h.faults = ""
        assert NodeEnv.FAULTS not in h._env_for(0)


# -- scenario: kill at barrier → reform → resume ------------------------------


class TestKillAtBarrier:
    def test_sigkill_at_barrier_reforms_and_resumes(self, tmp_path):
        """P1 is SIGKILLed entering the chaos barrier (fault armed via
        env in the spawned world); after reform the fault's ``r0``
        qualifier no longer matches, the world restores the checkpoint
        saved before the kill, and the collective proves everyone is
        back."""
        ckpt = tmp_path / "chaos.ckpt"
        h = MultiProcessWorldHarness(
            CHAOS_WORKER, 2, workdir=str(tmp_path / "w"),
            extra_env={
                "CHAOS_WORKER_MODE": "barrier-kill",
                "CHAOS_WORKER_CKPT": str(ckpt),
            },
            faults="barrier_enter:chaos-barrier+p1+r0:kill",
        )
        h.start()
        try:
            # Detection: the injected SIGKILL, exactly at the barrier.
            assert h.wait_one(1, timeout_s=120.0) == -signal.SIGKILL
            deadline = time.time() + 30
            while not ckpt.exists() and time.time() < deadline:
                time.sleep(0.1)
            assert ckpt.exists(), "p0 never saved before the kill"
            # Recovery: restart-world with the SAME faults still armed —
            # restart_count=1 must not re-trigger the r0 spec.
            h.reform()
            assert h.wait(timeout_s=180.0) == {0: 0, 1: 0}
            results = h.results()
            for pid in (0, 1):
                assert results[pid]["restart_count"] == 1
                assert results[pid]["restored_step"] == 7
                assert results[pid]["psum"] == 3  # both participated
        finally:
            h.terminate()


# -- scenario: SIGKILL chaos run → bundle → the doctor names the fault --------


class TestDoctorOnChaosBundle:
    def test_doctor_names_the_injected_fault(self, tmp_path, monkeypatch):
        """The ISSUE 5 acceptance loop: run the scripted SIGKILL chaos
        world with telemetry armed, collect a debug bundle, run the
        doctor CLI on it, and check the incident report (a) attributes
        the incident to the exact injected fault point on the exact
        first-failing rank, and (b) prices the run's incidents so their
        cost sum agrees with (100 − online goodput) within ±3 points."""
        from dlrover_tpu.telemetry import bundle as tbundle
        from dlrover_tpu.telemetry import events as tevents
        from dlrover_tpu.telemetry.goodput import GoodputAccountant

        tdir = tmp_path / "telemetry"
        ckpt = tmp_path / "chaos.ckpt"
        h = MultiProcessWorldHarness(
            CHAOS_WORKER, 2, workdir=str(tmp_path / "w"),
            extra_env={
                "CHAOS_WORKER_MODE": "barrier-kill",
                "CHAOS_WORKER_CKPT": str(ckpt),
                "CHAOS_WORKER_TELEMETRY": "1",
                "DLROVER_TELEMETRY_DIR": str(tdir),
                "DLROVER_JOB_UID": "chaosdoc",
            },
            faults="barrier_enter:chaos-barrier+p1+r0:kill",
        )
        h.start()
        try:
            assert h.wait_one(1, timeout_s=120.0) == -signal.SIGKILL
            deadline = time.time() + 30
            while not ckpt.exists() and time.time() < deadline:
                time.sleep(0.1)
            h.reform()
            assert h.wait(timeout_s=180.0) == {0: 0, 1: 0}
        finally:
            h.terminate()

        # The online goodput: the accountant fed the run's streams, as
        # the master's /goodput.json would have been.
        acct = GoodputAccountant()
        acct.ingest(tevents.read_dir(str(tdir)))
        online = acct.summary(detail=False)["goodput_pct"]
        assert online is not None

        # Bundle from the agent's perspective (role=agent so the capture
        # event annotates the timeline without entering goodput).
        monkeypatch.setenv(tevents.ENV_TELEMETRY_DIR, str(tdir))
        tevents.configure(role="agent", rank=0, directory=str(tdir))
        try:
            bundle_path = tbundle.collect_bundle(
                reason="chaos_test",
                out_dir=str(tmp_path),
                telemetry_dir=str(tdir),
                goodput=acct.summary(detail=True),
                run_id="chaosdoc",
                attempt=1,
            )
        finally:
            tevents.reset()
        assert bundle_path and os.path.exists(bundle_path)
        assert os.path.basename(bundle_path) == "bundle_chaosdoc_1.tar.gz"

        out_dir = tmp_path / "report"
        proc = subprocess.run(
            [
                sys.executable, "-m", "dlrover_tpu.doctor",
                bundle_path, "--out-dir", str(out_dir), "--json",
            ],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr
        report = json.loads(proc.stdout)

        assert report["run"] == "chaosdoc"
        assert report["incidents"], "doctor found no incidents"
        fault_incidents = [
            i for i in report["incidents"]
            if i["trigger"] == "injected_fault"
        ]
        assert fault_incidents, report["incidents"]
        inc = fault_incidents[0]
        assert inc["fault_point"] == "barrier_enter"
        assert inc["first_failing_rank"] == 1
        # Cost closure: per-incident goodput points sum to the goodput
        # the run lost (±3 covers online-vs-offline skew + rounding).
        assert report["total_cost_pts"] == pytest.approx(
            100.0 - online, abs=3.0
        )
        # The human report exists and names the fault too.
        md = (out_dir / "incident_report.md").read_text()
        assert "barrier_enter" in md


# -- scenario: SIGTERM grace → emergency ckpt → reform restores ---------------


class TestSigtermGrace:
    def test_preemption_grace_checkpoints_then_resumes(self, tmp_path):
        ckpt = tmp_path / "grace.ckpt"
        h = MultiProcessWorldHarness(
            CHAOS_WORKER, 2, workdir=str(tmp_path / "w"),
            extra_env={
                "CHAOS_WORKER_MODE": "grace",
                "CHAOS_WORKER_CKPT": str(ckpt),
            },
        )
        h.start()
        try:
            deadline = time.time() + 120
            while len(h.results()) < 2 and time.time() < deadline:
                for hp in h.procs:
                    assert hp.proc.poll() is None, "worker died early"
                time.sleep(0.2)
            assert len(h.results()) == 2, "grace world never armed"
            assert not ckpt.exists()
            # The preemption notice.
            h.send_signal(1, signal.SIGTERM)
            code = h.wait_one(1, timeout_s=60.0)
            assert code == preemption.PREEMPTION_EXIT_CODE  # 143
            # Detection proof: the checkpoint was written BEFORE exit.
            assert ckpt.exists()
            with open(ckpt) as f:
                saved = json.load(f)
            assert saved == {"step": 11, "emergency": True}
            # Recovery: the reformed world restores the emergency save.
            h.reform()
            assert h.wait(timeout_s=180.0) == {0: 0, 1: 0}
            results = h.results()
            for pid in (0, 1):
                assert results[pid]["restart_count"] == 1
                assert results[pid]["restored_step"] == 11
                assert results[pid]["psum"] == 3
        finally:
            h.terminate()


# -- scenario: stalled rank → warn → stack dump → restart-world ---------------


class TestStalledRank:
    def test_agent_watchdog_escalates_and_recovers(
        self, tmp_path, monkeypatch, log_records
    ):
        """A worker wedges at step 4 (injected stall); the agent's
        watchdog logs warn → stack dump → restart-world, the worker log
        carries the faulthandler traceback, and the restarted
        incarnation finishes the job."""
        import sys as _sys

        from dlrover_tpu.agent.training_agent import (
            ElasticLaunchConfig,
            ElasticTrainingAgent,
            WorkerState,
        )

        monkeypatch.setenv(
            "DLROVER_TPU_METRICS_DIR", str(tmp_path / "metrics")
        )
        # Armed only in the spawned worker (this process imported the
        # registry long before the env var existed).
        monkeypatch.setenv(NodeEnv.FAULTS, "step:4:stall=600")
        master = LocalJobMaster(port=0, node_num=1)
        master.run(blocking=False)
        try:
            client = MasterClient(
                master.addr, node_id=0, node_type="worker"
            )
            assert client.ready(10)
            client.report_rdzv_params(1, 1, 0.5, 1)
            repo_root = os.path.dirname(os.path.dirname(__file__))
            script = tmp_path / "stall_train.py"
            script.write_text(textwrap.dedent(
                f"""
                import os, sys, time
                sys.path.insert(0, {repo_root!r})
                from dlrover_tpu.agent.monitor.progress import (
                    publish_progress,
                )
                from dlrover_tpu.common.preemption import (
                    install_stack_dump_handler,
                )
                install_stack_dump_handler()
                restart = int(os.environ.get(
                    "DLROVER_RESTART_COUNT", "0"))
                limit = 3 if restart > 0 else 10
                for step in range(limit):
                    publish_progress(step, process_id=int(
                        os.environ.get("DLROVER_PROCESS_ID", "0")))
                    time.sleep(0.05)
                sys.exit(0)
                """
            ))
            config = ElasticLaunchConfig(
                min_nodes=1, max_nodes=1, nproc_per_node=1,
                monitor_interval=0.2, rdzv_timeout=15, max_restarts=2,
                hang_watchdog=True, hang_warn_after=0.5,
                hang_dump_after=1.0, hang_restart_after=1.5,
                log_dir=str(tmp_path / "logs"),
            )
            agent = ElasticTrainingAgent(
                config, [_sys.executable, str(script)], client
            )
            state = agent.run()
            assert state == WorkerState.SUCCEEDED
            assert agent._worker_group.restart_count >= 1
        finally:
            master.stop()
        msgs = [r.getMessage() for r in log_records]

        def first_index(sub):
            for i, m in enumerate(msgs):
                if sub in m:
                    return i
            raise AssertionError(f"{sub!r} not logged: {msgs}")

        warn_i = first_index("escalating if it persists")
        dump_i = first_index("stack dump signalled")
        restart_i = first_index("ordering restart-world")
        assert warn_i < dump_i < restart_i  # the ladder, in order
        assert any("hang watchdog restarting world" in m for m in msgs)
        # The stack dump landed in the wedged worker's log.
        log0 = (
            tmp_path / "logs" / "node_0_restart_0" / "worker_0.log"
        )
        content = log0.read_text(errors="replace")
        assert "Current thread" in content or "Thread 0x" in content
        assert "publish_progress" in content  # it shows WHERE it hung


# -- scenario: checkpoint trust — the four corruption fault points ------------


class TestCheckpointCorruption:
    """Commit verification and the restore ladder refuse bytes that fail
    their digests (docs/CHECKPOINT.md failure drill, fault-point catalog
    rows in docs/FAULT_TOLERANCE.md)."""

    def _state(self, step):
        import jax.numpy as jnp

        return {
            "w": jnp.arange(8, dtype=jnp.float32) * step,
            "step": jnp.asarray(step),
        }

    def _wait_for(self, cond, timeout=90.0, every=0.1):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if cond():
                return True
            time.sleep(every)
        return cond()

    def test_truncated_shard_refuses_commit_and_quarantines(
        self, tmp_path, isolated_ipc
    ):
        from dlrover_tpu.checkpoint import Checkpointer, StorageType

        root = str(tmp_path / "ckpt")
        # A torn write: the shard hits disk half-length, AFTER its done
        # record captured the intended bytes.
        faults.install("ckpt_truncate:1:noop")
        ckpt = Checkpointer(root, start_saver=True)
        try:
            ckpt.save_checkpoint(1, self._state(1), StorageType.DISK)
            assert self._wait_for(
                lambda: os.path.isdir(
                    os.path.join(root, "checkpoint-1.corrupt")
                )
            )
            # The torn step never reached the tracker, and the step dir
            # is quarantined — not silently reusable.
            assert ckpt.latest_persisted_step() is None
            assert not os.path.exists(os.path.join(root, "checkpoint-1"))
            faults.reset()
            # The failed save cost one interval, not the job: the next
            # save commits normally.
            assert ckpt.save_checkpoint(
                2, self._state(2), StorageType.DISK
            )
            assert ckpt.wait(timeout=90)
            assert ckpt.latest_persisted_step() == 2
            assert ckpt.verified_steps() == [2]
        finally:
            ckpt.close()

    def test_bitflip_refuses_commit_and_nothing_unverified_restores(
        self, tmp_path, isolated_ipc
    ):
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.checkpoint.ckpt_saver import AsyncCheckpointSaver

        root = str(tmp_path / "ckpt")
        faults.install("ckpt_bitflip:*:noop")
        ckpt = Checkpointer(root, start_saver=True)
        try:
            ckpt.save_checkpoint(1, self._state(1), StorageType.DISK)
            assert self._wait_for(
                lambda: os.path.isdir(
                    os.path.join(root, "checkpoint-1.corrupt")
                )
            )
            assert ckpt.latest_persisted_step() is None
        finally:
            ckpt.close()
            AsyncCheckpointSaver.reset()
        faults.reset()
        # A fresh process finds nothing trustworthy: no unverified byte
        # reaches device_put — the restore comes back empty-handed.
        ckpt2 = Checkpointer(root, start_saver=True)
        try:
            assert ckpt2.verified_steps() == []
            step, _ = ckpt2.load_checkpoint(self._state(0))
            assert step is None
        finally:
            ckpt2.close()

    def test_stale_tracker_sealed_step_still_restores(
        self, tmp_path, isolated_ipc
    ):
        from dlrover_tpu.checkpoint import Checkpointer, StorageType
        from dlrover_tpu.checkpoint.ckpt_saver import AsyncCheckpointSaver

        root = str(tmp_path / "ckpt")
        ckpt = Checkpointer(root, start_saver=True)
        try:
            assert ckpt.save_checkpoint(
                1, self._state(1), StorageType.DISK
            )
            assert ckpt.wait(timeout=90)
            assert ckpt.latest_persisted_step() == 1
            # Crash-before-flip: the manifest seals step 3, then the
            # tracker write is dropped.
            faults.install("ckpt_stale_tracker:*:noop")
            ckpt.save_checkpoint(3, self._state(3), StorageType.DISK)
            assert self._wait_for(
                lambda: any(
                    r["point"] == "ckpt_stale_tracker"
                    for r in faults.fired()
                )
            )
            assert ckpt.latest_persisted_step() == 1
        finally:
            ckpt.close()
            AsyncCheckpointSaver.reset()
        faults.reset()
        ckpt2 = Checkpointer(root, start_saver=True)
        try:
            # A manifest-verified step ABOVE the tracker is trusted —
            # the ladder recovers the lost flip.
            assert ckpt2.verified_steps() == [3, 1]
            step, state = ckpt2.load_checkpoint(self._state(0))
            assert step == 3
            assert float(state["w"][1]) == 3.0
        finally:
            ckpt2.close()

    def test_shm_corrupt_restore_falls_through_to_storage(
        self, tmp_path, isolated_ipc
    ):
        from dlrover_tpu.checkpoint import Checkpointer, StorageType

        root = str(tmp_path / "ckpt")
        ckpt = Checkpointer(root, start_saver=True)
        try:
            assert ckpt.save_checkpoint(
                1, self._state(1), StorageType.DISK
            )
            assert ckpt.wait(timeout=90)
            # A stray write / DMA error corrupts the NEXT (memory-only)
            # snapshot as it lands in shm.
            faults.install("ckpt_shm_corrupt:*:noop")
            assert ckpt.save_checkpoint(
                2, self._state(2), StorageType.MEMORY, block=True
            )
            assert any(
                r["point"] == "ckpt_shm_corrupt" for r in faults.fired()
            )
            step, state = ckpt.load_checkpoint(self._state(0))
            # The per-tensor crc rejects shm step 2; the ladder falls
            # through to disk step 1 instead of flash-restoring garbage.
            assert step == 1
            assert float(state["w"][1]) == 1.0
        finally:
            ckpt.close()

    def test_save_cut_short_restore_falls_through_to_storage(
        self, tmp_path, isolated_ipc
    ):
        from dlrover_tpu.checkpoint import Checkpointer, StorageType

        root = str(tmp_path / "ckpt")
        ckpt = Checkpointer(root, start_saver=True)
        try:
            assert ckpt.save_checkpoint(
                1, self._state(1), StorageType.DISK
            )
            assert ckpt.wait(timeout=90)
            # The NEXT (memory-only) save dies in the middle of its
            # pipeline, after its first leaf was handed to the block.
            faults.install("ckpt_stage_cut:*:raise@1")
            assert not ckpt.save_checkpoint(
                2, self._state(2), StorageType.MEMORY, block=True
            )
            assert any(
                r["point"] == "ckpt_stage_cut" for r in faults.fired()
            )
            faults.reset()
            step, state = ckpt.load_checkpoint(self._state(0))
            # The block holds part of step 2 and no header: no reader
            # opens it, and the ladder restores disk step 1 — neither
            # step 2's bytes under step 1's meta nor the other way round.
            assert step == 1
            assert float(state["w"][1]) == 1.0
        finally:
            ckpt.close()


# -- scenario: bit rot + SIGKILL → reform from the agreed verified step -------


class TestCorruptionReformDrill:
    def test_bit_rot_reform_restores_agreed_verified_step(
        self, tmp_path, monkeypatch
    ):
        """ISSUE 6 acceptance drill: the newest COMMITTED checkpoint is
        bit-flipped on disk (true rot — no fault event to lean on) and a
        rank is SIGKILLed.  The reformed world must quarantine the
        rotted step, agree on the newest step verifiable EVERYWHERE, and
        restore it on every rank; the doctor must name the corruption
        and price the incident within ±3 goodput points."""
        import shutil

        from dlrover_tpu.checkpoint.ckpt_saver import shard_file
        from dlrover_tpu.common.faults import corrupt_file
        from dlrover_tpu.telemetry import bundle as tbundle
        from dlrover_tpu.telemetry import events as tevents
        from dlrover_tpu.telemetry.goodput import GoodputAccountant

        root = tmp_path / "ckpt"
        tdir = tmp_path / "telemetry"
        m = LocalJobMaster(port=0, node_num=2)
        m.run(blocking=False)
        h = MultiProcessWorldHarness(
            CHAOS_WORKER, 2, workdir=str(tmp_path / "w"),
            extra_env={
                "CHAOS_WORKER_MODE": "ckpt-drill",
                "CHAOS_DRILL_CKPT_DIR": str(root),
                "CHAOS_WORKER_TELEMETRY": "1",
                "DLROVER_TELEMETRY_DIR": str(tdir),
                "DLROVER_JOB_UID": "ckptdrill",
                "DLROVER_MASTER_ADDR": m.addr,
            },
        )
        h.start()
        try:
            deadline = time.time() + 240
            while time.time() < deadline:
                res = h.results()
                if len(res) == 2 and all(
                    r.get("tracker") == 9 for r in res.values()
                ):
                    break
                time.sleep(0.2)
            res = h.results()
            assert len(res) == 2 and all(
                r.get("tracker") == 9 for r in res.values()
            ), f"round 0 never committed step 9: {res}"

            # True bit rot on the newest committed step — both shards.
            for gid in (0, 1):
                assert corrupt_file(
                    shard_file(str(root), 9, gid), mode="bitflip"
                )
            h.send_signal(1, signal.SIGKILL)
            assert h.wait_one(1, timeout_s=60.0) == -signal.SIGKILL
            h.reform()
            assert h.wait(timeout_s=300.0) == {0: 0, 1: 0}
            results = h.results()
        finally:
            h.terminate()
            m.stop()

        for pid in (0, 1):
            r = results[pid]
            assert r["restart_count"] == 1
            # Every rank restored the SAME consensus-agreed step: the
            # newest one verifiable everywhere.
            assert r["verified_steps"] == [5]
            assert r["agreed_step"] == 5
            assert r["restored_step"] == 5
            assert r["restored_w1"] == 5.0
            assert r["quarantined"] == ["checkpoint-9.corrupt"]
        assert results[0]["scrub"]["corrupt"] == [9]
        assert (root / "checkpoint-9.corrupt").is_dir()
        assert not (root / "checkpoint-9").exists()
        assert (root / "checkpoint-5").is_dir()

        # Online goodput, as the master's /goodput.json would price it.
        acct = GoodputAccountant()
        acct.ingest(tevents.read_dir(str(tdir)))
        online = acct.summary(detail=False)["goodput_pct"]
        assert online is not None

        monkeypatch.setenv(tevents.ENV_TELEMETRY_DIR, str(tdir))
        tevents.configure(role="agent", rank=0, directory=str(tdir))
        try:
            bundle_path = tbundle.collect_bundle(
                reason="ckpt_drill",
                out_dir=str(tmp_path),
                telemetry_dir=str(tdir),
                goodput=acct.summary(detail=True),
                run_id="ckptdrill",
                attempt=1,
            )
        finally:
            tevents.reset()
        assert bundle_path and os.path.exists(bundle_path)

        out_dir = tmp_path / "report"
        proc = subprocess.run(
            [
                sys.executable, "-m", "dlrover_tpu.doctor",
                bundle_path, "--out-dir", str(out_dir), "--json",
            ],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr
        report = json.loads(proc.stdout)

        assert report["run"] == "ckptdrill"
        corruption = [
            i for i in report["incidents"]
            if i["trigger"] == "ckpt_corruption"
        ]
        assert corruption, report["incidents"]
        inc = corruption[0]
        assert inc["fault_point"] == "ckpt_quarantine"
        assert inc["ckpt_quarantined_steps"] == [9]
        assert report["total_cost_pts"] == pytest.approx(
            100.0 - online, abs=3.0
        )
        md = (out_dir / "incident_report.md").read_text()
        assert "Quarantined checkpoint step" in md

class TestServeFaultPoints:
    """The three serving fault points (fleet/gateway/worker) fire under
    the grammar and drive the recovery paths they were built to prove."""

    def test_serve_spawn_fail_retries_through(self):
        from dlrover_tpu.serving.fleet import (
            _spawn_retry_counter,
            spawn_with_retry,
        )

        faults.install("serve_spawn_fail:raise@1")
        calls = []
        before = _spawn_retry_counter().value()
        out = spawn_with_retry(
            lambda: calls.append(1) or "replica", attempts=3,
            backoff_s=0.0,
        )
        # First attempt faulted before the factory ran; the retry made
        # it through — one retry counted, factory called exactly once.
        assert out == "replica" and len(calls) == 1
        recs = [r for r in faults.fired() if r["point"] == "serve_spawn_fail"]
        assert len(recs) == 1 and recs[0]["ctx"]["attempt"] == 0
        assert _spawn_retry_counter().value() == before + 1

    def test_serve_spawn_fail_exhausts_attempts(self):
        faults.install("serve_spawn_fail:raise")
        from dlrover_tpu.serving.fleet import spawn_with_retry

        with pytest.raises(FaultInjectedError):
            spawn_with_retry(lambda: "never", attempts=2, backoff_s=0.0)
        assert len(
            [r for r in faults.fired() if r["point"] == "serve_spawn_fail"]
        ) == 2

    def test_serve_heartbeat_drop_ejects_then_recovers(self):
        """Arm the poll-path fault: the gateway sees consecutive poll
        failures against a live replica, ejects it with a durable
        verdict, and serves again once the fault clears."""
        from dlrover_tpu.serving.gateway import InferenceGateway

        class _Replica:
            def __init__(self):
                import uuid

                self.uid = f"hb-{uuid.uuid4().hex[:6]}"
                self._reqs = {}

            def submit(self, rid, prompt, gen_budget, orig_prompt_len,
                       trace=""):
                self._reqs[rid] = {
                    "prompt": list(prompt), "budget": int(gen_budget),
                    "done": 0,
                }
                return True, ""

            def poll(self):
                emitted, completions = {}, []
                for rid, st in list(self._reqs.items()):
                    emitted[rid] = [7]
                    st["done"] += 1
                    if st["done"] >= st["budget"]:
                        completions.append({
                            "request_id": rid,
                            "tokens": st["prompt"] + [7] * st["budget"],
                            "prompt_len": len(st["prompt"]),
                            "finished_reason": "budget",
                        })
                        del self._reqs[rid]
                return {"emitted": emitted, "completions": completions,
                        "stats": {"ticks": 1}}

            def alive(self):
                return True

            def kill(self):
                pass

            def stop(self):
                pass

        gw = InferenceGateway(
            _Replica, n_replicas=1, heartbeat_misses=2,
            default_gen_budget=3, retention_s=None,
        )
        try:
            gw.pump()
            rid = gw.submit([1, 2])["request_id"]
            faults.install("serve_heartbeat_drop:raise@1-2")
            gw.pump()  # miss 1
            gw.pump()  # miss 2 -> ejection verdict
            assert len(
                [r for r in faults.fired()
                 if r["point"] == "serve_heartbeat_drop"]
            ) == 2
            assert any(
                e.get("action") == "serve_heartbeat_drop"
                for e in gw.events if e.get("ev") == "verdict"
            )
            faults.reset()
            # The fault cleared: the replacement replica serves the
            # replayed request to completion.
            out = gw.get(rid, timeout_s=10)
            assert out["ok"] and gw.disruptions == 1
        finally:
            gw.stop()

    def test_serve_replica_wedge_stalls_the_pump(self):
        """A `stall` action on the worker-pump fault point freezes the
        tick loop (the wedged-but-alive shape) for its duration."""
        faults.install("serve_replica_wedge:stall=0.2")
        t0 = time.monotonic()
        action = fault_point("serve_replica_wedge", worker="w0")
        elapsed = time.monotonic() - t0
        assert action == "stall" and elapsed >= 0.15
        recs = [
            r for r in faults.fired()
            if r["point"] == "serve_replica_wedge"
        ]
        assert recs and recs[0]["ctx"]["worker"] == "w0"


class TestKvReplicationFaultPoints:
    """The three kv replication fault points (PR 17): a dropped push
    fails sync replication (and with it the mutation RPC — the
    zero-acked-write-loss contract), a partitioned primary walks the
    HA manager's miss ladder to ``unhealthy``, and a forced stale
    epoch drives the lease fence's refusal path end-to-end."""

    def _mem_replicator(self, dim=4):
        import numpy as np

        from dlrover_tpu.common import comm
        from dlrover_tpu.kv_service.replication import (
            ChainReplicator,
            _Follower,
        )
        from dlrover_tpu.native.kv_variable import KvVariable

        table = KvVariable(dim, seed=11)
        rep = ChainReplicator(table, "kv-0", mode="sync")
        follower = _Follower("mem://f0", "f0", client=None)

        def send(f, msg):
            return comm.KvReplAck(ok=True, applied=msg.seq)

        rep._send = send
        rep._followers["mem://f0"] = follower
        return table, rep, follower, np

    def test_kv_repl_stall_drop_fails_the_sync_mutation(self):
        """An injected ``drop`` on the push path means the follower
        never applied the link — sync replication raises, so the
        client's mutation RPC fails instead of acking an unreplicated
        write.  Clearing the fault lets ``drain`` catch the follower
        back up."""
        table, rep, follower, np = self._mem_replicator()
        try:
            table.insert(
                np.arange(3, dtype=np.int64),
                np.ones((3, 4), dtype=np.float32),
            )
            rep.on_mutation()
            assert follower.bootstrapped
            assert follower.acked == int(table.version)

            faults.install("kv_repl_stall:drop@1")
            table.insert(
                np.arange(3, 6, dtype=np.int64),
                np.ones((3, 4), dtype=np.float32),
            )
            with pytest.raises(RuntimeError, match="not acked"):
                rep.on_mutation()
            recs = [
                r for r in faults.fired()
                if r["point"] == "kv_repl_stall"
            ]
            assert recs and recs[0]["ctx"]["owner"] == "kv-0"
            assert recs[0]["ctx"]["follower"] == "mem://f0"
            assert follower.acked < int(table.version)  # lag is real

            faults.reset()
            assert rep.drain() == {"mem://f0": True}
            assert follower.acked == int(table.version)
        finally:
            table.close()

    def test_kv_repl_stall_stall_delays_the_push(self):
        """The ``stall`` action models a slow follower link: the push
        completes but late — the shape that grows
        ``dlrover_kv_repl_lag_seconds`` and burns the kv_freshness
        SLO."""
        table, rep, follower, np = self._mem_replicator()
        try:
            faults.install("kv_repl_stall:stall=0.2")
            table.insert(
                np.arange(2, dtype=np.int64),
                np.ones((2, 4), dtype=np.float32),
            )
            t0 = time.monotonic()
            rep.on_mutation()
            elapsed = time.monotonic() - t0
            assert elapsed >= 0.15
            assert follower.acked == int(table.version)  # late, not lost
        finally:
            table.close()

    def test_kv_primary_partition_reaches_the_miss_limit(self):
        """The partition fault fires from the HA manager's seat: each
        armed poll counts as a miss with no RPC attempted, and the miss
        limit flips the primary unhealthy — the promotion trigger."""
        from dlrover_tpu.kv_service.replication import (
            KvHaManager,
            _ReplicaSet,
        )

        ha = KvHaManager(client=None, miss_limit=2)
        ha._sets["kv-0"] = _ReplicaSet(
            "kv-0", "127.0.0.1:1", epoch=1, mode="sync"
        )
        faults.install("kv_primary_partition:drop@1-2")
        assert ha.poll("kv-0") == "miss"
        assert ha.poll("kv-0") == "unhealthy"
        assert not ha.healthy("kv-0")
        recs = [
            r for r in faults.fired()
            if r["point"] == "kv_primary_partition"
        ]
        assert len(recs) == 2
        assert all(r["ctx"]["owner"] == "kv-0" for r in recs)

    def test_kv_stale_epoch_forces_the_fence_refusal(self):
        """Arming ``kv_stale_epoch`` with ``noop`` makes the lease
        fence refuse a mutation that would otherwise be admitted — the
        full deposed-primary refusal plumbing (typed refusal result,
        fence counter) without needing a real partition."""
        import numpy as np

        from dlrover_tpu.common import comm
        from dlrover_tpu.kv_service.server import KvShardServer

        server = KvShardServer("kv-chaos", dim=4, epoch=1, seed=7)
        try:
            keys = np.arange(4, dtype=np.int64).tobytes()
            values = np.ones(16, dtype=np.float32).tobytes()
            ok = server._handle_apply(comm.KvApplyRequest(
                optimizer="insert", keys=keys, values=values, epoch=1,
            ))
            assert not getattr(ok, "refused", False)

            faults.install("kv_stale_epoch:noop@1")
            refused = server._handle_apply(comm.KvApplyRequest(
                optimizer="insert", keys=keys, values=values, epoch=1,
            ))
            assert refused.refused and refused.epoch == 1
            recs = [
                r for r in faults.fired()
                if r["point"] == "kv_stale_epoch"
            ]
            assert recs and recs[0]["ctx"]["shard"] == "kv-chaos"
        finally:
            server.stop()
