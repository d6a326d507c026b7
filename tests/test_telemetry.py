"""Telemetry subsystem tests: event log, spans, metrics, goodput.

Strategy mirrors the control-plane tests: real files, a real in-process
master + RPC transport, real subprocesses for the kill/recovery scenario
— no mocks around the parts whose failure modes (torn writes, SIGKILL,
RPC loss) are the subject.
"""

import json
import os
import re
import signal
import subprocess
import sys
import textwrap
import time
import urllib.request

import pytest

from dlrover_tpu.telemetry import events as tevents
from dlrover_tpu.telemetry import metrics as tmetrics
from dlrover_tpu.telemetry.goodput import PHASES, GoodputAccountant
from dlrover_tpu.telemetry.httpd import TelemetryHTTPServer, last_goodput
from dlrover_tpu.telemetry.spans import (
    export_chrome_trace,
    span,
    to_chrome_trace,
)

pytestmark = pytest.mark.telemetry


@pytest.fixture()
def tdir(tmp_path, monkeypatch):
    d = str(tmp_path / "telemetry")
    monkeypatch.setenv(tevents.ENV_TELEMETRY_DIR, d)
    tevents.reset()
    yield d
    tevents.reset()


# -- event log ---------------------------------------------------------------


class TestEventLog:
    def test_schema_round_trip(self, tdir):
        log = tevents.EventLog(tdir, rank=3, role="worker", run_id="r1",
                               attempt=2)
        rec = log.emit("step", step=17)
        events = tevents.read_events(log.path)
        assert len(events) == 1
        got = events[0]
        assert got["ev"] == "step"
        assert got["step"] == 17
        assert got["rank"] == 3
        assert got["role"] == "worker"
        assert got["run"] == "r1"
        assert got["attempt"] == 2
        assert got["pid"] == os.getpid()
        # both clocks present and equal to what emit returned
        assert got["t"] == rec["t"]
        assert got["mono"] == rec["mono"]

    def test_closed_schema_rejects_typos(self, tdir):
        log = tevents.EventLog(tdir, rank=0)
        with pytest.raises(ValueError, match="unknown telemetry event"):
            log.emit("setp")
        # disabled emission still validates — a typo must never hide
        # behind DLROVER_TELEMETRY=0
        os.environ[tevents.ENV_TELEMETRY] = "0"
        try:
            with pytest.raises(ValueError):
                tevents.emit("no_such_event")
            assert tevents.emit("step") is None
        finally:
            os.environ.pop(tevents.ENV_TELEMETRY)

    def test_crash_truncation_tolerated(self, tdir):
        log = tevents.EventLog(tdir, rank=0)
        log.emit("step", step=1)
        log.emit("step", step=2)
        # simulate SIGKILL mid-write: torn trailing line
        with open(log.path, "a") as f:
            f.write('{"ev":"step","t":123.0,"step":3')
        events = tevents.read_events(log.path)
        assert [e["step"] for e in events] == [1, 2]

    def test_read_dir_merges_sorted(self, tdir):
        a = tevents.EventLog(tdir, rank=0)
        b = tevents.EventLog(tdir, rank=1)
        a.emit("step", step=1)
        time.sleep(0.01)
        b.emit("step", step=1)
        merged = tevents.read_dir(tdir)
        assert len(merged) == 2
        assert merged[0]["t"] <= merged[1]["t"]
        assert {e["rank"] for e in merged} == {0, 1}

    def test_standby_env_quarantines_stream(self, tdir, monkeypatch):
        monkeypatch.setenv("DLROVER_STANDBY_FIFO", "/tmp/x.fifo")
        log = tevents.EventLog(tdir, rank=0)
        assert log.role == "standby"
        assert "standby0" in log.path


class TestEventShipper:
    def test_poll_incremental_and_partial_lines(self, tdir):
        log = tevents.EventLog(tdir, rank=0)
        log.emit("step", step=1)
        shipper = tevents.EventShipper(tdir)
        assert [e["step"] for e in shipper.poll()] == [1]
        assert shipper.poll() == []  # nothing new
        log.emit("step", step=2)
        with open(log.path, "a") as f:
            f.write('{"ev":"step","st')  # torn tail stays unconsumed
        assert [e["step"] for e in shipper.poll()] == [2]
        with open(log.path, "a") as f:
            f.write('ep":3}\n')  # tail completed → next poll gets it
        assert [e["step"] for e in shipper.poll()] == [3]

    def test_rollback_resends_failed_batch(self, tdir):
        log = tevents.EventLog(tdir, rank=0)
        log.emit("step", step=1)
        shipper = tevents.EventShipper(tdir)

        class FlakyClient:
            calls = 0

            def report_telemetry_events(self, batch):
                self.calls += 1
                if self.calls == 1:
                    raise RuntimeError("master away")
                self.batch = batch

        client = FlakyClient()
        assert tevents.ship_events(shipper, client) == 0  # failed
        assert tevents.ship_events(shipper, client) == 1  # re-sent
        assert client.batch[0]["step"] == 1


class TestRotation:
    def test_size_rotation_keeps_last_segment_and_current(self, tdir):
        log = tevents.EventLog(tdir, rank=0, max_bytes=256)
        for i in range(20):
            log.emit("step", step=i)
        assert os.path.exists(log.path + tevents.SEGMENT_SUFFIX)
        # Rotation happens only at line boundaries — every line in both
        # files parses.
        for path in (log.path + tevents.SEGMENT_SUFFIX, log.path):
            with open(path) as f:
                for line in f:
                    json.loads(line)

    def test_read_stream_concatenates_segments(self, tdir):
        log = tevents.EventLog(tdir, rank=0, max_bytes=256)
        for i in range(20):
            log.emit("step", step=i)
        # Retention is last segment + live file, so readers see a
        # contiguous tail of the stream — segment first, in order.
        steps = [e["step"] for e in tevents.read_stream(log.path)]
        assert steps == list(range(steps[0], 20))
        seg_steps = [
            e["step"]
            for e in tevents.read_events(
                log.path + tevents.SEGMENT_SUFFIX
            )
        ]
        assert seg_steps  # the tail truly spans both files
        assert steps[: len(seg_steps)] == seg_steps
        # read_dir sees the same concatenated stream
        merged = [e["step"] for e in tevents.read_dir(tdir)]
        assert sorted(merged) == steps

    def test_shipper_survives_rotation_without_loss(self, tdir):
        # Pin every record-size-determining field: an ambient
        # DLROVER_JOB_UID (other tests set one) inflates "run" enough
        # that a 256-byte cap rotates on EVERY emit, and with polls only
        # every 3 events a file can age out of the .1 segment unread —
        # the documented multi-rotation loss, not a shipper bug.  400
        # bytes holds 2-3 pinned records, so rotation still happens
        # mid-stream but never twice between polls.
        log = tevents.EventLog(
            tdir, rank=0, role="worker", run_id="", attempt=0,
            max_bytes=400,
        )
        shipper = tevents.EventShipper(tdir)
        got = []
        for i in range(20):
            log.emit("step", step=i)
            if i % 3 == 0:  # poll mid-stream, across rotations
                got.extend(e["step"] for e in shipper.poll())
        got.extend(e["step"] for e in shipper.poll())
        assert got == list(range(20))
        assert shipper.poll() == []


# -- goodput accountant ------------------------------------------------------


def _ev(ev, t, rank=0, role="worker", pid=1, **kw):
    return {"ev": ev, "t": t, "mono": t, "pid": pid, "rank": rank,
            "role": role, **kw}


class TestGoodputAccountant:
    def test_attribution_math_synthetic(self):
        acc = GoodputAccountant()
        acc.ingest([
            _ev("process_start", 0.0),
            _ev("world_init", 4.0),      # 0-4 rendezvous
            _ev("restore_begin", 5.0),   # 4-5 idle
            _ev("restore_end", 7.0),     # 5-7 restore
            _ev("compile_begin", 7.0),
            _ev("compile_end", 17.0),    # 7-17 compile
            _ev("step", 18.0),           # 17-18 idle
            _ev("step", 28.0),           # 18-28 productive
        ])
        s = acc.summary()
        entry = s["ranks"]["worker0"]
        assert entry["phases"]["rendezvous"] == 4.0
        assert entry["phases"]["restore"] == 2.0
        assert entry["phases"]["compile"] == 10.0
        assert entry["phases"]["productive"] == 10.0
        assert entry["phases"]["idle"] == 2.0
        # window starts at FIRST step: 18 → 28 all productive
        assert entry["goodput_pct"] == 100.0

    def test_sigkill_gap_is_detect_respawn(self):
        acc = GoodputAccountant()
        acc.ingest([
            _ev("step", 10.0, pid=1),
            _ev("step", 11.0, pid=1),
            # SIGKILL: no terminal event; replacement starts at 15
            _ev("process_start", 15.0, pid=2),
            _ev("step", 17.0, pid=2),
            _ev("step", 21.0, pid=2),
        ])
        s = acc.summary()
        entry = s["ranks"]["worker0"]
        assert entry["phases"]["detect_respawn"] == 4.0  # 11 → 15
        assert entry["phases"]["rendezvous"] == 2.0      # 15 → 17
        assert entry["phases"]["productive"] == 1.0 + 4.0
        # window 10→21 = 11s; productive 5s
        assert entry["goodput_pct"] == pytest.approx(5 / 11 * 100, abs=0.1)
        phases = [seg["phase"] for seg in entry["segments"]]
        assert phases == [
            "productive", "detect_respawn", "rendezvous", "productive"
        ]

    def test_duplicate_batches_ignored(self):
        acc = GoodputAccountant()
        batch = [_ev("step", 1.0), _ev("step", 2.0)]
        assert acc.ingest(batch) == 2
        assert acc.ingest(batch) == 0  # RPC-retry re-send
        assert acc.summary()["events_ingested"] == 2

    def test_only_workers_aggregate(self):
        acc = GoodputAccountant()
        acc.ingest([
            _ev("step", 0.0), _ev("step", 10.0),
            _ev("save_begin", 0.0, role="agent"),
            _ev("save_end", 500.0, role="agent"),
        ])
        s = acc.summary()
        assert s["window_s"] == 10.0  # agent stream excluded
        assert "agent0" in s["ranks"]  # but still visible per-stream
        assert s["goodput_pct"] == 100.0

    def test_save_events_do_not_change_phase(self):
        acc = GoodputAccountant()
        acc.ingest([
            _ev("step", 0.0),
            _ev("save_begin", 1.0),
            _ev("save_end", 2.0),
            _ev("step", 3.0),
        ])
        entry = acc.summary()["ranks"]["worker0"]
        assert entry["phases"]["productive"] == 3.0
        assert entry["goodput_pct"] == 100.0

    def test_phase_names_closed(self):
        assert set(PHASES) == {
            "productive", "detect_respawn", "rendezvous", "compile",
            "restore", "stalled", "idle",
        }


# -- metrics registry --------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (-?\d+(\.\d+)?([eE]-?\d+)?|"
    r"\+Inf|-Inf|NaN)$"
)


class TestMetrics:
    def test_prometheus_text_format(self):
        reg = tmetrics.MetricsRegistry()
        c = reg.counter("events_total", "Total events.")
        c.inc(ev="step")
        c.inc(2, ev="stall")
        reg.gauge("speed", "Steps/s.").set(1.5)
        h = reg.histogram("latency_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = reg.render()
        lines = text.strip().splitlines()
        # every sample line parses; HELP/TYPE present
        assert "# TYPE events_total counter" in lines
        assert "# HELP events_total Total events." in lines
        assert "# TYPE latency_seconds histogram" in lines
        for line in lines:
            if line.startswith("#"):
                continue
            assert _SAMPLE_RE.match(line), f"unparseable: {line!r}"
        assert 'events_total{ev="step"} 1' in lines
        assert 'events_total{ev="stall"} 2' in lines
        # histogram buckets are cumulative; +Inf == count
        assert 'latency_seconds_bucket{le="0.1"} 1' in lines
        assert 'latency_seconds_bucket{le="1"} 2' in lines
        assert 'latency_seconds_bucket{le="+Inf"} 3' in lines
        assert "latency_seconds_count 3" in lines
        assert "latency_seconds_sum 5.55" in lines

    def test_idempotent_getter_and_type_clash(self):
        reg = tmetrics.MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")

    def test_counter_rejects_negative(self):
        reg = tmetrics.MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("c").inc(-1)

    def test_counts_snapshot(self):
        reg = tmetrics.MetricsRegistry()
        c = reg.counter("c")
        c.inc(a="1")
        c.inc(a="2")
        reg.gauge("g").set(1)
        assert reg.counts() == {"c": 2, "g": 1}


# -- spans / chrome trace ----------------------------------------------------


class TestSpans:
    def test_span_emits_pair_with_dur(self, tdir):
        with span("restore", source="shm"):
            time.sleep(0.01)
        events = tevents.read_dir(tdir)
        assert [e["ev"] for e in events] == ["restore_begin", "restore_end"]
        assert events[1]["dur"] >= 0.01
        assert events[1]["source"] == "shm"

    def test_span_exception_flagged_and_reraised(self, tdir):
        with pytest.raises(KeyError):
            with span("compile"):
                raise KeyError("boom")
        events = tevents.read_dir(tdir)
        assert events[-1]["ev"] == "compile_end"
        assert events[-1]["ok"] is False
        assert events[-1]["error"] == "KeyError"

    def test_chrome_trace_validity(self, tdir):
        log = tevents.EventLog(tdir, rank=0)
        log.emit("process_start")
        log.emit("restore_begin")
        log.emit("restore_end")
        log.emit("compile_begin")
        log.emit("compile_end")
        log.emit("step", step=1)
        log.emit("save_begin")  # truncated: killed mid-save
        out = str(os.path.join(tdir, "trace.json"))
        export_chrome_trace(tdir, out_path=out)
        with open(out) as f:
            trace = json.load(f)  # valid JSON by construction of the test
        names = [e["name"] for e in trace["traceEvents"]]
        assert "restore" in names
        assert "compile" in names
        slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in slices} == {"restore", "compile"}
        for s in slices:
            assert s["dur"] >= 0
        truncated = [
            e for e in trace["traceEvents"]
            if e.get("args", {}).get("truncated")
        ]
        assert [e["name"] for e in truncated] == ["save"]
        meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
        assert meta[0]["args"]["name"] == "worker0"

    def test_generic_span_uses_name_field(self, tdir):
        with span("data_loading"):
            pass
        events = tevents.read_dir(tdir)
        assert [e["ev"] for e in events] == ["span_begin", "span_end"]
        assert events[0]["name"] == "data_loading"
        trace = to_chrome_trace(events)
        assert trace["traceEvents"][0]["name"] == "data_loading"


# -- HTTP endpoint -----------------------------------------------------------


class TestHTTPEndpoint:
    def test_metrics_and_goodput_served(self):
        reg = tmetrics.MetricsRegistry()
        reg.counter("served_total", "x").inc()
        acc = GoodputAccountant()
        acc.ingest([_ev("step", 0.0), _ev("step", 5.0)])
        server = TelemetryHTTPServer(
            registry=reg, goodput_source=acc.summary, host="127.0.0.1"
        )
        try:
            addr = server.start()
            with urllib.request.urlopen(f"http://{addr}/metrics") as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                body = r.read().decode()
            assert "served_total 1" in body
            for line in body.strip().splitlines():
                if not line.startswith("#"):
                    assert _SAMPLE_RE.match(line)
            with urllib.request.urlopen(
                f"http://{addr}/goodput.json"
            ) as r:
                data = json.loads(r.read())
            assert data["goodput_pct"] == 100.0
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"http://{addr}/nope")
        finally:
            server.stop()
        # final snapshot survives the server for in-process harnesses
        assert last_goodput()["goodput_pct"] == 100.0

    def test_endpoints_stamped_and_diagnosis_served(self, monkeypatch):
        monkeypatch.setenv("DLROVER_JOB_UID", "job-abc")
        monkeypatch.setenv("DLROVER_RESTART_COUNT", "2")
        verdicts = [
            {"t": 1.0, "action": "restart_worker", "reason": "hang",
             "nodes": [["worker", 0]]},
        ]
        server = TelemetryHTTPServer(
            registry=tmetrics.MetricsRegistry(),
            goodput_source=lambda: {"goodput_pct": 50.0},
            diagnosis_source=lambda: verdicts,
            host="127.0.0.1",
        )
        try:
            addr = server.start()
            with urllib.request.urlopen(
                f"http://{addr}/goodput.json"
            ) as r:
                data = json.loads(r.read())
            assert data["schema_version"] == tevents.SCHEMA_VERSION
            assert data["run"] == "job-abc"
            assert data["attempt"] == 2
            assert data["goodput_pct"] == 50.0
            with urllib.request.urlopen(f"http://{addr}/metrics") as r:
                body = r.read().decode()
            info = [
                ln for ln in body.splitlines()
                if ln.startswith("dlrover_telemetry_info")
            ]
            assert len(info) == 1
            assert 'run="job-abc"' in info[0]
            assert 'attempt="2"' in info[0]
            assert _SAMPLE_RE.match(info[0])
            with urllib.request.urlopen(
                f"http://{addr}/diagnosis.json"
            ) as r:
                diag = json.loads(r.read())
            assert diag["run"] == "job-abc"
            assert diag["verdicts"] == verdicts
        finally:
            server.stop()


class TestVerdictPersistence:
    def test_record_verdict_is_durable_and_bounded(self, tdir):
        from dlrover_tpu.master.diagnosis.diagnosis import (
            DiagnosisAction,
            DiagnosisManager,
        )

        mgr = DiagnosisManager()
        mgr.record_verdict(DiagnosisAction(
            action="restart_worker", reason="hang detected",
            nodes=[("worker", 1)],
        ))
        history = mgr.verdict_history()
        assert len(history) == 1
        assert history[0]["action"] == "restart_worker"
        assert history[0]["nodes"] == [["worker", 1]]
        # Durable copy: a first-class event on the master's own stream.
        events = tevents.read_dir(tdir)
        verdicts = [e for e in events if e["ev"] == "verdict"]
        assert len(verdicts) == 1
        assert verdicts[0]["role"] == "master"
        assert verdicts[0]["action"] == "restart_worker"
        assert verdicts[0]["reason"] == "hang detected"
        # History stays bounded.
        for i in range(DiagnosisManager.MAX_HISTORY + 10):
            mgr.record_verdict(DiagnosisAction(action="report",
                                               reason=str(i)))
        assert len(mgr.verdict_history()) == DiagnosisManager.MAX_HISTORY

    def test_diagnose_once_records_each_action(self, tdir):
        from dlrover_tpu.master.diagnosis.diagnosis import (
            DiagnosisAction,
            Diagnostician,
            DiagnosisManager,
        )

        class Canned(Diagnostician):
            def diagnose(self):
                return [DiagnosisAction(action="report", reason="x")]

        handled = []
        mgr = DiagnosisManager(
            Canned(), action_handler=handled.append
        )
        mgr.diagnose_once()
        assert [v["action"] for v in mgr.verdict_history()] == ["report"]
        assert len(handled) == 1

    def test_verdicts_do_not_move_goodput(self):
        acc = GoodputAccountant()
        acc.ingest([
            _ev("step", 0.0),
            _ev("verdict", 2.0, action="report"),
            _ev("step", 10.0),
        ])
        assert acc.summary()["goodput_pct"] == 100.0


# -- master RPC pipeline -----------------------------------------------------


class TestMasterPipeline:
    def test_report_and_get_goodput_over_rpc(self):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.local_master import LocalJobMaster

        m = LocalJobMaster(port=0, node_num=1)
        m.run(blocking=False)
        try:
            c = MasterClient(m.addr, node_id=0, node_type="worker")
            assert c.ready(10)
            assert c.report_telemetry_events(
                [_ev("step", 1.0), _ev("step", 2.0)]
            )
            data = c.get_goodput()
            assert data["goodput_pct"] == 100.0
            assert data["ranks"]["worker0"]["events"] == 2
            # the HTTP endpoint serves the same accountant
            addr = m.telemetry_http.addr
            with urllib.request.urlopen(
                f"http://{addr}/goodput.json"
            ) as r:
                assert json.loads(r.read())["events_ingested"] == 2
        finally:
            m.stop()


# -- satellites --------------------------------------------------------------


class TestSatellites:
    def test_speed_monitor_reset_restarts_stall_clock(self):
        from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

        sm = SpeedMonitor()
        sm.collect_global_step(10, time.time())
        # simulate a long-stalled monitor
        sm._last_progress_ts = time.time() - 9999
        sm._stall_warned = True
        assert sm.stall_verdict(warn_after=60, restart_after=600) == (
            "restart"
        )
        sm.reset_running_speed_monitor()
        # reform must not inherit the stale stall clock
        assert len(sm._global_step_records) == 0
        assert sm.seconds_since_progress() < 5
        assert sm._stall_warned is False
        assert sm.stall_verdict(warn_after=60, restart_after=600) == ""

    def test_stats_reporter_bounded_deque(self):
        from collections import deque

        from dlrover_tpu.master.stats.reporter import LocalStatsReporter

        rep = LocalStatsReporter()
        assert isinstance(rep.runtime_stats, deque)
        for i in range(600):
            rep.report_runtime_stats(
                type("R", (), {"global_step": i})()
            )
        assert len(rep.runtime_stats) == 500
        assert rep.runtime_stats[0].global_step == 100

    def test_progress_stamps_and_staleness(self, tmp_path, tdir,
                                           monkeypatch):
        from dlrover_tpu.agent.monitor import progress

        monkeypatch.setenv("DLROVER_JOB_UID", "run-xyz")
        monkeypatch.setenv("DLROVER_RESTART_COUNT", "4")
        d = str(tmp_path / "prog")
        progress.publish_progress(7, directory=d)
        snaps = progress.read_progress(d)
        snap = snaps[os.getpid()]
        assert snap["step"] == 7
        assert snap["run"] == "run-xyz"
        assert snap["attempt"] == 4
        # telemetry "step" event rode the same publish call
        events = tevents.read_dir(tdir)
        assert [e["ev"] for e in events] == ["step"]
        assert events[0]["step"] == 7
        # stale snapshot (dead pid from a previous run) is dropped
        stale = {"ts": time.time() - 7200, "step": 99, "pid": 12345}
        with open(os.path.join(d, "progress_12345.json"), "w") as f:
            json.dump(stale, f)
        assert 12345 not in progress.read_progress(d)
        assert progress.max_progress_step(d) == 7

    def test_speed_monitor_step_reaches_the_registry(self):
        from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

        SpeedMonitor().collect_global_step(1, time.time())
        assert tmetrics.REGISTRY.counts().get(
            "dlrover_training_global_step"
        ) == 1
        assert "dlrover_training_global_step" in tmetrics.REGISTRY.render()


# -- 2-process kill/recovery through the full online pipeline ----------------

_WORKER_SRC = textwrap.dedent(
    """
    import os, sys, time
    sys.path.insert(0, {repo!r})
    from dlrover_tpu.telemetry.events import EventLog
    from dlrover_tpu.telemetry.spans import span

    rank = int(sys.argv[1])
    attempt = int(sys.argv[2])
    log = EventLog({tdir!r}, rank=rank, role="worker", run_id="killtest",
                   attempt=attempt)
    log.emit("process_start")
    log.emit("rendezvous", round=attempt)
    if attempt > 0:
        with span("restore", log=log):
            time.sleep(0.15)
    with span("compile", log=log):
        time.sleep(0.1)
    step = 0
    while True:
        time.sleep(0.04)
        step += 1
        log.emit("step", step=step)
    """
)


def _subsequence(needle, haystack):
    it = iter(haystack)
    return all(x in it for x in needle)


@pytest.mark.telemetry
def test_kill_recovery_attribution_order(tmp_path):
    """Two real worker processes emit telemetry; one is SIGKILLed and
    respawned; the master's aggregated online goodput must name the
    recovery phases in order: productive → detect+respawn → rendezvous
    → restore → compile → productive."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.local_master import LocalJobMaster

    tdir = str(tmp_path / "telemetry")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER_SRC.format(repo=repo, tdir=tdir))

    def spawn(rank, attempt):
        return subprocess.Popen(
            [sys.executable, str(script), str(rank), str(attempt)],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )

    m = LocalJobMaster(port=0, node_num=2)
    m.run(blocking=False)
    procs = []
    try:
        client = MasterClient(m.addr, node_id=0, node_type="worker")
        assert client.ready(10)
        shipper = tevents.EventShipper(tdir)
        procs = [spawn(0, 0), spawn(1, 0)]
        time.sleep(1.0)  # both workers stepping
        tevents.ship_events(shipper, client)
        os.kill(procs[0].pid, signal.SIGKILL)  # mid-write is fine
        procs[0].wait()
        time.sleep(0.3)  # detection window
        procs.append(spawn(0, 1))  # respawn, attempt+1
        time.sleep(1.2)  # restore + compile + fresh steps
        tevents.ship_events(shipper, client)

        addr = m.telemetry_http.addr
        with urllib.request.urlopen(f"http://{addr}/goodput.json") as r:
            data = json.loads(r.read())

        w0 = data["ranks"]["worker0"]
        order = [s["phase"] for s in w0["segments"]]
        assert _subsequence(
            ["productive", "detect_respawn", "rendezvous", "restore",
             "compile", "productive"],
            order,
        ), f"recovery phases out of order: {order}"
        assert w0["phases"]["detect_respawn"] >= 0.3
        assert w0["phases"]["restore"] >= 0.1
        # the healthy rank never left productive after its first step
        w1 = data["ranks"]["worker1"]
        assert w1["goodput_pct"] > 90.0
        # aggregate blends both ranks — the kill must cost rank 0
        assert data["goodput_pct"] < 100.0
        assert w0["goodput_pct"] < w1["goodput_pct"]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        m.stop()
