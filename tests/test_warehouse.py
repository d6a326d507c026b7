"""Telemetry warehouse: durable cross-job stats in the Brain store.

Covers the versioned sqlite schema, the five durable record kinds, the
master servicer's batched ingestion path, retention, the read-side
warm-start queries consumed by ``auto/planner.py``, the flat-file
backfill, the ``python -m dlrover_tpu.brain report`` CLI, the Brain RPC
warehouse messages, and the RPC-layer metrics satellite.

The acceptance test at the bottom runs two REAL worker processes and
checks the warehouse sqlite reproduces what the online accountant and
doctor saw.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from dlrover_tpu.brain.warehouse import (
    SCHEMA_VERSION,
    TelemetryWarehouse,
    config_fingerprint,
)

pytestmark = pytest.mark.telemetry

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _mk(tmp_path=None, name="wh.sqlite"):
    if tmp_path is None:
        return TelemetryWarehouse()
    return TelemetryWarehouse(os.path.join(str(tmp_path), name))


class TestSchema:
    def test_version_stamped_and_survives_reopen(self, tmp_path):
        db = os.path.join(str(tmp_path), "wh.sqlite")
        wh = TelemetryWarehouse(db)
        assert wh.schema_version == SCHEMA_VERSION
        fp = wh.register_run(
            "job-1", run="r1", attempt=2,
            config={"model": {"layers": 4}},
            versions={"python": "3.10"},
        )
        wh.add_goodput_summary("job-1", {"goodput_pct": 95.0},
                               run="r1", attempt=2)
        wh.close()

        wh2 = TelemetryWarehouse(db)
        assert wh2.schema_version == SCHEMA_VERSION
        run = wh2.get_run("job-1", run="r1", attempt=2)
        assert run["fingerprint"] == fp
        assert run["config"] == {"model": {"layers": 4}}
        assert run["versions"] == {"python": "3.10"}
        assert len(wh2.records("job-1", kind="goodput")) == 1
        wh2.close()

    def test_register_run_upserts(self):
        wh = _mk()
        wh.register_run("j", run="r", config={"a": 1})
        fp2 = wh.register_run("j", run="r", config={"a": 2})
        assert len(wh.runs("j")) == 1
        assert wh.get_run("j", run="r")["config"] == {"a": 2}
        assert fp2 == config_fingerprint({"a": 2})
        wh.close()

    def test_update_run_config_merges_and_refingerprints(self):
        wh = _mk()
        fp1 = wh.register_run("j", config={"model": {"d": 128}})
        fp2 = wh.update_run_config("j", {"mesh": {"dp": 8}})
        assert fp1 != fp2
        assert wh.get_run("j")["config"] == {
            "model": {"d": 128}, "mesh": {"dp": 8},
        }
        # creates the row when config arrives before registration
        wh.update_run_config("j2", {"x": 1})
        assert wh.get_run("j2")["config"] == {"x": 1}
        wh.close()

    def test_fingerprint_is_stable_and_order_insensitive(self):
        assert config_fingerprint({"a": 1, "b": 2}) == config_fingerprint(
            {"b": 2, "a": 1}
        )
        assert config_fingerprint(None) == config_fingerprint({})
        assert config_fingerprint({"a": 1}) != config_fingerprint({"a": 2})


class TestRecordKinds:
    def test_all_five_kinds_land(self):
        wh = _mk()
        wh.add_goodput_summary(
            "j", {"goodput_pct": 91.5, "window_s": 30.0,
                  "events_ingested": 12}
        )
        wh.add_incident("j", trigger="straggler", reason="3x skew",
                        nodes=[("worker", 1)])
        wh.add_step_phase(
            "j", {"data_wait_s": 0.01, "device_s": 0.2, "total_s": 0.25},
            rank="worker0",
        )
        wh.add_memory_watermark("j", 2 ** 30, rank="worker0")
        wh.add_perf_entry("j", {"ts": "2026-08-05T12:00:00",
                                "tokens_per_sec": 1e5, "source": "bench"})
        kinds = {r["kind"] for r in wh.records("j")}
        assert kinds == {"goodput", "incident", "step_phase",
                         "device_mem", "perf"}
        # the ISO-8601 perf timestamp was coerced to epoch seconds
        perf = wh.records("j", kind="perf")[0]
        assert isinstance(perf["t"], float) and perf["t"] > 1e9
        assert perf["value"] == 1e5
        wh.close()

    def test_unknown_kind_raises_but_batch_drops(self):
        wh = _mk()
        with pytest.raises(ValueError):
            wh._add("j", "bogus")
        n = wh.add_records("j", [
            {"kind": "goodput", "value": 90.0},
            {"kind": "bogus", "value": 1.0},
            "not-a-dict",
        ])
        assert n == 1
        assert [r["kind"] for r in wh.records("j")] == ["goodput"]
        wh.close()

    def test_ingest_events_batches_durable_kinds_only(self):
        wh = _mk()
        events = [
            {"ev": "step", "role": "worker", "rank": 0, "t": 1.0},
            {"ev": "step_phase", "role": "worker", "rank": 0, "t": 2.0,
             "run": "r1", "attempt": 1, "data_wait_s": 0.01,
             "device_s": 0.2, "total_s": 0.25, "step": 7,
             "mem_peak_bytes": 4096, "mem_devices": 8},
            {"ev": "verdict", "role": "master", "rank": 0, "t": 3.0,
             "action": "straggler", "reason": "skew",
             "nodes": [["worker", 1]]},
            {"ev": "stall", "role": "worker", "rank": 1, "t": 4.0},
        ]
        counts = wh.ingest_events("j", events)
        assert counts == {"step_phase": 1, "device_mem": 1, "incident": 1}
        sp = wh.records("j", kind="step_phase")[0]
        assert sp["run"] == "r1" and sp["attempt"] == 1
        assert sp["payload"]["step"] == 7
        assert sp["value"] == 0.25
        mem = wh.records("j", kind="device_mem")[0]
        assert mem["value"] == 4096.0
        assert mem["payload"]["devices"] == 8
        inc = wh.records("j", kind="incident")[0]
        assert inc["trigger"] == "straggler"
        assert inc["payload"]["nodes"] == [["worker", 1]]
        # raw step/stall events stay in the JSONL streams
        assert len(wh.records("j")) == 3
        wh.close()


class TestQueries:
    def _seed(self, wh):
        fp = wh.register_run("jobA", run="r1",
                             config={"model": {"d": 64}, "mesh": {"dp": 2}})
        wh.register_run("jobB", run="r1",
                        config={"model": {"d": 64}, "mesh": {"dp": 2}})
        wh.register_run("jobC", run="r1", config={"other": True})
        wh.add_goodput_summary("jobA", {"goodput_pct": 90.0}, run="r1",
                               t=10.0)
        wh.add_goodput_summary("jobA", {"goodput_pct": 93.0}, run="r1",
                               t=20.0)
        wh.add_goodput_summary("jobB", {"goodput_pct": 99.0}, run="r1",
                               t=10.0)
        wh.add_perf_entry("jobA", {"ts": 15.0, "tokens_per_sec": 120000.0,
                                   "source": "train"}, run="r1")
        wh.add_incident("jobA", trigger="straggler", reason="skew",
                        nodes=[("worker", 1)], run="r1", t=12.0)
        wh.add_incident("jobA", trigger="straggler", reason="again",
                        nodes=[("worker", 1)], run="r1", t=13.0)
        wh.add_incident("jobB", trigger="hang", reason="barrier",
                        nodes=[("worker", 0)], run="r1", t=14.0)
        return fp

    def test_history_annotates_outcomes(self):
        wh = _mk()
        fp = self._seed(wh)
        hist = {h["job_uid"]: h for h in wh.history(fp)}
        assert set(hist) == {"jobA", "jobB"}  # jobC: different fingerprint
        a = hist["jobA"]
        assert a["goodput_avg"] == pytest.approx(91.5)
        assert a["goodput_last"] == pytest.approx(93.0)
        assert a["best_tokens_per_sec"] == pytest.approx(120000.0)
        assert a["incidents"] == 2
        wh.close()

    def test_best_known_config_prefers_perf_evidence(self):
        wh = _mk()
        fp = self._seed(wh)
        # jobB has higher goodput, but jobA has a real tokens/s
        # measurement — perf evidence outranks goodput.
        best = wh.best_known_config(fp)
        assert best["job_uid"] == "jobA"
        assert best["score_source"] == "tokens_per_sec"
        assert best["score"] == pytest.approx(120000.0)
        assert best["config"] == {"model": {"d": 64}, "mesh": {"dp": 2}}
        assert wh.best_known_config("nope") is None
        wh.close()

    def test_goodput_trend_and_incident_frequency(self):
        wh = _mk()
        self._seed(wh)
        trend = wh.goodput_trend("jobA")
        assert [p["goodput_pct"] for p in trend] == [90.0, 93.0]
        freq = wh.incident_frequency()
        assert freq == {"straggler": 2, "hang": 1}
        assert wh.incident_frequency("jobB") == {"hang": 1}
        wh.close()

    def test_straggler_offenders_counts_repeats(self):
        wh = _mk()
        self._seed(wh)
        off = wh.straggler_offenders()
        assert off.get("worker1") == 2  # hang trigger is not an offender
        assert "worker0" not in off
        wh.close()

    def test_clean_retention(self):
        wh = _mk()
        now = time.time()
        wh.register_run("old-job", run="r")
        wh.add_goodput_summary("old-job", {"goodput_pct": 50.0},
                               t=now - 200 * 86400)
        wh.register_run("new-job", run="r")
        for i in range(10):
            wh.add_goodput_summary("new-job", {"goodput_pct": 90.0},
                                   t=now - i)
        out = wh.clean(max_age_s=90 * 86400, max_records_per_job=5)
        # the ancient record and the per-job overflow both go
        assert out["records"] == 1 + 5
        assert len(wh.records("new-job")) == 5
        assert wh.records("old-job") == []
        # a run with no records left and a stale update stamp compacts
        wh2 = _mk()
        wh2.register_run("stale", run="r")
        with wh2._lock:
            wh2._conn.execute(
                "UPDATE runs SET updated=?", (now - 100 * 86400,)
            )
            wh2._conn.commit()
        assert wh2.clean(max_age_s=90 * 86400)["runs"] == 1
        assert wh2.runs() == []
        wh.close()
        wh2.close()


class TestBackfill:
    def _write_flat_files(self, root):
        ledger = [
            {"ts": "2026-08-01T10:00:00", "round": "r01",
             "tokens_per_sec": 100000.0, "mfu": 0.40, "source": "bench",
             "backend": "cpu", "measured": True, "blind": False},
            {"ts": "2026-08-02T10:00:00", "round": "r02",
             "tokens_per_sec": 118000.0, "mfu": 0.48, "source": "bench",
             "backend": "cpu", "measured": True, "blind": False},
        ]
        with open(os.path.join(root, "perf_history.jsonl"), "w") as f:
            for e in ledger:
                f.write(json.dumps(e) + "\n")
            f.write('{"torn": ')  # crashed appender's partial line
        bench = {
            "rc": 0,
            "parsed": {"metric": "train_throughput_gpt2s_1chip",
                       "value": 99000.0, "unit": "tokens/s",
                       "backend": "cpu", "mfu": 0.39},
        }
        with open(os.path.join(root, "BENCH_r03.json"), "w") as f:
            json.dump(bench, f)

    def test_backfill_ledger_and_bench(self, tmp_path):
        root = str(tmp_path)
        self._write_flat_files(root)
        wh = _mk(tmp_path)
        counts = wh.backfill(root=root)
        assert counts == {"ledger": 2, "bench": 1}
        # one run per ledger round + one per bench file
        assert {r["run"] for r in wh.runs("perf-ledger")} == {"r01", "r02"}
        assert {r["run"] for r in wh.runs("bench")} == {"r03"}
        trend = wh.perf_trend()
        by_round = {p["round"]: p for p in trend}
        assert by_round["r02"]["tokens_per_sec"] == pytest.approx(118000.0)
        assert by_round["r02"]["mfu"] == pytest.approx(0.48)
        assert by_round["r03"]["tokens_per_sec"] == pytest.approx(99000.0)
        wh.close()

    def test_backfill_reads_program_history_never_the_drivers_ledger(
        self, tmp_path
    ):
        """A checkout-shaped fixture directory: every kind of line the
        program writes is ingested, and the file under the driver's name
        (``PERF_LEDGER.jsonl``, a decoy there) is never opened."""
        root = os.path.join(REPO, "tests", "fixtures", "perf_history")
        assert os.path.exists(os.path.join(root, "PERF_LEDGER.jsonl"))
        wh = _mk(tmp_path)
        counts = wh.backfill(root=root)
        assert counts == {"ledger": 7, "bench": 1}  # torn tail dropped
        runs = {r["run"] for r in wh.runs("perf-ledger")}
        assert runs == {"s01", "s02", "s03"}
        assert "driver-decoy" not in runs
        trend = wh.perf_trend()
        assert all(p["tokens_per_sec"] != 7.0 for p in trend)
        assert any(
            p["round"] == "s02"
            and p["tokens_per_sec"] == pytest.approx(120000.0)
            for p in trend
        )
        assert wh.kv_trend() and wh.serve_trend()
        wh.close()


class TestReportAndCli:
    def _seeded_db(self, tmp_path):
        db = os.path.join(str(tmp_path), "wh.sqlite")
        wh = TelemetryWarehouse(db)
        wh.register_run("jobA", run="r1", config={"model": {"d": 64}})
        wh.add_goodput_summary("jobA", {"goodput_pct": 92.0,
                                        "window_s": 30.0}, run="r1")
        wh.add_incident("jobA", trigger="straggler", reason="skew",
                        nodes=[("worker", 1)], run="r1")
        wh.add_perf_entry("jobA", {"ts": 10.0, "round": "r1",
                                   "tokens_per_sec": 50000.0,
                                   "mfu": 0.3, "source": "train"},
                          run="r1")
        wh.close()
        return db

    def test_markdown_sections(self, tmp_path):
        from dlrover_tpu.brain.report import build_report, render_markdown

        wh = TelemetryWarehouse(self._seeded_db(tmp_path))
        md = render_markdown(build_report(wh))
        wh.close()
        assert "## Goodput trend" in md
        assert "## Perf / MFU trend" in md
        assert "## Incident frequency by trigger" in md
        assert "## Straggler repeat offenders" in md
        assert "straggler" in md and "jobA" in md

    def test_report_cli_json(self, tmp_path):
        db = self._seeded_db(tmp_path)
        out = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.brain", "report",
             "--db", db, "--json", "-"],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert out.returncode == 0, out.stderr
        report = json.loads(out.stdout)
        assert report["schema_version"] == SCHEMA_VERSION
        assert "jobA" in report["jobs"]
        assert report["incident_frequency"] == {"straggler": 1}
        assert report["jobs"]["jobA"]["goodput_last"] == pytest.approx(92.0)

    def test_report_cli_missing_db_exits_2(self, tmp_path):
        out = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.brain", "report",
             "--db", os.path.join(str(tmp_path), "nope.sqlite")],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert out.returncode == 2
        assert "not found" in out.stderr

    def test_backfill_cli(self, tmp_path):
        db = os.path.join(str(tmp_path), "bf.sqlite")
        TestBackfill()._write_flat_files(str(tmp_path))
        out = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.brain", "backfill",
             "--db", db, "--root", str(tmp_path)],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert out.returncode == 0, out.stderr
        counts = json.loads(out.stdout)
        assert counts["ledger"] == 2 and counts["bench"] == 1
        assert os.path.exists(db)


class TestBrainRpcIngestion:
    def test_run_meta_and_batch_over_servicer(self):
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.brain.store import JobStatsStore
        from dlrover_tpu.common import comm

        store, wh = JobStatsStore(), _mk()
        servicer = BrainServicer(store, warehouse=wh)
        assert servicer.report(0, "master", comm.BrainRunMeta(
            job_uuid="u1", run="r1", attempt=1,
            config={"model": {"d": 8}}, versions={"jax": "x"},
        ))
        assert servicer.report(0, "master", comm.BrainWarehouseBatch(
            job_uuid="u1",
            records=[
                {"kind": "goodput", "run": "r1", "attempt": 1,
                 "value": 88.0, "payload": {"window_s": 30.0}},
                {"kind": "incident", "run": "r1", "attempt": 1,
                 "trigger": "hang", "payload": {"reason": "barrier"}},
            ],
        ))
        run = wh.get_run("u1", run="r1", attempt=1)
        assert run["config"] == {"model": {"d": 8}}
        assert len(wh.records("u1")) == 2
        assert wh.incident_frequency("u1") == {"hang": 1}
        store.close()
        wh.close()

    def test_no_warehouse_reports_false(self):
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.brain.store import JobStatsStore
        from dlrover_tpu.common import comm

        store = JobStatsStore()
        servicer = BrainServicer(store)
        assert not servicer.report(0, "m", comm.BrainRunMeta(job_uuid="u"))
        assert not servicer.report(
            0, "m", comm.BrainWarehouseBatch(job_uuid="u")
        )
        store.close()

    def test_brain_client_round_trip(self):
        from dlrover_tpu.brain.client import BrainClient
        from dlrover_tpu.brain.service import BrainService

        service = BrainService(port=0)
        service.start()
        try:
            client = BrainClient(service.addr)
            assert client.register_run(
                "u2", run="r1", config={"mesh": {"dp": 4}},
            )
            assert client.report_warehouse_records("u2", [
                {"kind": "goodput", "run": "r1", "value": 95.0},
            ])
            assert service.warehouse.get_run("u2", run="r1")["config"] == {
                "mesh": {"dp": 4},
            }
            assert len(service.warehouse.records("u2")) == 1
        finally:
            service.stop()


class TestPlannerWarmStart:
    def _history_db(self, tmp_path, model, mesh):
        db = os.path.join(str(tmp_path), "wh.sqlite")
        wh = TelemetryWarehouse(db)
        wh.register_run(
            "hist-job", run="r1",
            config={"model": model, "mesh": mesh},
        )
        wh.add_perf_entry("hist-job", {"ts": 10.0,
                                       "tokens_per_sec": 77000.0,
                                       "source": "train"}, run="r1")
        wh.close()
        return db

    def test_warm_start_returns_matching_history(self, tmp_path):
        from dlrover_tpu.auto.planner import warehouse_warm_start

        model = {"n_layers": 4, "d_model": 256}
        mesh = {"dp": 2, "tp": 4}
        db = self._history_db(tmp_path, model, mesh)
        hint = warehouse_warm_start(
            model_config=model, mesh_shape=mesh, db_path=db
        )
        assert hint is not None
        assert hint["job_uid"] == "hist-job"
        assert hint["config"] == {"model": model, "mesh": mesh}
        assert hint["score"] == pytest.approx(77000.0)
        assert hint["score_source"] == "tokens_per_sec"
        # a different mesh fingerprint finds nothing
        assert warehouse_warm_start(
            model_config=model, mesh_shape={"dp": 8}, db_path=db
        ) is None

    def test_warm_start_disabled_or_missing_db(self, tmp_path, monkeypatch):
        from dlrover_tpu.auto.planner import warehouse_warm_start

        db = self._history_db(tmp_path, {"d": 1}, {"dp": 1})
        monkeypatch.setenv("DLROVER_WAREHOUSE", "0")
        assert warehouse_warm_start(
            model_config={"d": 1}, mesh_shape={"dp": 1}, db_path=db
        ) is None
        monkeypatch.delenv("DLROVER_WAREHOUSE")
        assert warehouse_warm_start(
            model_config={"d": 1}, mesh_shape={"dp": 1},
            db_path=os.path.join(str(tmp_path), "absent.sqlite"),
        ) is None


class TestLocalMasterWiring:
    def test_open_warehouse_registers_run(self, tmp_path, monkeypatch):
        from dlrover_tpu.master.local_master import LocalJobMaster

        db = os.path.join(str(tmp_path), "wh.sqlite")
        monkeypatch.setenv("DLROVER_WAREHOUSE_DB", db)
        monkeypatch.setenv("DLROVER_JOB_UID", "local-uid")
        monkeypatch.setenv("DLROVER_RESTART_COUNT", "2")
        wh = LocalJobMaster._open_warehouse()
        assert wh is not None
        run = wh.get_run("local-uid", run="local-uid", attempt=2)
        assert run is not None
        assert "python" in run["versions"]
        wh.close()

    def test_open_warehouse_disabled(self, monkeypatch):
        from dlrover_tpu.master.local_master import LocalJobMaster

        monkeypatch.setenv("DLROVER_WAREHOUSE", "0")
        assert LocalJobMaster._open_warehouse() is None


class TestRpcMetrics:
    def test_transport_latency_histogram(self):
        from dlrover_tpu.common import comm
        from dlrover_tpu.rpc.transport import (
            MasterTransport,
            TransportClient,
        )
        from dlrover_tpu.telemetry.metrics import REGISTRY

        class _Echo:
            def get(self, node_id, node_type, message):
                return message

            def report(self, node_id, node_type, message):
                return True

        server = MasterTransport(_Echo(), port=0)
        server.start()
        try:
            client = TransportClient(f"127.0.0.1:{server.port}")
            client.get(0, "w", comm.KeyValueRequest(key="k"))
            client.report(0, "w", comm.KeyValuePair(key="k", value=b"v"))
            client.close()
        finally:
            server.stop()
        hist = REGISTRY.get("dlrover_rpc_latency_seconds")
        assert hist is not None
        sample_keys = {key for _, key, _ in hist.samples()}
        methods = {dict(k).get("method") for k in sample_keys}
        assert {"get", "report"} <= methods

    def test_retry_and_error_counters(self, monkeypatch):
        from dlrover_tpu.agent import master_client as mc
        from dlrover_tpu.telemetry.metrics import REGISTRY

        monkeypatch.setattr(
            mc.JobConstant, "MASTER_CLIENT_MAX_RETRY", 2,
        )
        # tiny but positive: a zero delay reads as wall-budget exhausted
        monkeypatch.setattr(mc, "_retry_delay", lambda i: 0.001)

        class _Flaky:
            @mc.retry_rpc
            def always_down(self):
                raise ConnectionError("nope")

        with pytest.raises(RuntimeError, match="failed after 2 tries"):
            _Flaky().always_down()

        retries = REGISTRY.get("dlrover_rpc_retries_total")
        errors = REGISTRY.get("dlrover_rpc_errors_total")
        assert retries is not None and errors is not None

        def _value(metric, **labels):
            want = frozenset(labels.items())
            for _, key, value in metric.samples():
                if frozenset(key) == want:
                    return value
            return 0.0

        assert _value(retries, method="always_down") == 2.0
        assert _value(errors, method="always_down") == 1.0

    def test_rpc_metric_names_are_dlr008_clean(self):
        # the DLR008 checker's core contract, asserted directly: counter
        # names end in _total, timings in _seconds, all dlrover_-prefixed
        for name in ("dlrover_rpc_latency_seconds",
                     "dlrover_rpc_retries_total",
                     "dlrover_rpc_errors_total"):
            assert name.startswith("dlrover_")
        from dlrover_tpu.telemetry.metrics import render_metrics

        text = render_metrics()
        assert "dlrover_rpc_latency_seconds" in text


class TestEndToEndWarehouse:
    def test_two_process_run_lands_durable_history(
        self, tmp_path, monkeypatch
    ):
        """Acceptance: two REAL worker processes emit telemetry; the
        master servicer's RPC path warehouses it.  The sqlite then
        reproduces what the online side saw: at least one goodput
        summary within 3 points of the live accountant, and the doctor's
        straggler verdict as a durable incident the report CLI names."""
        from dlrover_tpu.common import comm
        from dlrover_tpu.master.diagnosis.diagnosis import DiagnosisManager
        from dlrover_tpu.master.monitor.straggler import StragglerDetector
        from dlrover_tpu.master.servicer import MasterServicer
        from dlrover_tpu.runtime.harness import MultiProcessWorldHarness
        from dlrover_tpu.telemetry.events import EventShipper

        shared = str(tmp_path / "telemetry")
        monkeypatch.setenv("DLROVER_TELEMETRY_DIR", shared)
        monkeypatch.setenv("DLROVER_TELEMETRY", "1")
        monkeypatch.setenv("DLROVER_JOB_UID", "wh-e2e")
        monkeypatch.setenv("DLROVER_RESTART_COUNT", "0")

        db = os.path.join(str(tmp_path), "warehouse.sqlite")
        warehouse = TelemetryWarehouse(db)
        warehouse.register_run("wh-e2e", run="wh-e2e", attempt=0,
                               config={"model": {"name": "straggler-e2e"}})
        dm = DiagnosisManager()
        dm.attach_warehouse(warehouse, job_uid="wh-e2e")
        servicer = MasterServicer(
            diagnosis_manager=dm,
            straggler_detector=StragglerDetector(diagnosis_manager=dm),
            warehouse=warehouse,
        )

        harness = MultiProcessWorldHarness(
            os.path.join(HERE, "_straggler_worker.py"),
            2,
            workdir=str(tmp_path / "work"),
            extra_env={
                "DLROVER_TELEMETRY_DIR": shared,
                "DLROVER_TELEMETRY": "1",
                "DLROVER_SLOW_RANK": "1",
                "DLROVER_JOB_UID": "wh-e2e",
            },
        )
        shipper = EventShipper(shared)
        harness.start()
        try:
            # Play the agent: tail the streams and ship them over the
            # telemetry report RPC while the skew is happening.
            deadline = time.time() + 60.0
            while time.time() < deadline and any(
                hp.proc.poll() is None for hp in harness.procs
            ):
                batch = shipper.poll()
                if batch:
                    servicer._report_telemetry(
                        0, "worker", comm.TelemetryEvents(events=batch)
                    )
                time.sleep(0.05)
            codes = harness.wait(timeout_s=30.0)
        finally:
            harness.terminate()
        assert codes == {0: 0, 1: 0}
        batch = shipper.poll()
        if batch:
            servicer._report_telemetry(
                0, "worker", comm.TelemetryEvents(events=batch)
            )
        # the master's shutdown flush lands the final interval summary
        servicer.flush_warehouse()
        online = servicer.goodput_accountant.summary(detail=False)
        warehouse.close()

        # -- durable state: goodput summary + straggler incident -------
        wh = TelemetryWarehouse(db)
        goodputs = wh.records("wh-e2e", kind="goodput")
        incidents = wh.records("wh-e2e", kind="incident")
        wh.close()
        assert goodputs, "no goodput summary landed in the warehouse"
        assert any(
            r["trigger"] == "straggler" for r in incidents
        ), f"no durable straggler verdict, got {incidents}"

        # -- the report CLI names the trigger and reproduces goodput ----
        out = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.brain", "report",
             "--db", db, "--json", "-"],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert out.returncode == 0, out.stderr
        report = json.loads(out.stdout)
        assert "straggler" in report["incident_frequency"]
        assert online["goodput_pct"] is not None
        warehoused = report["jobs"]["wh-e2e"]["goodput_last"]
        assert warehoused == pytest.approx(
            online["goodput_pct"], abs=3.0
        )
