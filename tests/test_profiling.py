"""Perf observability: step-phase profiler, memory watermarks, the
/profile endpoint, bundle pickup of traces, and the cost-model oracle.
"""

import json
import os
import tarfile
import time
import urllib.request

import pytest

from dlrover_tpu.telemetry import costmodel
from dlrover_tpu.telemetry import events as tevents
from dlrover_tpu.telemetry import metrics as tmetrics
from dlrover_tpu.telemetry import profiling
from dlrover_tpu.telemetry.bundle import collect_bundle
from dlrover_tpu.telemetry.httpd import TelemetryHTTPServer

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def telemetry_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("DLROVER_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("DLROVER_TELEMETRY", "1")
    tevents.reset()
    yield str(tmp_path)
    tevents.reset()


def _get(addr, path):
    try:
        with urllib.request.urlopen(f"http://{addr}{path}", timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestStepPhaseProfiler:
    def test_phases_add_up(self, telemetry_tmp):
        prof = profiling.StepPhaseProfiler(emit_interval=1)
        prof.begin_step()
        time.sleep(0.02)  # data wait
        prof.mark_data()
        time.sleep(0.01)  # dispatch
        prof.mark_dispatch()
        time.sleep(0.03)  # device
        prof.end_step(7)
        rec = prof.last
        assert rec["data_wait"] >= 0.015
        assert rec["dispatch"] >= 0.005
        assert rec["device"] >= 0.02
        assert rec["total"] == pytest.approx(
            rec["data_wait"] + rec["dispatch"] + rec["device"], rel=1e-6
        )
        assert prof.steps == 1
        assert prof.summary()["mean_s"]["total"] > 0

    def test_missing_marks_degrade_to_zero(self, telemetry_tmp):
        prof = profiling.StepPhaseProfiler(emit_interval=1)
        prof.begin_step()
        time.sleep(0.01)
        prof.end_step(0)  # no mark_data / mark_dispatch
        assert prof.last["data_wait"] == 0.0
        assert prof.last["dispatch"] == 0.0
        assert prof.last["device"] == pytest.approx(prof.last["total"])

    def test_end_without_begin_is_noop(self, telemetry_tmp):
        prof = profiling.StepPhaseProfiler()
        prof.end_step(0)
        assert prof.steps == 0 and prof.last == {}

    def test_emit_interval_thins_events_not_histograms(self, telemetry_tmp):
        prof = profiling.StepPhaseProfiler(emit_interval=2)
        for i in range(4):
            prof.begin_step()
            prof.mark_data()
            prof.mark_dispatch()
            prof.end_step(i)
        events = [
            e
            for e in tevents.read_dir(telemetry_tmp)
            if e["ev"] == "step_phase"
        ]
        assert len(events) == 2  # every 2nd step
        assert prof.steps == 4  # but every step was recorded

    def test_step_phase_event_schema(self, telemetry_tmp):
        prof = profiling.StepPhaseProfiler(emit_interval=1)
        prof.begin_step()
        prof.mark_data()
        prof.mark_dispatch()
        prof.end_step(42)
        (ev,) = [
            e
            for e in tevents.read_dir(telemetry_tmp)
            if e["ev"] == "step_phase"
        ]
        assert ev["step"] == 42
        for field in ("data_wait_s", "dispatch_s", "device_s", "total_s"):
            assert field in ev

    def test_histogram_rendered_with_phase_labels(self, telemetry_tmp):
        prof = profiling.StepPhaseProfiler(emit_interval=1)
        prof.begin_step()
        prof.mark_data()
        prof.mark_dispatch()
        prof.end_step(0)
        text = tmetrics.REGISTRY.render()
        assert "dlrover_step_time_seconds" in text
        assert 'phase="device"' in text
        assert 'phase="data_wait"' in text

    def test_collective_split_is_modeled_and_labeled(self, telemetry_tmp):
        """With a WUS collective fraction installed, the device phase
        splits into device_compute/device_collective — always labeled
        as a cost-model split, never a measurement."""
        prof = profiling.StepPhaseProfiler(emit_interval=1)
        prof.set_collective_fraction(0.25, source="costmodel")
        prof.begin_step()
        prof.mark_data()
        prof.mark_dispatch()
        time.sleep(0.02)
        prof.end_step(3)
        rec = prof.last
        assert rec["device_collective"] == pytest.approx(
            rec["device"] * 0.25, rel=1e-6
        )
        assert rec["device_compute"] == pytest.approx(
            rec["device"] * 0.75, rel=1e-6
        )
        (ev,) = [
            e for e in tevents.read_dir(telemetry_tmp)
            if e["ev"] == "step_phase"
        ]
        assert ev["collective_split"] == "costmodel"
        assert "device_compute_s" in ev and "device_collective_s" in ev
        assert set(profiling.DEVICE_SPLIT_PHASES) <= set(
            prof.summary()["mean_s"]
        )
        # Turning the fraction off removes the split from new records.
        prof.set_collective_fraction(None)
        prof.begin_step()
        prof.mark_data()
        prof.mark_dispatch()
        prof.end_step(4)
        assert "device_collective" not in prof.last

    def test_global_profiler_reset(self):
        a = profiling.get_step_profiler()
        assert profiling.get_step_profiler() is a
        profiling.reset_step_profiler()
        assert profiling.get_step_profiler() is not a


class TestMemoryWatermarks:
    class FakeDev:
        def __init__(self, dev_id, stats):
            self.id = dev_id
            self._stats = stats

        def memory_stats(self):
            return self._stats

    class CpuDev:
        id = 9  # no memory_stats attribute, like jax CPU devices

    def test_watermarks_published(self):
        peaks = profiling.update_memory_watermarks(
            [
                self.FakeDev(
                    0, {"bytes_in_use": 1024, "peak_bytes_in_use": 4096}
                ),
                self.CpuDev(),
            ]
        )
        assert peaks == {"0": 4096.0}
        text = tmetrics.REGISTRY.render()
        assert "dlrover_device_memory_bytes" in text
        assert 'kind="peak"' in text and 'kind="in_use"' in text

    def test_broken_memory_stats_skipped(self):
        class Broken:
            id = 1

            def memory_stats(self):
                raise RuntimeError("backend quirk")

        assert profiling.update_memory_watermarks([Broken()]) == {}


class TestProfileEndpoint:
    def test_status_start_conflict_and_bad_args(self, telemetry_tmp):
        server = TelemetryHTTPServer(host="127.0.0.1", port=0)
        addr = server.start()
        try:
            code, payload = _get(addr, "/profile?status=1")
            assert code == 200 and payload["active"] is False
            assert payload["schema_version"] == tevents.SCHEMA_VERSION

            code, payload = _get(addr, "/profile?seconds=nope")
            assert code == 400 and payload["ok"] is False

            code, payload = _get(addr, "/profile?seconds=0.2")
            assert code == 200 and payload["ok"] is True
            trace_dir = payload["dir"]
            assert trace_dir.startswith(
                os.path.join(telemetry_tmp, "profiles")
            )

            # One capture at a time: the second request is refused.
            code, payload = _get(addr, "/profile?seconds=0.2")
            assert code == 409 and payload["error"] == "trace already active"

            deadline = time.time() + 15.0
            while time.time() < deadline:
                code, payload = _get(addr, "/profile?status=1")
                if not payload["active"]:
                    break
                time.sleep(0.05)
            assert payload["active"] is False
            assert payload["captures"] >= 1
            assert os.path.isdir(trace_dir)
            assert any(os.scandir(trace_dir)), "trace dir is empty"
        finally:
            server.stop()

    def test_index_advertises_profile(self, telemetry_tmp):
        server = TelemetryHTTPServer(host="127.0.0.1", port=0)
        addr = server.start()
        try:
            with urllib.request.urlopen(
                f"http://{addr}/", timeout=10
            ) as r:
                assert b"/profile" in r.read()
        finally:
            server.stop()


class TestBundlePicksUpProfiles:
    def test_trace_files_land_in_bundle(self, telemetry_tmp, tmp_path):
        trace_dir = os.path.join(telemetry_tmp, "profiles", "trace_1_2")
        os.makedirs(trace_dir)
        with open(os.path.join(trace_dir, "host.trace"), "wb") as f:
            f.write(b"x" * 128)
        tevents.emit("step", step=1)
        path = collect_bundle(
            "test", str(tmp_path / "bundles"), telemetry_dir=telemetry_tmp
        )
        assert path
        with tarfile.open(path) as tar:
            names = tar.getnames()
        assert "profiles/trace_1_2/host.trace" in names
        manifest_ok = any(n == "manifest.json" for n in names)
        assert manifest_ok


class TestCostModel:
    def test_prediction_round_trips_green_bench(self):
        """Calibrated on round-2's measured MFU, the 6·N·tokens model
        must reproduce round-2's own measured throughput — that's what
        'calibrated' means."""
        pred = costmodel.predict_tokens_per_sec(
            134105856, tokens_per_step=8 * 1024, backend="v5e", mfu=0.4839
        )
        assert pred["predicted_tokens_per_sec"] == pytest.approx(
            118483.9, rel=0.01
        )

    def test_aot_flops_path_beats_param_estimate(self):
        pred = costmodel.predict_step_time(
            1816984551424, backend="v5e", mfu=0.40
        )
        # 1.82 TF/step at 40% of 197 TF/s ≈ 23 ms
        assert pred["predicted_step_s"] == pytest.approx(0.02306, rel=0.01)
        assert pred["peak_flops"] == 197e12

    def test_calibration_prefers_green_then_ledger_then_assumed(
        self, tmp_path, monkeypatch
    ):
        ledger = tmp_path / "perf_history.jsonl"
        monkeypatch.setenv("DLROVER_PERF_LEDGER", str(ledger))
        # Nothing anywhere: assumed.
        cal = costmodel.load_calibration(str(tmp_path))
        assert cal["source"] == "assumed"
        assert cal["mfu"] == costmodel.DEFAULT_ASSUMED_MFU
        # Ledger with a measured green TPU entry wins over assumed.
        costmodel.append_ledger(
            {"backend": "tpu", "measured": True, "mfu": 0.48,
             "tokens_per_sec": 118000.0, "n_params": 134105856},
            path=str(ledger),
        )
        costmodel.append_ledger(  # blind entries never calibrate
            {"backend": "tpu", "measured": True, "blind": True,
             "mfu": 0.99, "tokens_per_sec": 1.0},
            path=str(ledger),
        )
        cal = costmodel.load_calibration(str(tmp_path))
        assert cal["source"] == "perf_history.jsonl"
        assert cal["mfu"] == 0.48
        # BENCH_LAST_GREEN.json beats the ledger.
        with open(tmp_path / "BENCH_LAST_GREEN.json", "w") as f:
            json.dump({"mfu": 0.4839, "value": 118483.9,
                       "n_params": 134105856}, f)
        cal = costmodel.load_calibration(str(tmp_path))
        assert cal["source"] == "BENCH_LAST_GREEN.json"
        assert cal["mfu"] == 0.4839

    def test_ledger_append_read_and_torn_line(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        assert costmodel.append_ledger({"a": 1}, path=path) == path
        costmodel.append_ledger({"b": 2}, path=path)
        with open(path, "a") as f:
            f.write('{"torn": tru')  # kill mid-write
        entries = costmodel.read_ledger(path)
        assert len(entries) == 2  # torn line dropped
        assert entries[0]["a"] == 1 and entries[1]["b"] == 2
        assert all("ts" in e for e in entries)

    def test_mixed_history_calibrates_from_its_green_round(
        self, tmp_path, monkeypatch
    ):
        """A history shaped like the program writes it — a failed round,
        one green on-chip round, then blind CPU-fallback rounds and a
        blind gate line — must calibrate from the green round: newer
        blind or failed lines never displace it."""
        ledger = tmp_path / costmodel.LEDGER_BASENAME
        monkeypatch.setenv("DLROVER_PERF_LEDGER", str(ledger))
        rows = [
            {"round": "r01", "source": "bench", "backend": "none",
             "tokens_per_sec": 0.0, "measured": False, "blind": True,
             "error": "backend setup error"},
            {"round": "r02", "source": "bench", "backend": "tpu",
             "tokens_per_sec": 120000.0, "measured": True, "blind": False,
             "mfu": 0.49, "n_params": 134105856, "steps": 80},
            {"round": "r03", "source": "bench", "backend": "cpu-fallback",
             "tokens_per_sec": 48.0, "measured": True, "blind": True,
             "predicted_tpu_tokens_per_sec": 119000.0},
            {"round": "r04", "source": "gate", "backend": "cpu-fallback",
             "tokens_per_sec": None, "measured": False, "blind": True},
            {"round": "r05", "source": "bench", "backend": "tpu",
             "tokens_per_sec": 90000.0, "measured": True, "blind": True,
             "mfu": 0.37, "archived": True},
        ]
        for row in rows:
            costmodel.append_ledger(row, path=str(ledger))
        entries = costmodel.read_ledger()
        assert [e["round"] for e in entries] == [r["round"] for r in rows]
        cal = costmodel.load_calibration(str(tmp_path))
        assert cal["source"] == costmodel.LEDGER_BASENAME
        assert cal["mfu"] == pytest.approx(0.49)
        assert cal["n_params"] == 134105856
