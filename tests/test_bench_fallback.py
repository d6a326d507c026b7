"""bench.py's degradation contract: a green on-chip result is archived
to BENCH_LAST_GREEN.json, and a run that finds no accelerator publishes
that archive (staleness-flagged) instead of a CPU number.  Pinned
without touching any backend.
"""

import importlib.util
import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(tmp_path, monkeypatch, capsys):
    """Import bench.py as a module with its archive path redirected (and
    the perf ledger sandboxed — every emit appends there now)."""
    monkeypatch.setenv(
        "DLROVER_PERF_LEDGER", str(tmp_path / "perf_history.jsonl")
    )
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.LAST_GREEN = str(tmp_path / "BENCH_LAST_GREEN.json")
    return mod


def _emitted_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, f"exactly one JSON line expected, got {out}"
    return json.loads(out[-1])


def test_green_tpu_emit_archives(bench, capsys):
    bench.emit(118207.2, 1.182, "tpu", extra={"steps": 85, "mfu": 0.4828})
    payload = _emitted_line(capsys)
    assert payload["backend"] == "tpu" and "error" not in payload
    rec = json.load(open(bench.LAST_GREEN))
    assert rec["value"] == 118207.2
    assert rec["archived_ts"] and rec["archived_unix"] > 0
    # sha present when git works in the repo; never raises either way
    assert "archived_sha" in rec


def test_cpu_fallback_emit_does_not_archive(bench, capsys):
    bench.emit(45.6, 0.0, "cpu-fallback", error="tpu unreachable")
    _emitted_line(capsys)
    assert not os.path.exists(bench.LAST_GREEN)


def test_errored_tpu_emit_does_not_archive(bench, capsys):
    bench.emit(100.0, 0.001, "tpu", error="timeout mid-run")
    _emitted_line(capsys)
    assert not os.path.exists(bench.LAST_GREEN)


def test_archived_fallback_round_trip(bench, capsys):
    bench.emit(118207.2, 1.182, "tpu", extra={"steps": 85})
    capsys.readouterr()
    bench._emitted = False  # new bench invocation in the same process
    assert bench._emit_archived_green("tpu unavailable") is True
    payload = _emitted_line(capsys)
    assert payload["archived"] is True
    assert payload["backend"] == "tpu"  # the measurement's true backend
    assert payload["value"] == 118207.2
    assert payload["staleness_s"] >= 0
    assert payload["fallback_reason"] == "tpu unavailable"
    assert "archived_unix" not in payload  # internal field stripped


def test_archived_fallback_without_archive_returns_false(bench, capsys):
    assert bench._emit_archived_green("tpu unavailable") is False
    assert capsys.readouterr().out == ""  # caller proceeds to CPU measurement


def test_archive_older_than_cap_is_ignored(bench, capsys):
    bench.emit(118207.2, 1.182, "tpu")
    capsys.readouterr()
    rec = json.load(open(bench.LAST_GREEN))
    rec["archived_unix"] -= bench.MAX_ARCHIVE_STALENESS_S + 60
    json.dump(rec, open(bench.LAST_GREEN, "w"))
    bench._emitted = False
    # A previous round's archive must not stand in for this round.
    assert bench._emit_archived_green("tpu unavailable") is False
    assert capsys.readouterr().out == ""


def test_archive_fallback_suppressed_by_env(bench, capsys, monkeypatch):
    bench.emit(118207.2, 1.182, "tpu")
    capsys.readouterr()
    bench._emitted = False
    # The gate presses for a fresh number on early attempts.
    monkeypatch.setenv("BENCH_NO_ARCHIVE_FALLBACK", "1")
    assert bench._emit_archived_green("tpu unavailable") is False
    assert capsys.readouterr().out == ""


def test_green_emit_lands_in_the_ledger(bench, capsys):
    from dlrover_tpu.telemetry import costmodel

    bench.emit(
        118207.2, 1.182, "tpu",
        extra={"steps": 85, "mfu": 0.4828, "n_params": 134105856},
    )
    _emitted_line(capsys)
    (entry,) = costmodel.read_ledger()
    assert entry["source"] == "bench"
    assert entry["backend"] == "tpu"
    assert entry["tokens_per_sec"] == 118207.2
    assert entry["measured"] is True and entry["blind"] is False
    assert entry["mfu"] == 0.4828
    assert entry["ts"] and entry["unix"] > 0


def test_blind_fallback_ledger_entry_is_flagged(bench, capsys):
    from dlrover_tpu.telemetry import costmodel

    bench.emit(
        45.6, 0.0, "cpu-fallback",
        error="tpu unavailable",
        extra={"steps": 5, "blind": True,
               "predicted_tpu_tokens_per_sec": 118480.0},
    )
    _emitted_line(capsys)
    (entry,) = costmodel.read_ledger()
    assert entry["blind"] is True
    assert entry["measured"] is True  # a real (if proxy) timing loop ran
    assert entry["predicted_tpu_tokens_per_sec"] == 118480.0
    assert entry["error"].startswith("tpu unavailable")


def test_watchdog_partial_is_not_measured(bench, capsys):
    from dlrover_tpu.telemetry import costmodel

    bench.emit(0.0, 0.0, "none", error="timeout after 480.0s: calibrating")
    _emitted_line(capsys)
    (entry,) = costmodel.read_ledger()
    assert entry["measured"] is False and entry["blind"] is True


def _load_round_gate():
    spec = importlib.util.spec_from_file_location(
        "round_gate_under_test", os.path.join(REPO, "scripts",
                                              "round_gate.py")
    )
    mod = importlib.util.module_from_spec(spec)
    saved = sys.argv
    sys.argv = ["round_gate.py"]
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.argv = saved
    return mod


def test_gate_accepts_archived_green():
    mod = _load_round_gate()
    archived = {"backend": "tpu", "vs_baseline": 1.182, "value": 118207.2,
                "archived": True, "staleness_s": 3600.0,
                "fallback_reason": "tpu unavailable"}
    assert mod.bench_green(archived)
    # ...but not one staler than the cap (old-commit numbers must not
    # certify the round) or with unknown staleness.
    assert not mod.bench_green(
        dict(archived, staleness_s=mod.MAX_ARCHIVE_STALENESS_S + 1)
    )
    assert not mod.bench_green(
        {k: v for k, v in archived.items() if k != "staleness_s"}
    )
    assert not mod.bench_green({"backend": "cpu-fallback", "vs_baseline": 0.0})
    assert not mod.bench_green(None)


def test_gate_perf_stage_reports_delta(tmp_path, monkeypatch):
    """run_perf prices the bench number against the calibrated
    prediction and appends the comparison to the (sandboxed) ledger."""
    from dlrover_tpu.telemetry import costmodel

    mod = _load_round_gate()
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    ledger = tmp_path / "perf_history.jsonl"
    monkeypatch.setenv("DLROVER_PERF_LEDGER", str(ledger))
    costmodel.append_ledger(
        {"source": "bench", "backend": "tpu", "tokens_per_sec": 118483.9,
         "measured": True, "blind": False, "mfu": 0.4839,
         "n_params": 134105856},
        path=str(ledger),
    )
    out = mod.run_perf({"backend": "tpu", "value": 112000.0})
    assert out["ok"] and not out["blind"]
    assert out["measured_tokens_per_sec"] == 112000.0
    # Calibrated on its own green run, the prediction round-trips to
    # that run's throughput, so the delta is just 112000/118483.9 - 1.
    assert out["predicted_tokens_per_sec"] == pytest.approx(
        118483.9, rel=0.01
    )
    assert out["delta_pct"] == pytest.approx(-5.5, abs=0.6)
    gate = [e for e in costmodel.read_ledger(str(ledger))
            if e["source"] == "gate"]
    assert len(gate) == 1
    assert gate[0]["delta_pct"] == out["delta_pct"]
    assert gate[0]["measured"] is True and gate[0]["blind"] is False


def test_gate_perf_stage_blind_without_chip(tmp_path, monkeypatch):
    from dlrover_tpu.telemetry import costmodel

    mod = _load_round_gate()
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    ledger = tmp_path / "perf_history.jsonl"
    monkeypatch.setenv("DLROVER_PERF_LEDGER", str(ledger))
    out = mod.run_perf({"backend": "cpu-fallback",
                        "error": "tpu unavailable",
                        "n_params": 134105856})
    # No chip, no measurement — but the prediction still lands, flagged
    # blind, so the round record is never throughput-empty.
    assert out["ok"] and out["blind"]
    assert out["measured_tokens_per_sec"] is None
    assert out["delta_pct"] is None
    assert out["predicted_tokens_per_sec"] > 0
    (entry,) = costmodel.read_ledger(str(ledger))
    assert entry["source"] == "gate" and entry["blind"] is True
    assert entry["measured"] is False


def test_gate_budget_rechecked_after_each_attempt(monkeypatch, tmp_path):
    """The gate decides 'last chance' AFTER each bench run too: a bench
    that eats the remaining budget triggers exactly one immediate final
    attempt (archive allowed) instead of a sleep plus an extra fresh
    attempt — the round-4 overshoot."""
    mod = _load_round_gate()
    saved = sys.argv
    calls = []

    def fake_run_bench(budget_s=480, allow_archive=False):
        calls.append(allow_archive)
        # Each fake bench "takes" 400s of the 500s budget.
        mod.T0 -= 400
        if allow_archive:
            # n_params as bench.py emits it: the perf stage predicts
            # from it (the sandboxed history holds no calibration run).
            return {"backend": "tpu", "vs_baseline": 1.1, "value": 111000.0,
                    "n_params": 134105856,
                    "archived": True, "staleness_s": 60.0}
        return {"backend": "cpu-fallback", "vs_baseline": 0.0,
                "error": "tpu unavailable"}

    monkeypatch.setattr(mod, "run_bench", fake_run_bench)
    monkeypatch.setattr(mod, "run_dryrun", lambda **kw: {"ok": True,
                                                         "rc": 0,
                                                         "tail": []})
    # The analyzer/drill stages subprocess with cwd=REPO, which this test
    # sandboxes to tmp_path — stub them like the other stage runners.
    monkeypatch.setattr(mod, "run_analysis", lambda **kw: {"ok": True,
                                                           "rc": 0})
    monkeypatch.setattr(mod, "run_corruption_drill",
                        lambda **kw: {"passed": 5, "failed": 0, "rc": 0})
    monkeypatch.setattr(mod, "run_packed_census",
                        lambda **kw: {"ok": True, "seq_len": 8192})
    monkeypatch.setattr(mod, "run_kv",
                        lambda **kw: {"ok": True,
                                      "aggregate_rows_per_s": 1.0e7,
                                      "reshard_recovery_s": 0.03,
                                      "reshard_lost_rows": 0})
    monkeypatch.setattr(mod, "run_serve",
                        lambda **kw: {"ok": True,
                                      "gateway_tokens_per_sec": 150.0,
                                      "speedup_vs_legacy": 3.3})
    monkeypatch.setattr(mod, "run_serve_chaos",
                        lambda **kw: {"ok": True, "zero_loss": True,
                                      "promoted_reform_pts": 0.1,
                                      "cold_reform_pts": 10.7,
                                      "delta_pts": 10.6,
                                      "brownout": {"peak": 3,
                                                   "released": True}})
    monkeypatch.setattr(mod, "run_kv_ha",
                        lambda **kw: {"ok": True, "zero_loss": True,
                                      "promotion": {"unavailable_s": 0.003},
                                      "chain_restore":
                                          {"unavailable_s": 0.017},
                                      "promotion_beats_chain_restore": True})
    monkeypatch.setattr(mod, "run_trace",
                        lambda **kw: {"ok": True, "requests": 12,
                                      "span_total": 100,
                                      "reconstruction": {"found": True,
                                                         "span_count": 10,
                                                         "causal": True}})
    monkeypatch.setattr(mod, "run_observer",
                        lambda **kw: {"ok": True,
                                      "divergence_verdicts": 1,
                                      "fleet_p50": 0.4,
                                      "fleetz_sources": 4})
    # subprocess.run(timeout=...) itself calls time.sleep while reaping,
    # so the sleep trap below would misfire on any real stage subprocess.
    monkeypatch.setattr(mod, "run_doctor",
                        lambda **kw: {"ok": True,
                                      "names_injected_fault": True})
    monkeypatch.setattr(mod.time, "sleep",
                        lambda s: (_ for _ in ()).throw(
                            AssertionError("gate slept past its budget")))
    mod.REPO = str(tmp_path)  # GATE_STATUS.json lands in the sandbox
    mod.T0 = mod.time.time()
    sys.argv = ["round_gate.py", "--max-wait-s", "500",
                "--retry-sleep-s", "300", "--skip-chaos"]
    try:
        with pytest.raises(SystemExit) as e:
            mod.main()
    finally:
        sys.argv = saved
    assert e.value.code == 0  # archived green accepted on the final try
    # attempt 1 fresh (no archive), attempt 2 final (archive allowed),
    # and NOTHING after — no sleep happened (the monkeypatch would throw).
    assert calls == [False, True], calls
    # The report-only perf stage ran in-process against the sandboxed
    # REPO: delta recorded in GATE_STATUS.json, ledger appended there.
    status = json.load(open(tmp_path / "GATE_STATUS.json"))
    assert status["perf"]["ok"] is True
    assert status["perf"]["measured_tokens_per_sec"] == 111000.0
    assert status["perf"]["delta_pct"] is not None
    assert (tmp_path / "perf_history.jsonl").exists()
