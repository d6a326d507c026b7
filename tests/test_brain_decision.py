"""Brain v2 decision plane (ISSUE 16 tentpole + satellites).

Covers the analytic layout planner (enumerator constraints and the
scoring arithmetic against a hand-computed oracle), the traffic
forecast fit on a synthetic diurnal trace, the predictive-vs-reactive
replay drill priced in servput points, the ``python -m
dlrover_tpu.brain plan`` CLI round-trip, the drafted-config-diff
section in a doctor incident report, and the warehouse ``traffic``
record kind the pump writes.

The acceptance tests at the bottom rescore the measured search's own
candidate pool under the same calibrated cost model (the brain space
is a superset, so its best must come within 5%), and AOT-probe the
winner with the real XLA compiler when the TPU compile-only client is
available.

Everything up to the acceptance section is jax-free: the decision
package imports no jax by design (DLR013 keeps it replayable).
"""

import json
import os
import subprocess
import sys

import pytest

from dlrover_tpu.brain.decision import (
    LayoutCandidate,
    LayoutProfile,
    TrafficForecast,
    draft_config_diff,
    enumerate_layouts,
    fit_traffic,
    forecast_from_warehouse,
    plan_capacity,
    plan_layout,
    predictive_vs_reactive,
    render_plan_markdown,
    replay_fleet,
    replica_capacity,
    score_layout,
)
from dlrover_tpu.brain.warehouse import TelemetryWarehouse
from dlrover_tpu.serving.fleet import FleetAutoscaler
from dlrover_tpu.telemetry import costmodel

pytestmark = pytest.mark.telemetry

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

HOUR = 3600.0
DAY = 86400.0


# -- layout planner ----------------------------------------------------------


def _tiny_profile(**kw):
    """Small enough to verify every scoring term by hand."""
    defaults = dict(
        num_params=1000, batch_size=4, seq_len=8, num_layers=2,
        hidden_size=4, num_heads=2, num_kv_heads=2,
    )
    defaults.update(kw)
    return LayoutProfile(**defaults)


# A spec with round numbers so oracle arithmetic stays exact.
_SPEC = {
    "backend": "test",
    "peak_flops": 1e12,
    "ici_bw_bytes": 1e9,
    "hbm_bw_bytes": 1e9,
    "hbm_capacity_bytes": 1e9,
}


def _mesh(**kw):
    m = {"pp": 1, "dp": 1, "fsdp": 1, "ep": 1, "sp": 1, "tp": 1}
    m.update(kw)
    return m


class TestLayoutEnumerator:
    def test_every_candidate_factorizes_the_device_count(self):
        prof = _tiny_profile()
        cands = enumerate_layouts(prof, 4)
        assert cands
        for c in cands:
            n = 1
            for v in c.mesh.values():
                n *= v
            assert n == 4, c.key()

    def test_tp_bounded_by_kv_heads(self):
        # kv_heads=2 — a tp=4 mesh would shard KV heads 4 ways and
        # fail to compile; the enumerator must never emit it.
        prof = _tiny_profile(num_heads=4, num_kv_heads=2)
        cands = enumerate_layouts(prof, 4)
        assert cands
        assert all(c.mesh["tp"] <= 2 for c in cands)

    def test_pp_divides_layers(self):
        prof = _tiny_profile(num_layers=3)  # 2 does not divide 3
        cands = enumerate_layouts(prof, 4)
        assert all(c.mesh["pp"] in (1, 3) for c in cands)

    def test_sp_divides_seq_len(self):
        prof = _tiny_profile(seq_len=6)  # 4 does not divide 6
        cands = enumerate_layouts(prof, 4)
        assert all(c.mesh["sp"] != 4 for c in cands)

    def test_dp_fsdp_bounded_by_microbatch(self):
        # batch=4, ga=4 -> microbatch 1: no dp*fsdp>1 layout survives
        # at that accumulation depth.
        prof = _tiny_profile(batch_size=4)
        cands = enumerate_layouts(prof, 4, grad_accums=(4,))
        for c in cands:
            assert c.mesh["dp"] * c.mesh["fsdp"] <= 1, c.key()

    def test_ep_rides_the_dp_axis_only_for_moe(self):
        dense = enumerate_layouts(_tiny_profile(), 4)
        assert all(c.mesh["ep"] == 1 for c in dense)
        moe = enumerate_layouts(_tiny_profile(num_experts=2), 4)
        eps = {c.mesh["ep"] for c in moe}
        assert 2 in eps
        for c in moe:
            if c.mesh["ep"] > 1:
                assert c.mesh["dp"] % c.mesh["ep"] == 0

    def test_remat_and_grad_accum_cross_the_space(self):
        cands = enumerate_layouts(_tiny_profile(), 2,
                                  grad_accums=(1, 2))
        keys = {c.key() for c in cands}
        assert "1x2x1x1x1x1/remat=0/ga=1" in keys
        assert "1x2x1x1x1x1/remat=1/ga=1" in keys
        # ga=2 halves the microbatch; dp=2 still fits (2 <= 4//2).
        assert "1x2x1x1x1x1/remat=0/ga=2" in keys


class TestLayoutScoringOracle:
    """score_layout's arithmetic checked term by term by hand."""

    def test_pure_dp_is_compute_only(self):
        prof = _tiny_profile()
        c = LayoutCandidate(mesh=_mesh(dp=2), remat=False, grad_accum=1)
        score_layout(prof, c, _SPEC, mfu=0.5, n_devices=2)
        # flops/step = 6*1000 * 4 * 8 = 192000;
        # compute = 192000 / (1e12 * 0.5 * 2) = 1.92e-7
        assert c.compute_s == pytest.approx(1.92e-7)
        assert c.comm_s == 0.0
        assert c.bubble_s == 0.0
        assert c.est_step_s == pytest.approx(1.92e-7)
        # HBM: params 2000 + grads 2000 + adam moments 2*4*1000 = 8000
        # + acts 14 * (4*8/2 tokens) * hidden 4 * 2B * 2 layers = 3584
        assert c.hbm_bytes == pytest.approx(15584.0)
        assert c.feasible

    def test_fsdp_pays_three_weight_moves_per_accum_step(self):
        prof = _tiny_profile()
        c = LayoutCandidate(mesh=_mesh(fsdp=2), remat=False,
                            grad_accum=1)
        score_layout(prof, c, _SPEC, mfu=0.5, n_devices=2)
        # all-gather fwd + all-gather bwd + reduce-scatter:
        # 3 * param_bytes(2000) / 1e9
        assert c.comm_s == pytest.approx(6e-6)
        c2 = LayoutCandidate(mesh=_mesh(fsdp=2), remat=False,
                             grad_accum=2)
        score_layout(prof, c2, _SPEC, mfu=0.5, n_devices=2)
        assert c2.comm_s == pytest.approx(12e-6)  # weights move per micro
        # zero-3 halves params/grads/moments; ga=1 acts: tokens 16
        assert c.hbm_bytes == pytest.approx(
            1000 + 1000 + 4000 + 3584.0
        )

    def test_tp_activation_term(self):
        prof = _tiny_profile()
        c = LayoutCandidate(mesh=_mesh(tp=2), remat=False, grad_accum=1)
        score_layout(prof, c, _SPEC, mfu=0.5, n_devices=2)
        # per layer: 4 * B*S (32) * hidden 4 * 2B = 1024 bytes;
        # 2 layers * 1024 * (tp-1)/tp / 1e9
        assert c.comm_s == pytest.approx(2 * 1024 * 0.5 / 1e9)

    def test_remat_trades_compute_for_activation_memory(self):
        prof = _tiny_profile()
        base = LayoutCandidate(mesh=_mesh(dp=2), remat=False,
                               grad_accum=1)
        remat = LayoutCandidate(mesh=_mesh(dp=2), remat=True,
                                grad_accum=1)
        score_layout(prof, base, _SPEC, mfu=0.5, n_devices=2)
        score_layout(prof, remat, _SPEC, mfu=0.5, n_devices=2)
        assert remat.compute_s == pytest.approx(base.compute_s * 4 / 3)
        # acts shrink 5x, weights/moments unchanged
        assert remat.hbm_bytes == pytest.approx(
            12000 + 3584.0 / 5.0
        )

    def test_gpipe_bubble_fraction(self):
        prof = _tiny_profile()
        c = LayoutCandidate(mesh=_mesh(pp=2), remat=False, grad_accum=2)
        score_layout(prof, c, _SPEC, mfu=0.5, n_devices=2)
        # (pp-1)/(m+pp-1) with m=2 microbatches: 1/3 of compute+comm
        assert c.bubble_s == pytest.approx((c.compute_s + c.comm_s) / 3)

    def test_infeasible_when_hbm_exceeds_headroom(self):
        prof = _tiny_profile()
        spec = dict(_SPEC, hbm_capacity_bytes=16000.0)
        c = LayoutCandidate(mesh=_mesh(dp=2), remat=False, grad_accum=1)
        score_layout(prof, c, spec, mfu=0.5, n_devices=2)
        # 15584 > 0.9 * 16000 = 14400
        assert not c.feasible


class TestPlanLayout:
    def test_picks_the_cheapest_feasible_candidate(self):
        prof = _tiny_profile()
        plan = plan_layout(prof, 2, backend="v5e", mfu=0.5, top_k=3)
        assert plan["n_candidates"] > 0
        assert plan["best"] is not None
        ests = [c["est_step_s"] for c in plan["top_k"]]
        assert plan["best"]["est_step_s"] == min(ests)
        assert plan["calibration_source"] == "caller"
        # pure-dp beats every comm-paying layout on this tiny model
        assert plan["best"]["mesh"]["dp"] == 2

    def test_is_deterministic(self):
        prof = _tiny_profile()
        a = plan_layout(prof, 4, backend="v5e", mfu=0.5)
        b = plan_layout(prof, 4, backend="v5e", mfu=0.5)
        assert a == b

    def test_calibration_loaded_when_mfu_omitted(self):
        plan = plan_layout(_tiny_profile(), 2, backend="v5e",
                           repo=REPO)
        assert 0.0 < plan["mfu"] <= 1.0
        # load_calibration names its evidence file (or "assumed").
        assert plan["calibration_source"] != "caller"

    def test_probe_confirms_top_k_and_refutes_the_leader(self):
        prof = _tiny_profile()
        seen = []

        def probe(c):
            seen.append(c.key())
            # Claim the analytic leader does NOT fit; everyone else does.
            fits = 1024.0 if seen[0] != c.key() else 1e18
            return {"hbm_bytes_per_chip": fits}

        plan = plan_layout(prof, 2, backend="v5e", mfu=0.5, top_k=3,
                           probe=probe)
        assert len(seen) == 3
        assert plan["best"]["key"] != seen[0]  # leader yielded
        assert plan["best"]["probe"]["fits_hbm"] is True
        refuted = [c for c in plan["top_k"] if c["key"] == seen[0]][0]
        assert refuted["probe"]["fits_hbm"] is False
        assert refuted["feasible"] is False

    def test_probe_errors_are_best_effort(self):
        def probe(c):
            raise RuntimeError("no compiler here")

        plan = plan_layout(_tiny_profile(), 2, backend="v5e", mfu=0.5,
                           probe=probe)
        assert plan["best"]["probe"]["error"]

    def test_warehouse_history_cross_check(self, tmp_path):
        from dlrover_tpu.brain.warehouse import config_fingerprint

        prof = _tiny_profile()
        wh = TelemetryWarehouse(os.path.join(str(tmp_path), "w.sqlite"))
        try:
            model_cfg = {"layers": 2, "hidden": 4}
            fp = config_fingerprint({
                "model": model_cfg,
                "mesh": {"n_devices": 2, "backend": "v5e"},
            })
            # Pin history to the mesh the planner will pick (dp=2):
            # one run with this fingerprint plus a goodput record so
            # best_known_config has a score to rank on.
            wh.register_run(
                "job-h", run="r1",
                config={"mesh": {"dp": 2, "fsdp": 1, "tp": 1}},
                fingerprint=fp,
            )
            wh.add_goodput_summary("job-h", {"goodput_pct": 95.0},
                                   run="r1")
            plan = plan_layout(prof, 2, backend="v5e", mfu=0.5,
                               warehouse=wh, model_config=model_cfg)
        finally:
            wh.close()
        assert plan["history"] is not None
        assert plan["history"]["agrees"] is True


# -- traffic forecast --------------------------------------------------------


def _diurnal_trace(days=2, low=100.0, high=500.0):
    """Hourly windows: ``low`` tokens/s before noon, ``high`` after."""
    out = []
    for d in range(days):
        for h in range(24):
            out.append({
                "t": d * DAY + h * HOUR + 1800.0,
                "tokens_per_sec": low if h < 12 else high,
            })
    return out


class TestTrafficForecast:
    def test_recovers_the_diurnal_shape(self):
        fc = fit_traffic(_diurnal_trace(), period_s=DAY, n_bins=24)
        assert fc.fitted
        assert fc.n_windows == 48
        assert fc.bins[3] == pytest.approx(100.0)
        assert fc.bins[13] == pytest.approx(500.0)
        assert fc.mean_rate == pytest.approx(300.0)
        # Day-3 15:00 folds back into the fitted period.
        assert fc.rate_at(2 * DAY + 15 * HOUR) == pytest.approx(500.0)

    def test_predict_reads_ahead_by_the_lead(self):
        fc = fit_traffic(_diurnal_trace(), period_s=DAY, n_bins=24)
        now = 11 * HOUR + 1800.0  # mid-morning, still in the low phase
        assert fc.rate_at(now) == pytest.approx(100.0)
        # Two hours ahead lands in the afternoon surge.
        assert fc.predict(now, lead_s=2 * HOUR) == pytest.approx(500.0)

    def test_horizon_averages_across_bins(self):
        fc = fit_traffic(_diurnal_trace(), period_s=DAY, n_bins=24)
        # A full-period horizon averages to the global mean.
        assert fc.predict(0.0, lead_s=0.0, horizon_s=DAY) == (
            pytest.approx(300.0)
        )

    def test_empty_bins_fall_back_to_the_mean(self):
        trace = [{"t": 1800.0, "tokens_per_sec": 120.0}]
        fc = fit_traffic(trace, period_s=DAY, n_bins=24)
        assert fc.bins[0] == pytest.approx(120.0)
        assert fc.bins[5] is None
        assert fc.rate_at(5 * HOUR) == pytest.approx(120.0)

    def test_rates_derived_from_tokens_and_window(self):
        trace = [{"t": 5.0, "tokens": 500.0, "window_s": 10.0}]
        fc = fit_traffic(trace, period_s=60.0, n_bins=6)
        assert fc.mean_rate == pytest.approx(50.0)

    def test_fit_is_deterministic(self):
        trace = _diurnal_trace()
        assert fit_traffic(trace).as_dict() == fit_traffic(
            trace).as_dict()

    def test_unfitted_forecast_predicts_zero(self):
        fc = TrafficForecast()
        assert not fc.fitted
        assert fc.predict(123.0, lead_s=30.0) == 0.0

    def test_fit_from_warehouse_records(self, tmp_path):
        wh = TelemetryWarehouse(os.path.join(str(tmp_path), "w.sqlite"))
        try:
            for rec in _diurnal_trace(days=1):
                wh.add_traffic_summary("job-f", {
                    "ts": rec["t"],
                    "tokens_per_sec": rec["tokens_per_sec"],
                    "window_s": HOUR,
                    "source": "gateway",
                })
            fc = forecast_from_warehouse(wh, job_uid="job-f",
                                         period_s=DAY, n_bins=24)
        finally:
            wh.close()
        assert fc.n_windows == 24
        assert fc.bins[13] == pytest.approx(500.0)


# -- predictive vs reactive replay drill -------------------------------------


def _ramp_trace():
    """10s windows: 10 tokens/s for 5 minutes, then a 20x ramp."""
    return [
        {"t": i * 10.0, "tokens_per_sec": 10.0 if i < 30 else 200.0}
        for i in range(60)
    ]


def _drill_autoscaler():
    return FleetAutoscaler(
        min_replicas=1, max_replicas=3, tokens_per_replica=100.0,
        up_dwell_s=0.0, down_dwell_s=1e9, cooldown_s=0.0,
    )


class TestReplayDrill:
    def test_predictive_loses_strictly_fewer_servput_points(self):
        drill = predictive_vs_reactive(
            _ramp_trace(), _drill_autoscaler,
            period_s=600.0, n_bins=60, lead_s=30.0,
            capacity_tokens_per_s=100.0, standbys=1, warm_s=40.0,
        )
        # The acceptance property: pre-warm beats react, priced in the
        # servput accountant's own currency.
        assert drill["predictive"]["lost_points"] < (
            drill["reactive"]["lost_points"]
        )
        assert drill["points_saved"] > 0

    def test_prewarms_before_the_recorded_ramp(self):
        drill = predictive_vs_reactive(
            _ramp_trace(), _drill_autoscaler,
            period_s=600.0, n_bins=60, lead_s=30.0,
            capacity_tokens_per_s=100.0, standbys=1, warm_s=40.0,
        )
        assert drill["ramp_start_t"] == 300.0
        assert drill["prewarmed_before_ramp"] is True
        assert drill["predictive"]["first_grow_t"] < 300.0
        # Reactive can only move once the backlog exists.
        assert drill["reactive"]["first_grow_t"] >= 300.0

    def test_reactive_run_without_forecast_is_labeled_reactive(self):
        res = replay_fleet(_ramp_trace(), _drill_autoscaler(),
                           capacity_tokens_per_s=100.0, standbys=1,
                           warm_s=40.0)
        assert res.mode == "reactive"
        assert all(d.get("mode") == "reactive" for d in res.decisions)

    def test_predictive_decisions_carry_the_forecast_term(self):
        fc = fit_traffic(_ramp_trace(), period_s=600.0, n_bins=60)
        res = replay_fleet(_ramp_trace(), _drill_autoscaler(),
                           forecast=fc, lead_s=30.0,
                           capacity_tokens_per_s=100.0, standbys=1,
                           warm_s=40.0)
        assert res.mode == "predictive"
        grows = [d for d in res.decisions if d["action"] == "grow"]
        assert grows
        assert grows[0]["mode"] == "predictive"
        assert grows[0]["forecast_tokens"] > 0

    def test_drill_is_deterministic(self):
        kw = dict(period_s=600.0, n_bins=60, lead_s=30.0,
                  capacity_tokens_per_s=100.0, standbys=1, warm_s=40.0)
        a = predictive_vs_reactive(_ramp_trace(), _drill_autoscaler,
                                   **kw)
        b = predictive_vs_reactive(_ramp_trace(), _drill_autoscaler,
                                   **kw)
        assert a == b


class TestAutoscalerForecastTerm:
    """PR-15 hysteresis contract extended, never replaced."""

    def test_decide_without_forecast_is_unchanged_reactive(self):
        a = _drill_autoscaler()
        got = a.decide(0.0, queue_tokens=500.0, target_live=1)
        assert got == 3  # ceil(500/100) capped at max
        assert a.decisions[-1]["mode"] == "reactive"
        assert a.decisions[-1]["forecast_tokens"] is None

    def test_forecast_term_labels_the_decision_predictive(self):
        a = _drill_autoscaler()
        got = a.decide(0.0, queue_tokens=0.0, target_live=1,
                       forecast_tokens=250.0)
        assert got == 3
        assert a.decisions[-1]["mode"] == "predictive"
        assert a.decisions[-1]["forecast_tokens"] == 250.0

    def test_forecast_below_queue_stays_reactive(self):
        # max(queue, forecast): a forecast the backlog already dwarfs
        # changes nothing, so the label stays reactive.
        a = _drill_autoscaler()
        a.decide(0.0, queue_tokens=500.0, target_live=1,
                 forecast_tokens=10.0)
        assert a.decisions[-1]["mode"] == "reactive"

    def test_snapshot_exposes_the_input_side_state(self):
        a = FleetAutoscaler(min_replicas=1, max_replicas=4,
                            tokens_per_replica=128.0, up_dwell_s=5.0,
                            down_dwell_s=60.0, cooldown_s=30.0)
        snap = a.snapshot()
        assert snap["max_replicas"] == 4
        assert snap["tokens_per_replica"] == 128.0
        assert snap["up_dwell_s"] == 5.0
        assert snap["cooldown_s"] == 30.0
        # After a decision the cooldown timer shows up.
        for t in (0.0, 6.0):
            a.decide(t, queue_tokens=1000.0, target_live=1)
        snap = a.snapshot(now=6.0)
        assert snap["cooldown_until"] is not None
        assert snap["cooldown_remaining_s"] == pytest.approx(30.0)


# -- warehouse traffic kind --------------------------------------------------


class TestWarehouseTraffic:
    def _wh(self, tmp_path):
        return TelemetryWarehouse(
            os.path.join(str(tmp_path), "wh.sqlite")
        )

    def test_round_trip_and_trend(self, tmp_path):
        wh = self._wh(tmp_path)
        try:
            wh.add_traffic_summary("job-t", {
                "ts": 10.0, "source": "gateway", "requests": 5,
                "tokens": 1500, "window_s": 10.0,
                "tokens_per_sec": 150.0,
            }, run="r1")
            # tokens_per_sec derived when missing
            wh.add_traffic_summary("job-t", {
                "ts": 20.0, "source": "gateway", "requests": 2,
                "tokens": 400, "window_s": 10.0,
            }, run="r1")
            rows = wh.traffic_trend("job-t")
        finally:
            wh.close()
        assert [r["tokens_per_sec"] for r in rows] == [150.0, 40.0]
        assert rows[0]["requests"] == 5
        assert rows[0]["source"] == "gateway"
        assert rows[1]["window_s"] == 10.0

    def test_clean_caps_traffic_history_per_job(self, tmp_path):
        wh = self._wh(tmp_path)
        try:
            # Timestamps far in the future so the age purge (now-90d)
            # can't touch them — this test isolates the per-job cap.
            base = 4e9
            for i in range(6):
                wh.add_traffic_summary("job-c", {
                    "ts": base + i, "tokens_per_sec": float(i),
                    "window_s": 1.0,
                })
            wh.clean(max_traffic_records_per_job=3)
            rows = wh.traffic_trend("job-c")
        finally:
            wh.close()
        # Newest 3 windows survive the retention pass.
        assert [r["tokens_per_sec"] for r in rows] == [3.0, 4.0, 5.0]

    def test_fleet_report_carries_the_traffic_trend(self, tmp_path):
        from dlrover_tpu.brain.report import build_report, render_markdown

        wh = self._wh(tmp_path)
        try:
            wh.add_traffic_summary("job-r", {
                "ts": 10.0, "source": "gateway", "requests": 7,
                "tokens": 700, "window_s": 10.0,
                "tokens_per_sec": 70.0,
            })
            report = build_report(wh)
            md = render_markdown(report)
        finally:
            wh.close()
        assert report["traffic_trend"]
        assert "## Traffic shape (gateway arrivals)" in md
        assert "70.0" in md


# -- capacity planner + CLI --------------------------------------------------


def _seed_plan_db(path, with_serve=True):
    wh = TelemetryWarehouse(path)
    try:
        for rec in _ramp_trace():
            wh.add_traffic_summary("job-p", {
                "ts": rec["t"], "source": "gateway",
                "tokens_per_sec": rec["tokens_per_sec"],
                "window_s": 10.0,
                "tokens": rec["tokens_per_sec"] * 10.0,
                "requests": 3,
            })
        if with_serve:
            wh.add_serve_summary("job-p", {
                "ts": 600.0, "source": "serve",
                "gateway_tokens_per_sec": 120.0, "measured": True,
            })
    finally:
        wh.close()


class TestCapacityPlanner:
    def test_measured_serve_record_pins_replica_capacity(self, tmp_path):
        db = os.path.join(str(tmp_path), "wh.sqlite")
        _seed_plan_db(db)
        wh = TelemetryWarehouse(db)
        try:
            cap = replica_capacity(wh)
        finally:
            wh.close()
        assert cap["source"] == "serve_record"
        assert cap["tokens_per_sec"] == 120.0

    def test_roofline_fallback_without_serve_records(self):
        cap = replica_capacity(None, chip_gen="v5e", repo=REPO)
        assert cap["source"] == "roofline"
        assert cap["tokens_per_sec"] > 0

    def test_plan_prices_the_proposal(self, tmp_path):
        db = os.path.join(str(tmp_path), "wh.sqlite")
        _seed_plan_db(db)
        wh = TelemetryWarehouse(db)
        try:
            plan = plan_capacity(wh, replicas=2, standbys=1)
        finally:
            wh.close()
        assert plan["proposed"] == {
            "max_replicas": 2, "standby_target": 1, "chip_gen": "v5e",
        }
        assert plan["capacity"]["per_replica_tokens_per_sec"] == 120.0
        assert plan["traffic"]["windows"] == 60
        assert plan["traffic"]["peak_tokens_per_sec"] == 200.0
        # peak 200 > fleet 240? no: 240 > 200, so the proposal fits.
        assert plan["verdict"] == "fits"
        assert plan["drill"]["predictive"]["lost_points"] <= (
            plan["drill"]["reactive"]["lost_points"]
        )
        assert plan["config_draft"]["lines"]

    def test_under_provisioned_verdict(self, tmp_path):
        db = os.path.join(str(tmp_path), "wh.sqlite")
        _seed_plan_db(db)
        wh = TelemetryWarehouse(db)
        try:
            plan = plan_capacity(wh, replicas=1, standbys=0)
        finally:
            wh.close()
        assert plan["verdict"] == "under_provisioned"

    def test_no_traffic_verdict(self, tmp_path):
        wh = TelemetryWarehouse(os.path.join(str(tmp_path), "w.sqlite"))
        try:
            plan = plan_capacity(wh, replicas=2, standbys=1,
                                 repo=REPO)
        finally:
            wh.close()
        assert plan["verdict"] == "no_traffic"
        assert plan["drill"] is None

    def test_markdown_renders_every_section(self, tmp_path):
        db = os.path.join(str(tmp_path), "wh.sqlite")
        _seed_plan_db(db)
        wh = TelemetryWarehouse(db)
        try:
            md = render_plan_markdown(
                plan_capacity(wh, replicas=2, standbys=1)
            )
        finally:
            wh.close()
        for needle in (
            "# Capacity plan", "## Capacity", "## Recorded traffic",
            "## Replay pricing (servput points)",
            "## Drafted config change", "```diff",
        ):
            assert needle in md


class TestDraftConfigDiff:
    def test_only_changed_knobs_produce_lines(self):
        d = draft_config_diff(
            {"max_replicas": 1, "standby_target": 0},
            {"max_replicas": 1, "standby_target": 1},
            reason="cold spawn cost points",
        )
        assert d["lines"] == [
            "- standby_target = 0", "+ standby_target = 1",
        ]
        assert d["reason"] == "cold spawn cost points"

    def test_one_sided_knobs_show_as_pure_additions(self):
        d = draft_config_diff({}, {"chip_gen": "v5e"})
        assert d["lines"] == ["+ chip_gen = 'v5e'"]

    def test_no_change_no_lines(self):
        d = draft_config_diff({"a": 1}, {"a": 1})
        assert d["lines"] == []


class TestBrainPlanCli:
    def test_round_trip_markdown_and_json(self, tmp_path, capsys):
        from dlrover_tpu.brain.__main__ import main

        db = os.path.join(str(tmp_path), "wh.sqlite")
        _seed_plan_db(db)
        assert main(["plan", "--db", db, "--replicas", "2",
                     "--standbys", "1"]) == 0
        md = capsys.readouterr().out
        assert "# Capacity plan" in md
        assert "Proposed fleet: **2 replicas / 1 standbys**" in md

        assert main(["plan", "--db", db, "--replicas", "2",
                     "--standbys", "1", "--json", "-"]) == 0
        plan = json.loads(capsys.readouterr().out)
        assert plan["proposed"]["max_replicas"] == 2
        assert plan["drill"]["predictive"]["lost_points"] <= (
            plan["drill"]["reactive"]["lost_points"]
        )

    def test_json_and_md_files_written(self, tmp_path, capsys):
        from dlrover_tpu.brain.__main__ import main

        db = os.path.join(str(tmp_path), "wh.sqlite")
        _seed_plan_db(db)
        js = os.path.join(str(tmp_path), "plan.json")
        mdp = os.path.join(str(tmp_path), "plan.md")
        assert main(["plan", "--db", db, "--replicas", "3",
                     "--standbys", "2", "--json", js, "--md", mdp]) == 0
        capsys.readouterr()
        with open(js, encoding="utf-8") as f:
            plan = json.load(f)
        assert plan["proposed"]["standby_target"] == 2
        with open(mdp, encoding="utf-8") as f:
            assert "# Capacity plan" in f.read()

    def test_missing_db_exits_2(self, tmp_path, capsys):
        from dlrover_tpu.brain.__main__ import main

        missing = os.path.join(str(tmp_path), "nope.sqlite")
        assert main(["plan", "--db", missing, "--replicas", "1",
                     "--standbys", "0"]) == 2
        assert "not found" in capsys.readouterr().err

    def test_module_entry_point(self, tmp_path):
        db = os.path.join(str(tmp_path), "wh.sqlite")
        _seed_plan_db(db)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.brain", "plan",
             "--db", db, "--replicas", "2", "--standbys", "1",
             "--json", "-"],
            capture_output=True, text=True, timeout=120, cwd=REPO,
            env=env,
        )
        assert out.returncode == 0, out.stderr
        plan = json.loads(out.stdout)
        assert plan["verdict"] == "fits"


# -- doctor: drafted config change in the incident report --------------------


def _serve_ev(ev, t, **kw):
    return {"ev": ev, "t": t, "mono": t, "pid": 1, "rank": 0,
            "role": "serve", "attempt": 0, **kw}


def _cold_spawn_stream():
    """200s serving window with one 10s cold-spawn reform at t=100."""
    return [
        _serve_ev("serve_state", 0.0, state="serving"),
        _serve_ev(
            "verdict", 50.0, action="serve_scale",
            reason="demand needs 2 (mode=reactive)",
            snapshot={"autoscaler": {"max_replicas": 2}},
        ),
        _serve_ev("serve_state", 100.0, state="reform"),
        _serve_ev("serve_state", 110.0, state="serving"),
        _serve_ev("serve_state", 200.0, state="serving"),
    ]


class TestDoctorConfigDraft:
    def test_cold_spawn_drafts_one_more_standby(self):
        from dlrover_tpu import doctor

        report = doctor.diagnose(
            doctor.SourceData(events=_cold_spawn_stream())
        )
        draft = report["config_draft"]
        assert draft is not None
        # Current knobs anchored to the serve_scale verdict's snapshot.
        assert draft["current"]["max_replicas"] == 2
        assert draft["proposed"]["standby_target"] == 1
        assert "+ standby_target = 1" in draft["lines"]
        assert "cold-spawn" in draft["reason"]

    def test_markdown_renders_the_diff_section(self):
        from dlrover_tpu import doctor

        report = doctor.diagnose(
            doctor.SourceData(events=_cold_spawn_stream())
        )
        md = doctor.render_markdown(report)
        assert "## Drafted config change" in md
        assert "```diff" in md
        assert "+ standby_target = 1" in md

    def test_promotion_recovery_drafts_nothing(self):
        from dlrover_tpu import doctor

        events = _cold_spawn_stream()
        events.insert(3, _serve_ev(
            "verdict", 101.0, action="serve_promote",
            reason="standby promoted",
        ))
        report = doctor.diagnose(doctor.SourceData(events=events))
        # The standby already absorbed the death; no knob change and
        # therefore no draft at all.
        assert report["config_draft"] is None

    def test_stream_without_serving_has_no_draft(self):
        from dlrover_tpu import doctor

        events = [
            {"ev": "step", "t": 10.0, "mono": 10.0, "pid": 1,
             "rank": 0, "role": "worker", "attempt": 0, "step": 0},
            {"ev": "step", "t": 20.0, "mono": 20.0, "pid": 1,
             "rank": 0, "role": "worker", "attempt": 0, "step": 1},
        ]
        report = doctor.diagnose(doctor.SourceData(events=events))
        assert report["config_draft"] is None


# -- planner wiring (auto/planner.py) ----------------------------------------


class TestPlannerWiring:
    def test_strategy_from_layout_names_the_opts(self):
        from dlrover_tpu.auto.planner import strategy_from_layout

        best = LayoutCandidate(
            mesh={"pp": 2, "dp": 1, "fsdp": 2, "ep": 1, "sp": 2,
                  "tp": 2},
            remat=True, grad_accum=4,
        )
        s = strategy_from_layout(best.as_dict())
        names = s.opt_names()
        assert s.source == "brain"
        assert "fsdp" in names
        assert "tensor_parallel" in names
        assert "sequence_parallel" in names
        assert "pipeline_parallel" in names
        assert "checkpoint" in names
        assert "grad_accumulation" in names

    def test_trivial_layout_maps_to_parallel_mode(self):
        from dlrover_tpu.auto.planner import strategy_from_layout

        best = LayoutCandidate(mesh=_mesh(dp=8), remat=False,
                               grad_accum=1)
        s = strategy_from_layout(best.as_dict())
        names = s.opt_names()
        assert "parallel_mode" in names
        assert "tensor_parallel" not in names
        assert "checkpoint" not in names

    def test_brain_strategy_on_the_cpu_mesh(self, devices8):
        import jax.numpy as jnp

        from dlrover_tpu.auto.planner import brain_strategy
        from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

        class _Ctx:
            model = LlamaModel(LlamaConfig.tiny())
            sample_batch = {"input_ids": jnp.zeros((8, 128), jnp.int32)}
            devices = devices8

        strategy, plan = brain_strategy(_Ctx())
        assert strategy.source == "brain"
        assert plan["best"] is not None
        assert plan["n_candidates"] > 0


# -- acceptance --------------------------------------------------------------


def _llama_class_profile():
    """A 1.1B llama-shaped profile on paper numbers (no jax needed)."""
    from dlrover_tpu.auto.analyser import ModelProfile

    n = 1_100_000_000
    return ModelProfile(
        num_params=n, param_bytes=2 * n, flops_per_token=6.0 * n,
        batch_size=16, seq_len=2048, num_layers=22, hidden_size=2048,
        num_heads=32, num_kv_heads=4,
    )


def _v5e_device(n=16):
    from dlrover_tpu.auto.analyser import DeviceContext

    return DeviceContext(platform="tpu", n_devices=n,
                         hbm_bytes=16 << 30, bf16_flops=197e12,
                         ici_bandwidth=50e9)


class TestAcceptanceLayoutPlanner:
    """The analytic planner scores within 5% of (or beats) the best
    measured-search candidate under the same calibrated cost model, on
    a fixture llama-class model and a v5e-16 mesh."""

    def test_within_5pct_of_the_measured_search_pool(self):
        from dlrover_tpu.auto.engine.search import generate_candidates

        profile = _llama_class_profile()
        device = _v5e_device(16)
        spec = costmodel.chip_spec("v5e")
        mfu = 0.4

        lp = LayoutProfile.from_model_profile(profile)
        search_scores = []
        for cand in generate_candidates(profile, device):
            remat = "checkpoint" in cand.strategy.opt_names()
            lc = LayoutCandidate(mesh=dict(cand.mesh_sizes),
                                 remat=remat, grad_accum=1)
            score_layout(lp, lc, spec, mfu, device.n_devices)
            if lc.feasible:
                search_scores.append(lc.est_step_s)
        assert search_scores, "search pool has no feasible layout"
        best_search = min(search_scores)

        plan = plan_layout(lp, device.n_devices, backend="v5e",
                           mfu=mfu)
        assert plan["best"] is not None
        assert plan["best"]["feasible"]
        assert plan["best"]["est_step_s"] <= 1.05 * best_search
        # The brain space (pp/ep/ga/remat crossed freely) is a strict
        # superset of the search's, so it should in fact never lose.
        assert plan["best"]["est_step_s"] <= best_search * (1 + 1e-9)

    def test_best_layout_fits_v5e_hbm(self):
        lp = LayoutProfile.from_model_profile(_llama_class_profile())
        plan = plan_layout(lp, 16, backend="v5e", mfu=0.4)
        cap = costmodel.chip_spec("v5e")["hbm_capacity_bytes"]
        assert plan["best"]["hbm_bytes"] < 0.9 * cap


class TestAcceptanceAotProbe:
    """The AOT compile probe confirms the plan's HBM fit with the real
    XLA compiler (skips where the TPU compile-only client is absent)."""

    def test_probe_confirms_hbm_fit_for_v5e(self):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"TPU compile-only client unavailable: {e}")

        from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

        cfg = LlamaConfig.tiny()
        model = LlamaModel(cfg)
        seq = cfg.max_seq_len
        mesh = Mesh(np.array(topo.devices).reshape(4), ("fsdp",))
        ids = jax.ShapeDtypeStruct(
            (8, seq), jnp.int32,
            sharding=NamedSharding(mesh, P("fsdp")),
        )
        abs_params = jax.eval_shape(
            model.init, jax.random.key(0),
            jnp.zeros((1, seq), jnp.int32),
        )

        def loss(params, x):
            return model.apply(params, x).astype(jnp.float32).mean()

        lowered = jax.jit(jax.grad(loss)).lower(abs_params, ids)

        lp = LayoutProfile(
            num_params=int(sum(
                np.prod(l.shape) for l in jax.tree.leaves(abs_params)
            )),
            batch_size=8, seq_len=seq,
            num_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        )

        def probe(cand):
            return costmodel.compile_and_analyze(
                lowered, name=cand.key(), topology="v5e:2x2",
                n_params=lp.num_params,
            )

        plan = plan_layout(lp, 4, backend="v5e", mfu=0.4, top_k=1,
                           probe=probe)
        best = plan["best"]
        assert best["probe"]["ok"]
        assert best["probe"]["fits_hbm"] is True
