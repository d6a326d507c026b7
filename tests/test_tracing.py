"""Request-scoped tracing + SLO burn-rate engine tests (docs/TRACING.md).

Covers the PR 14 acceptance bars: trace-context wire roundtrip with
malformed-wire tolerance, head sampling (env-tuned, near-zero cost when
unsampled), span emission into both the in-process ring buffer and the
crash-safe per-rank event stream, cross-process timeline reconstruction
in causal order (including a real-process SIGKILL drill, marked slow),
histogram exemplars linking p99 to sampled trace ids, the multi-window
multi-burn-rate SLO engine — durable ``slo_burn`` verdicts with
exemplar trace ids, doctor attribution, warehouse error-budget
persistence — and the transport satellites (``dlrover_rpc_inflight``
gauge, one-shot slow-RPC warning).
"""

import itertools
import os
import signal
import time
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dlrover_tpu import doctor
from dlrover_tpu.brain.warehouse import TelemetryWarehouse
from dlrover_tpu.rpc import transport
from dlrover_tpu.serving.engine import PagedServingEngine
from dlrover_tpu.serving.gateway import (
    InferenceGateway,
    LocalReplica,
    ProcessReplica,
)
from dlrover_tpu.serving.worker import build_tiny_model
from dlrover_tpu.telemetry import events as tevents
from dlrover_tpu.telemetry import metrics as tmetrics
from dlrover_tpu.telemetry import slo as tslo
from dlrover_tpu.telemetry import tracing

pytestmark = pytest.mark.tracing

# Registry metrics are process-global; every test that needs a fresh
# series mints a unique name so nothing leaks between tests (or from
# the serving tests that ran earlier in the same process).
_uniq = itertools.count()


def _metric_name(stem: str) -> str:
    return f"dlrover_test_{stem}_{next(_uniq)}_seconds"


def _causal(spans):
    """Parents must appear before their children (reconstruct order)."""
    seen = set()
    ids = {s["span"] for s in spans}
    for s in spans:
        parent = s.get("parent", "")
        if parent and parent in ids and parent not in seen:
            return False
        seen.add(s["span"])
    return True


@pytest.fixture(scope="module")
def tiny_model():
    return build_tiny_model(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=2, num_kv_heads=2, max_seq_len=64,
        seed=0,
    )


def _local_factory(model, params, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 16)
    kw.setdefault("temperature", 1e-6)
    kw.setdefault("seed", 0)

    def factory():
        return LocalReplica(
            PagedServingEngine(model, params, **kw), ticks_per_poll=4
        )

    return factory


@pytest.fixture()
def sampled(monkeypatch):
    """Every request sampled + a clean ring buffer."""
    monkeypatch.setenv(tracing.ENV_SAMPLE_RATE, "1.0")
    tracing.clear_recent()
    yield
    tracing.clear_recent()


@pytest.fixture()
def events_dir(tmp_path, monkeypatch):
    """Point the process-global event log (and anything that spawns off
    it) at a per-test directory; restore the env-driven default after."""
    d = str(tmp_path / "events")
    monkeypatch.setenv(tevents.ENV_TELEMETRY_DIR, d)
    tevents.configure(directory=d, role="gateway", rank=0)
    yield d
    tevents.reset()


# -- trace context -----------------------------------------------------------


class TestTraceContext:
    def test_wire_roundtrip(self):
        ctx = tracing.start_trace(sampled=True)
        wire = tracing.to_wire(ctx)
        back = tracing.from_wire(wire)
        assert back is not None
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id
        assert tracing.to_wire(None) == ""

    def test_malformed_wire_means_unsampled(self):
        # Wire drift must never break an RPC — every bad shape decodes
        # to None (unsampled), never raises.
        for bad in (None, "", "abc", "a:b:c", ":x", "x:", 42, b"a:b"):
            assert tracing.from_wire(bad) is None

    def test_child_links_to_parent(self):
        ctx = tracing.start_trace(sampled=True)
        child = ctx.child()
        assert child.trace_id == ctx.trace_id
        assert child.parent_id == ctx.span_id
        assert child.span_id != ctx.span_id

    def test_head_sampling_env(self, monkeypatch):
        monkeypatch.setenv(tracing.ENV_SAMPLE_RATE, "0.0")
        assert all(tracing.start_trace() is None for _ in range(20))
        monkeypatch.setenv(tracing.ENV_SAMPLE_RATE, "1.0")
        assert tracing.start_trace() is not None
        # The forced override ignores the env entirely.
        monkeypatch.setenv(tracing.ENV_SAMPLE_RATE, "0.0")
        assert tracing.start_trace(sampled=True) is not None

    def test_sample_rate_clamped_and_tolerant(self, monkeypatch):
        monkeypatch.setenv(tracing.ENV_SAMPLE_RATE, "7.5")
        assert tracing.sample_rate() == 1.0
        monkeypatch.setenv(tracing.ENV_SAMPLE_RATE, "-3")
        assert tracing.sample_rate() == 0.0
        monkeypatch.setenv(tracing.ENV_SAMPLE_RATE, "junk")
        assert tracing.sample_rate() == tracing.DEFAULT_SAMPLE_RATE


# -- span emission -----------------------------------------------------------


class TestSpans:
    def test_emit_span_lands_in_ring_and_stream(self, sampled, events_dir):
        ctx = tracing.start_trace(sampled=True)
        rec = tracing.emit_span(ctx, "unit", 0.25, rid=7)
        assert rec is not None and rec["ev"] == "span"
        ring = tracing.recent_spans(ctx.trace_id)
        assert len(ring) == 1 and ring[0]["name"] == "unit"
        # And the same record is durable in the per-rank JSONL stream
        # (the crash-safe half of reconstruction).
        on_disk = [
            r for r in tevents.read_dir(events_dir)
            if r.get("ev") == "span" and r.get("trace") == ctx.trace_id
        ]
        assert len(on_disk) == 1
        assert on_disk[0]["span"] == ctx.span_id
        assert on_disk[0]["rid"] == 7

    def test_unsampled_hooks_are_noops(self, sampled):
        tracing.clear_recent()
        assert tracing.emit_span(None, "x", 0.1) is None
        assert tracing.point(None, "x") is None
        with tracing.span(None, "x") as child:
            assert child is None
        assert tracing.recent_spans() == []

    def test_span_context_manager_times_and_links(self, sampled):
        ctx = tracing.start_trace(sampled=True)
        with tracing.span(ctx, "work", rid=1) as child:
            assert child.parent_id == ctx.span_id
            time.sleep(0.01)
        rec = tracing.recent_spans(ctx.trace_id)[-1]
        assert rec["name"] == "work"
        assert rec["dur"] >= 0.01
        assert rec["rid"] == 1


# -- reconstruction ----------------------------------------------------------


class TestReconstruct:
    def test_causal_order_from_ring(self, sampled):
        # Spans are emitted at END time, so the leaf lands first in the
        # stream; reconstruct must still put parents before children.
        root = tracing.start_trace(sampled=True)
        with tracing.span(root, "parent") as p:
            with tracing.span(p, "child") as c:
                tracing.point(c, "leaf")
        recon = tracing.reconstruct(root.trace_id)
        assert recon["found"] and recon["span_count"] == 3
        assert [s["name"] for s in recon["spans"]] == [
            "parent", "child", "leaf",
        ]
        assert _causal(recon["spans"])

    def test_merges_ring_and_event_streams(self, sampled, events_dir):
        root = tracing.start_trace(sampled=True)
        gw_span = root.child()
        tracing.emit_span(gw_span, "gateway_side", 0.01)
        # A remote rank's stream (kv shard): same trace, different file.
        kv_log = tevents.EventLog(
            directory=events_dir, role="kv", rank=3
        )
        tracing.emit_span(
            gw_span.child(), "kv_side", 0.005, log=kv_log
        )
        kv_log.close()
        # Drop the ring: everything must come back from the JSONL files.
        tracing.clear_recent()
        recon = tracing.reconstruct(root.trace_id, events_dir=events_dir)
        assert recon["found"] and recon["span_count"] == 2
        names = [s["name"] for s in recon["spans"]]
        assert names == ["gateway_side", "kv_side"]
        assert _causal(recon["spans"])
        roles = {s["role"] for s in recon["spans"]}
        assert roles == {"gateway", "kv"}

    def test_unknown_trace_not_found(self, sampled):
        recon = tracing.reconstruct("deadbeefdeadbeef")
        assert not recon["found"] and recon["span_count"] == 0


# -- quantiles + exemplars ---------------------------------------------------


class TestQuantilesAndExemplars:
    def test_quantile_from_cumulative_interpolates(self):
        uppers = (1.0, 2.0, 4.0, float("inf"))
        cumulative = (10, 20, 30, 40)
        q = tmetrics.quantile_from_cumulative
        assert q(uppers, cumulative, 40, 0.5) == pytest.approx(2.0)
        assert q(uppers, cumulative, 40, 0.25) == pytest.approx(1.0)
        # Within-bucket interpolation: rank 12 sits 20% into (1, 2].
        assert q(uppers, cumulative, 40, 0.3) == pytest.approx(1.2)
        assert q(uppers, cumulative, 0, 0.5) == 0.0
        assert q((), (), 0, 0.5) == 0.0

    def test_histogram_summary_and_exemplars(self):
        h = tmetrics.histogram(_metric_name("exemplar"), "test")
        h.observe(0.2, exemplar="aaaa")
        h.observe(3.0, exemplar="bbbb")
        h.observe(0.01)
        s = h.summary()
        assert s["count"] == 3
        assert set(s) >= {"p50", "p95", "p99", "count", "sum"}
        rows = h.all_exemplars()
        by_tid = {r["trace_id"]: r for r in rows}
        assert {"aaaa", "bbbb"} <= set(by_tid)
        assert by_tid["bbbb"]["value"] == pytest.approx(3.0)


# -- SLO engine --------------------------------------------------------------


def _latency_spec(name="unit_ttft", metric=None, **kw):
    kw.setdefault("target", 0.9)
    kw.setdefault("threshold_s", 0.5)
    kw.setdefault("quantile", 0.9)
    return tslo.SloSpec(
        name=name, metric=metric or _metric_name("slo"), **kw
    )


class TestSloEngine:
    def test_spec_validation(self):
        with pytest.raises(ValueError):
            tslo.SloSpec(name="x", metric="m", kind="junk")
        with pytest.raises(ValueError):
            tslo.SloSpec(name="x", metric="m", target=1.0)
        with pytest.raises(ValueError):
            tslo.SloSpec(name="x", metric="m", kind="availability")

    def test_default_specs_cover_serving_and_kv(self):
        engine = tslo.SloEngine()
        names = set(engine.snapshot()["slos"])
        assert names == {
            "serve_ttft_p99", "serve_tpot_p99",
            "serve_availability", "kv_lookup_p99", "kv_freshness",
        }

    def test_latency_burn_fires_verdict_with_exemplars(self, events_dir):
        spec = _latency_spec()
        engine = tslo.SloEngine(
            specs=(spec,), windows=((10.0, 2.0, 2.0),), interval_s=0.0
        )
        h = tmetrics.histogram(spec.metric, "test")
        assert engine.tick(1000.0) == []  # single sample: no frame yet
        # Distinct buckets (1.0 / 5.0 / 2.5) — exemplars are last-per-
        # bucket, so same-bucket values would overwrite each other.
        for tid, v in (("t-a", 0.7), ("t-b", 3.0), ("t-c", 2.0)):
            h.observe(v, exemplar=tid)
        fired = engine.tick(1001.0)
        assert len(fired) == 1
        alert = fired[0]
        assert alert["slo"] == spec.name
        # Every observation breached: bad fraction 1.0, burning 10x the
        # (1 - 0.9) budget — over both the long and the short window.
        assert alert["bad_fraction"] == pytest.approx(1.0)
        assert alert["long_burn_rate"] == pytest.approx(10.0)
        assert alert["short_burn_rate"] >= alert["burn_factor"]
        # Exemplars: slowest sampled requests first.
        assert [e["trace_id"] for e in alert["exemplars"]] == [
            "t-b", "t-c", "t-a",
        ]
        assert alert["budget"]["remaining"] < 0  # budget overspent
        # The alert is a durable verdict carrying the trace ids.
        verdicts = [
            r for r in tevents.read_dir(events_dir)
            if r.get("ev") == "verdict" and r.get("action") == "slo_burn"
        ]
        assert len(verdicts) == 1
        assert verdicts[0]["slo"] == spec.name
        assert "t-b" in verdicts[0]["exemplars"]
        # Cooldown: still burning, but no re-alert inside short_s.
        assert engine.tick(1001.5) == []
        # Fresh badness after the cooldown (ends at 1003) fires again;
        # 1003.5 keeps the 1001.5 sample inside the 2s confirm window.
        h.observe(2.0, exemplar="t-d")
        assert len(engine.tick(1003.5)) == 1

    def test_no_alert_when_meeting_objective(self):
        spec = _latency_spec()
        engine = tslo.SloEngine(
            specs=(spec,), windows=((10.0, 2.0, 2.0),), interval_s=0.0
        )
        h = tmetrics.histogram(spec.metric, "test")
        engine.tick(1000.0)
        for _ in range(20):
            h.observe(0.01)
        assert engine.tick(1001.0) == []
        snap = engine.snapshot(1001.0)
        state = snap["slos"][spec.name]
        assert not state["windows"]["10s"]["burning"]
        assert state["budget"]["remaining"] == pytest.approx(1.0)

    def test_availability_slo_counts_sheds(self):
        bad = _metric_name("shed").replace("_seconds", "_total")
        good = _metric_name("served")
        spec = tslo.SloSpec(
            name="avail", kind="availability", metric=bad,
            good_metric=good, target=0.5,
        )
        # Factor 100: measure the window stats without ever alerting.
        engine = tslo.SloEngine(
            specs=(spec,), windows=((10.0, 2.0, 100.0),), interval_s=0.0
        )
        engine.tick(1000.0)
        h = tmetrics.histogram(good, "test")
        for _ in range(8):
            h.observe(0.01)
        tmetrics.counter(bad, "test").inc(2.0, reason="queue_full")
        engine.tick(1001.0)
        w = engine.snapshot(1001.0)["slos"]["avail"]["windows"]["10s"]
        assert w["long"]["events"] == pytest.approx(10.0)
        assert w["long"]["bad_fraction"] == pytest.approx(0.2)
        assert w["long"]["burn_rate"] == pytest.approx(0.4)

    def test_warehouse_budget_roundtrip(self, events_dir):
        wh = TelemetryWarehouse()
        spec = _latency_spec(name="wh_ttft")
        engine = tslo.SloEngine(
            specs=(spec,), windows=((10.0, 2.0, 2.0),), interval_s=0.0,
            warehouse=wh, job_uid="job-slo",
        )
        h = tmetrics.histogram(spec.metric, "test")
        engine.tick(1000.0)
        h.observe(2.0, exemplar="t-wh")
        fired = engine.tick(1001.0)
        assert fired  # the alert forces a kind="slo" record
        engine.persist_budget()  # and the explicit checkpoint path
        trend = wh.slo_trend()
        assert len(trend) == 2
        assert all(r["job_uid"] == "job-slo" for r in trend)
        assert all(r["tightest_slo"] == "wh_ttft" for r in trend)
        assert all(r["budget_remaining"] is not None for r in trend)
        # Exactly one row was alert-forced.
        assert sorted(r["alert"] for r in trend if r["alert"]) == [
            "wh_ttft"
        ]


# -- transport satellites ----------------------------------------------------


class TestTransportTelemetry:
    def test_inflight_gauge_is_shared_registry_metric(self):
        g = transport._inflight_gauge()
        assert tmetrics.gauge("dlrover_rpc_inflight") is g
        v0 = g.value(method="get")
        g.inc(method="get")
        assert g.value(method="get") == pytest.approx(v0 + 1)
        g.dec(method="get")
        assert g.value(method="get") == pytest.approx(v0)

    def test_slow_threshold_parsing(self, monkeypatch):
        monkeypatch.delenv(transport.ENV_SLOW_RPC_S, raising=False)
        assert transport._slow_threshold_s() == transport.DEFAULT_SLOW_RPC_S
        monkeypatch.setenv(transport.ENV_SLOW_RPC_S, "0.25")
        assert transport._slow_threshold_s() == 0.25
        monkeypatch.setenv(transport.ENV_SLOW_RPC_S, "junk")
        assert transport._slow_threshold_s() == transport.DEFAULT_SLOW_RPC_S

    def test_slow_rpc_warns_once_per_method(self, monkeypatch):
        monkeypatch.setenv(transport.ENV_SLOW_RPC_S, "0.05")
        monkeypatch.setattr(transport, "_slow_warned", set())
        warnings = []
        monkeypatch.setattr(
            transport, "logger",
            types.SimpleNamespace(
                warning=lambda *a, **k: warnings.append(a),
                debug=lambda *a, **k: None,
                info=lambda *a, **k: None,
            ),
        )
        n0 = transport._latency_histogram().summary(method="get")["count"]
        transport._note_latency("get", 0.2)
        transport._note_latency("get", 0.3)   # suppressed
        transport._note_latency("get", 0.01)  # under threshold
        assert len(warnings) == 1
        assert "slow RPC" in warnings[0][0]
        transport._note_latency("report", 0.2)  # fresh method warns
        assert len(warnings) == 2
        # Every call still lands in the latency histogram.
        n1 = transport._latency_histogram().summary(method="get")["count"]
        assert n1 == n0 + 3


# -- gateway end-to-end ------------------------------------------------------


class TestGatewayTracing:
    def test_sampled_request_reconstructs_causally(
        self, tiny_model, sampled, events_dir
    ):
        model, params = tiny_model
        gw = InferenceGateway(
            _local_factory(model, params), default_gen_budget=4
        )
        try:
            res = gw.submit([1, 2, 3, 4, 5])
            assert res["ok"] and "trace_id" in res
            out = gw.get(res["request_id"], timeout_s=120)
            assert out["ok"]
        finally:
            gw.stop()
        recon = tracing.reconstruct(
            res["trace_id"], events_dir=events_dir
        )
        assert recon["found"] and recon["span_count"] >= 5
        names = [s["name"] for s in recon["spans"]]
        # The queue span's start is back-dated to admission time (its
        # duration IS the queue wait), so either may sort first — both
        # must precede dispatch and the terminal marker.
        assert names.index("dispatch") < names.index("done")
        assert {"admission", "queue", "dispatch", "commit", "done"} <= set(
            names
        )
        assert _causal(recon["spans"])

    def test_unsampled_request_costs_nothing(self, tiny_model, monkeypatch):
        monkeypatch.setenv(tracing.ENV_SAMPLE_RATE, "0.0")
        tracing.clear_recent()
        model, params = tiny_model
        gw = InferenceGateway(
            _local_factory(model, params), default_gen_budget=4
        )
        try:
            res = gw.submit([1, 2, 3])
            assert res["ok"] and "trace_id" not in res
            assert gw.get(res["request_id"], timeout_s=120)["ok"]
        finally:
            gw.stop()
        assert tracing.recent_spans() == []

    def test_trace_survives_kill_and_replay(
        self, tiny_model, sampled, events_dir
    ):
        """The kill-replay drill keeps ONE timeline: the replayed
        request's spans stay under the original trace id, with a
        reform_replay marker at the boundary."""
        model, params = tiny_model
        gw = InferenceGateway(
            _local_factory(model, params), default_gen_budget=8
        )
        try:
            res = gw.submit([1, 2, 3, 4, 5])
            rid = res["request_id"]
            deadline = time.time() + 120
            while time.time() < deadline:
                gw.pump()
                if len(gw._requests[rid].committed) >= 1:
                    break
            assert gw._requests[rid].committed, "never started decoding"
            gw._replica.kill()
            out = gw.get(rid, timeout_s=120)
            assert out["ok"]
            assert gw.disruptions == 1
        finally:
            gw.stop()
        recon = tracing.reconstruct(
            res["trace_id"], events_dir=events_dir
        )
        names = [s["name"] for s in recon["spans"]]
        assert "reform_replay" in names
        assert "done" in names
        assert _causal(recon["spans"])

    def test_slowed_replica_burns_ttft_slo_into_doctor(
        self, tiny_model, sampled, events_dir
    ):
        """Acceptance analog: a slowed replica drives the TTFT SLO into
        multi-window burn; the verdict carries exemplar trace ids and
        the doctor names the trigger with /trace.json links."""
        model, params = tiny_model
        inner = _local_factory(model, params)

        class SlowReplica:
            def __init__(self, replica, delay_s):
                self._inner = replica
                self._delay = delay_s

            def poll(self):
                time.sleep(self._delay)
                return self._inner.poll()

            def __getattr__(self, name):
                return getattr(self._inner, name)

        def factory():
            return SlowReplica(inner(), 0.08)

        # Same spec as serve_ttft_p99 but with a CI-scale threshold the
        # slowed replica is guaranteed to breach (0.05 is a bucket
        # boundary, as the spec contract requires).
        spec = tslo.SloSpec(
            name="serve_ttft_p99", metric="dlrover_serve_ttft_seconds",
            target=0.9, threshold_s=0.05, quantile=0.99,
        )
        engine = tslo.SloEngine(
            specs=(spec,), windows=((120.0, 60.0, 2.0),), interval_s=0.0
        )
        engine.tick(time.time())  # baseline before the traffic
        gw = InferenceGateway(factory, default_gen_budget=4)
        try:
            rids = [gw.submit([1, 2, 3]) for _ in range(4)]
            assert all(r["ok"] for r in rids)
            for r in rids:
                assert gw.get(r["request_id"], timeout_s=120)["ok"]
        finally:
            gw.stop()
        fired = engine.tick(time.time())
        assert len(fired) == 1
        alert = fired[0]
        assert alert["slo"] == "serve_ttft_p99"
        assert alert["long_burn_rate"] >= alert["burn_factor"]
        assert alert["short_burn_rate"] >= alert["burn_factor"]
        assert len(alert["exemplars"]) >= 1
        # The doctor reconstructs the burn from the durable verdict.
        rows = tevents.read_dir(events_dir)
        report = doctor.diagnose(doctor.SourceData(events=rows))
        assert report["slo_burns"]
        burn = report["slo_burns"][0]
        assert burn["slo"] == "serve_ttft_p99"
        assert len(burn["exemplars"]) >= 1
        md = doctor.render_markdown(report)
        assert "SLO burn alerts" in md
        assert "/trace.json?id=" in md

    @pytest.mark.slow
    def test_sigkill_drill_reconstructs_cross_process_timeline(
        self, tmp_path, sampled, events_dir
    ):
        """The real thing: SIGKILL a decode-worker PROCESS mid-flight,
        then rebuild one sampled request's cross-process timeline —
        gateway spans and (dead + replacement) worker spans merge from
        the shared events directory into one causal order."""
        wargs = dict(
            vocab=64, hidden=32, intermediate=64, layers=2, heads=2,
            kv_heads=2, slots=4, max_len=64, block_size=16, seed=0,
            temperature=1e-6,
        )

        def factory():
            return ProcessReplica(str(tmp_path), worker_args=wargs)

        rng = np.random.default_rng(0)
        prompts = [
            [int(t) for t in rng.integers(1, 64, size=n)]
            for n in (5, 23, 17, 9)
        ]
        gw = InferenceGateway(factory, default_gen_budget=12)
        try:
            subs = [gw.submit(p) for p in prompts]
            rids = [s["request_id"] for s in subs]
            deadline = time.time() + 120
            while time.time() < deadline:
                gw.pump()
                committed = sum(
                    len(gw._requests[r].committed) for r in rids
                )
                if committed >= 6:
                    break
            assert committed >= 6, "never reached mid-generation state"
            os.kill(gw._replica.pid, signal.SIGKILL)
            time.sleep(0.2)
            outs = [gw.get(r, timeout_s=180) for r in rids]
            assert all(o["ok"] for o in outs)
            assert gw.disruptions == 1
        finally:
            gw.stop()
        # The longest prompt's request is all but guaranteed to span the
        # kill; check them all and require at least one cross-process
        # reconstruction with the replay marker.
        crossed = 0
        for sub in subs:
            recon = tracing.reconstruct(
                sub["trace_id"], events_dir=events_dir
            )
            assert recon["found"]
            assert _causal(recon["spans"])
            pids = {s["pid"] for s in recon["spans"]}
            names = [s["name"] for s in recon["spans"]]
            if len(pids) >= 2 and "reform_replay" in names:
                crossed += 1
        assert crossed >= 1
