"""Declarative SLOs + multi-window multi-burn-rate alerting.

The metrics registry answers "what is the p99 right now"; this module
answers the operator questions above it: *is the service meeting its
objectives, how fast is it spending its error budget, and which
requests should I look at first?*

* :class:`SloSpec` declares one objective — a latency SLO ("99% of
  requests see TTFT ≤ 500 ms") over a histogram, or an availability SLO
  ("99.5% of admissions are served, not shed") over a bad-event counter
  paired with a served-request histogram.
* :class:`SloEngine` snapshots the process-local registry on a cadence
  (cumulative histograms/counters diff cleanly, the standard Prometheus
  recipe), estimates windowed quantiles off the bucket diffs, and runs
  **multi-window multi-burn-rate** alerting: an alert fires only when
  BOTH the long window and its short confirmation window burn the error
  budget faster than the pair's factor — fast enough to page on a real
  regression, immune to a single slow request.
* A firing alert becomes a durable ``verdict`` event
  (``action="slo_burn"``) carrying exemplar trace ids of the slowest
  sampled requests (the ``/trace.json?id=...`` links), and the running
  error-budget account is persisted as a ``kind="slo"`` warehouse
  record.  ``snapshot()`` backs the gateway's ``/slo.json``.
"""

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from dlrover_tpu.common.log import logger
from dlrover_tpu.telemetry import events as _events
from dlrover_tpu.telemetry import metrics as _metrics

# (long window s, short confirmation window s, burn-rate factor) —
# Google SRE workbook pairs, scaled for a process-local engine.
DEFAULT_WINDOWS: Tuple[Tuple[float, float, float], ...] = (
    (3600.0, 300.0, 14.4),
    (21600.0, 1800.0, 6.0),
)


@dataclass(frozen=True)
class SloSpec:
    """One objective over the process-local registry.

    ``kind="latency"``: good events are observations of histogram
    ``metric`` at or under ``threshold_s`` (measured at the nearest
    bucket boundary ≥ the threshold — pick thresholds on boundaries).
    ``kind="availability"``: bad events are increments of counter
    ``metric`` (summed across label sets), good events are
    observations of histogram ``good_metric``.

    ``label_filter`` restricts BOTH metrics to label sets containing
    every listed ``(name, value)`` pair — how the canary objectives
    (observer/canary.py) carve the serve and kv probes out of the one
    ``dlrover_canary_*`` metric family without separate metric names.
    """

    name: str
    metric: str
    kind: str = "latency"               # "latency" | "availability"
    target: float = 0.99                # objective fraction of good events
    threshold_s: float = 0.5            # latency only
    quantile: float = 0.99              # reported windowed quantile
    good_metric: str = ""               # availability only
    label_filter: Tuple[Tuple[str, str], ...] = ()

    def __post_init__(self):
        if self.kind not in ("latency", "availability"):
            raise ValueError(f"unknown SLO kind {self.kind!r}")
        if not 0.0 < self.target < 1.0:
            raise ValueError("target must be in (0, 1)")
        if self.kind == "availability" and not self.good_metric:
            raise ValueError("availability SLOs need a good_metric")


# The serving + kv tier objectives (docs/TRACING.md).  Thresholds are
# sized for the CI-scale tiny model, not production hardware — the
# point is the machinery, re-declare for a real deployment.
DEFAULT_SPECS: Tuple[SloSpec, ...] = (
    SloSpec(name="serve_ttft_p99", metric="dlrover_serve_ttft_seconds",
            target=0.99, threshold_s=5.0, quantile=0.99),
    SloSpec(name="serve_tpot_p99", metric="dlrover_serve_tpot_seconds",
            target=0.99, threshold_s=0.5, quantile=0.99),
    SloSpec(name="serve_availability", kind="availability",
            metric="dlrover_serve_shed_total",
            good_metric="dlrover_serve_ttft_seconds", target=0.995),
    SloSpec(name="kv_lookup_p99", metric="dlrover_kv_gather_seconds",
            target=0.99, threshold_s=0.1, quantile=0.99),
    # Update-to-serve freshness of replicated embedding shards: a
    # replication link acked within threshold_s of the mutation is
    # "good".  Burns when the stream stalls (kv_repl_stall) — the
    # online-learning scenario's first-class freshness objective.
    SloSpec(name="kv_freshness", metric="dlrover_kv_repl_lag_seconds",
            target=0.99, threshold_s=0.1, quantile=0.99),
)


@dataclass
class _Sample:
    """One registry snapshot for one spec: cumulative (good, total)
    event counts plus the raw bucket counts for windowed quantiles."""

    t: float
    good: float
    total: float
    buckets: Tuple[float, ...] = ()
    counts: Tuple[float, ...] = ()


@dataclass
class _SpecState:
    spec: SloSpec
    history: "deque[_Sample]" = field(default_factory=deque)
    alert_until: float = 0.0            # cooldown end for re-alerting
    alerts: int = 0


def _match(key, label_filter) -> bool:
    """True when a series' label key contains every filter pair."""
    if not label_filter:
        return True
    pairs = set(key)
    return all((k, v) in pairs for k, v in label_filter)


def _hist_cumulative(
    hist: _metrics.Histogram,
    label_filter: Tuple[Tuple[str, str], ...] = (),
) -> Tuple[Tuple[float, ...], List[float], float]:
    """(bucket uppers, summed cumulative counts, total n) across every
    matching label set of a histogram."""
    snap = hist.snapshot()
    counts = [0.0] * len(hist.buckets)
    n = 0.0
    for key, (series_counts, _total, series_n) in snap.items():
        if not _match(key, label_filter):
            continue
        for i, c in enumerate(series_counts):
            counts[i] += c
        n += series_n
    return hist.buckets, counts, n


def _counter_total(
    counter: _metrics.Counter,
    label_filter: Tuple[Tuple[str, str], ...] = (),
) -> float:
    return sum(
        v for _name, key, v in counter.samples()
        if _match(key, label_filter)
    )


class SloEngine:
    """Evaluate :class:`SloSpec` objectives off the metrics registry.

    Drive it with :meth:`maybe_tick` from any existing pump loop (the
    gateway's ``_tick`` does) — it self-throttles to ``interval_s`` and
    never raises into the caller.
    """

    def __init__(
        self,
        specs: Optional[Tuple[SloSpec, ...]] = None,
        windows: Tuple[Tuple[float, float, float], ...] = DEFAULT_WINDOWS,
        interval_s: float = 5.0,
        warehouse: Optional[Any] = None,
        job_uid: str = "",
        exemplar_limit: int = 3,
    ):
        self._specs = tuple(specs if specs is not None else DEFAULT_SPECS)
        if not windows:
            raise ValueError("need at least one (long, short, factor)")
        self._windows = tuple(
            (float(l), float(s), float(f)) for l, s, f in windows
        )
        self._interval = max(float(interval_s), 0.0)
        self._warehouse = warehouse
        self._job_uid = job_uid or "slo"
        self._exemplar_limit = max(int(exemplar_limit), 1)
        self._states = {s.name: _SpecState(spec=s) for s in self._specs}
        self._lock = threading.Lock()
        self._last_tick = 0.0
        self._started = time.time()
        # History must outlive the longest window by one sample.
        self._max_age = max(l for l, _s, _f in self._windows) * 1.5

    # -- sampling ----------------------------------------------------------

    def _measure(self, spec: SloSpec, now: float) -> _Sample:
        if spec.kind == "latency":
            hist = _metrics.histogram(spec.metric)
            uppers, counts, n = _hist_cumulative(hist, spec.label_filter)
            good = 0.0
            for le, c in zip(uppers, counts):
                good = c
                if le >= spec.threshold_s:
                    break
            else:
                good = n  # threshold above every finite bucket
            return _Sample(t=now, good=good, total=n,
                           buckets=uppers, counts=tuple(counts))
        bad = _counter_total(
            _metrics.counter(spec.metric), spec.label_filter
        )
        _u, _c, served = _hist_cumulative(
            _metrics.histogram(spec.good_metric), spec.label_filter
        )
        return _Sample(t=now, good=served, total=served + bad)

    def _window_frame(
        self, state: _SpecState, now: float, window_s: float
    ) -> Optional[Tuple[_Sample, _Sample]]:
        """(oldest sample inside the window, newest sample) — None until
        the window has two samples to diff."""
        if not state.history:
            return None
        newest = state.history[-1]
        base = None
        for sample in state.history:
            if sample.t >= now - window_s:
                base = sample
                break
        if base is None or base is newest:
            return None
        return base, newest

    def _window_stats(
        self, state: _SpecState, now: float, window_s: float
    ) -> Dict[str, float]:
        """bad fraction + burn rate (and windowed quantile for latency
        specs) over one sliding window."""
        frame = self._window_frame(state, now, window_s)
        out = {"events": 0.0, "bad_fraction": 0.0, "burn_rate": 0.0}
        if frame is None:
            return out
        base, newest = frame
        d_total = newest.total - base.total
        if d_total <= 0:
            return out
        d_bad = max(d_total - (newest.good - base.good), 0.0)
        budget = 1.0 - state.spec.target
        out["events"] = d_total
        out["bad_fraction"] = d_bad / d_total
        out["burn_rate"] = (d_bad / d_total) / budget
        if state.spec.kind == "latency" and newest.counts and base.counts:
            d_counts = [
                max(a - b, 0.0)
                for a, b in zip(newest.counts, base.counts)
            ]
            out[f"p{round(state.spec.quantile * 100)}"] = (
                _metrics.quantile_from_cumulative(
                    newest.buckets, d_counts, d_total, state.spec.quantile
                )
            )
        return out

    # -- exemplars ---------------------------------------------------------

    def _slow_exemplars(self, spec: SloSpec) -> List[Dict[str, Any]]:
        """The slowest sampled requests for a spec — bucket exemplars
        at/above the latency threshold, slowest first."""
        metric = spec.metric if spec.kind == "latency" else spec.good_metric
        hist = _metrics.histogram(metric)
        rows = hist.all_exemplars()
        if spec.label_filter:
            rows = [
                r for r in rows
                if all(
                    r.get("labels", {}).get(k) == v
                    for k, v in spec.label_filter
                )
            ]
        if spec.kind == "latency":
            rows = [r for r in rows if r["value"] > spec.threshold_s]
        rows.sort(key=lambda r: -r["value"])
        return [
            {"trace_id": r["trace_id"], "value": r["value"]}
            for r in rows[: self._exemplar_limit]
        ]

    # -- evaluation --------------------------------------------------------

    def maybe_tick(self, now: Optional[float] = None) -> None:
        """Throttled snapshot + evaluation; safe to call every pump."""
        now = time.time() if now is None else float(now)
        with self._lock:
            if now - self._last_tick < self._interval:
                return
            self._last_tick = now
        self.tick(now)

    def tick(self, now: Optional[float] = None) -> List[Dict[str, Any]]:
        """Unthrottled: snapshot every spec, evaluate every window pair,
        emit ``slo_burn`` verdicts for new alerts.  Returns the alerts
        fired this tick (tests drive this directly)."""
        now = time.time() if now is None else float(now)
        fired: List[Dict[str, Any]] = []
        with self._lock:
            for state in self._states.values():
                state.history.append(self._measure(state.spec, now))
                while (
                    len(state.history) > 2
                    and state.history[0].t < now - self._max_age
                ):
                    state.history.popleft()
                alert = self._evaluate(state, now)
                if alert is not None:
                    fired.append(alert)
        for alert in fired:
            self._emit_alert(alert)
        return fired

    def _evaluate(
        self, state: _SpecState, now: float
    ) -> Optional[Dict[str, Any]]:
        for long_s, short_s, factor in self._windows:
            long_w = self._window_stats(state, now, long_s)
            short_w = self._window_stats(state, now, short_s)
            if (
                long_w["events"] > 0
                and short_w["events"] > 0
                and long_w["burn_rate"] >= factor
                and short_w["burn_rate"] >= factor
            ):
                if now < state.alert_until:
                    return None  # still in cooldown for this spec
                state.alert_until = now + short_s
                state.alerts += 1
                return {
                    "slo": state.spec.name,
                    "kind": state.spec.kind,
                    "target": state.spec.target,
                    "window_s": long_s,
                    "confirm_window_s": short_s,
                    "burn_factor": factor,
                    "long_burn_rate": long_w["burn_rate"],
                    "short_burn_rate": short_w["burn_rate"],
                    "bad_fraction": long_w["bad_fraction"],
                    "exemplars": self._slow_exemplars(state.spec),
                    "budget": self._budget_locked(state),
                }
        return None

    def _emit_alert(self, alert: Dict[str, Any]) -> None:
        try:
            _events.emit(
                "verdict",
                action="slo_burn",
                slo=alert["slo"],
                window_s=alert["window_s"],
                burn_rate=alert["long_burn_rate"],
                burn_factor=alert["burn_factor"],
                exemplars=[e["trace_id"] for e in alert["exemplars"]],
            )
        except Exception:  # noqa: BLE001 — alerting must not kill pumps
            logger.debug("slo_burn verdict emit failed", exc_info=True)
        logger.warning(
            "SLO burn: %s burning %.1fx budget over %ss (confirmed at "
            "%.1fx over %ss); slowest sampled traces: %s",
            alert["slo"], alert["long_burn_rate"], alert["window_s"],
            alert["short_burn_rate"], alert["confirm_window_s"],
            [e["trace_id"] for e in alert["exemplars"]] or "none sampled",
        )
        self._persist(alert)

    # -- budget accounting -------------------------------------------------

    def _budget_locked(self, state: _SpecState) -> Dict[str, float]:
        """Lifetime error-budget account off the newest sample."""
        budget = 1.0 - state.spec.target
        if not state.history:
            return {"budget": budget, "consumed": 0.0, "remaining": 1.0}
        newest = state.history[-1]
        if newest.total <= 0:
            return {"budget": budget, "consumed": 0.0, "remaining": 1.0}
        bad_frac = max(newest.total - newest.good, 0.0) / newest.total
        consumed = bad_frac / budget
        return {
            "budget": budget,
            "consumed": consumed,
            "remaining": 1.0 - consumed,
        }

    def _persist(self, alert: Optional[Dict[str, Any]] = None) -> None:
        """Write the error-budget account (and the triggering alert, if
        any) as one ``kind="slo"`` warehouse record."""
        if self._warehouse is None:
            return
        try:
            entry = dict(self.snapshot())
            if alert is not None:
                entry["alert"] = alert
            self._warehouse.add_slo_record(
                self._job_uid, entry,
                trigger=alert["slo"] if alert else "",
            )
        except Exception:  # noqa: BLE001 — persistence is best-effort
            logger.debug("slo warehouse record failed", exc_info=True)

    def persist_budget(self) -> None:
        """Checkpoint the current account."""
        self._persist(None)

    # -- exposure ----------------------------------------------------------

    def burning(self, now: Optional[float] = None) -> List[str]:
        """Names of specs currently burning on ANY window pair — the
        capacity signal the serving fleet's autoscaler consumes
        (serving/fleet.py): a burning TTFT/TPOT/availability SLO asks
        for another replica even when the queue alone would not."""
        now = time.time() if now is None else float(now)
        out: List[str] = []
        with self._lock:
            for name, state in self._states.items():
                for long_s, short_s, factor in self._windows:
                    lw = self._window_stats(state, now, long_s)
                    sw = self._window_stats(state, now, short_s)
                    if (
                        lw["events"] > 0 and sw["events"] > 0
                        and lw["burn_rate"] >= factor
                        and sw["burn_rate"] >= factor
                    ):
                        out.append(name)
                        break
        return out

    def snapshot(self, now: Optional[float] = None) -> Dict[str, Any]:
        """The ``/slo.json`` payload: every spec's windowed stats, burn
        rates, budget account and slow-request exemplars."""
        now = time.time() if now is None else float(now)
        out: Dict[str, Any] = {
            "ts": now,
            "uptime_s": now - self._started,
            "windows": [list(w) for w in self._windows],
            "slos": {},
        }
        with self._lock:
            for name, state in self._states.items():
                spec = state.spec
                per_window = {}
                alerting = False
                for long_s, short_s, factor in self._windows:
                    lw = self._window_stats(state, now, long_s)
                    sw = self._window_stats(state, now, short_s)
                    burning = (
                        lw["events"] > 0 and sw["events"] > 0
                        and lw["burn_rate"] >= factor
                        and sw["burn_rate"] >= factor
                    )
                    alerting = alerting or burning
                    per_window[f"{int(long_s)}s"] = {
                        "long": lw, "short": sw,
                        "factor": factor, "burning": burning,
                    }
                out["slos"][name] = {
                    "kind": spec.kind,
                    "metric": spec.metric,
                    "target": spec.target,
                    "threshold_s": (
                        spec.threshold_s
                        if spec.kind == "latency" else None
                    ),
                    "windows": per_window,
                    "budget": self._budget_locked(state),
                    "alerts": state.alerts,
                    "exemplars": self._slow_exemplars(spec),
                }
        return out
