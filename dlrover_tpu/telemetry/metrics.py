"""Process-local metrics registry with Prometheus text exposition.

Counter / Gauge / Histogram, stdlib only, thread-safe.  The default
:data:`REGISTRY` is the process's single sink: ``SpeedMonitor``,
``LocalStatsReporter`` and the agent resource monitor publish into it
instead of (only) their private lists, and the master's telemetry HTTP
endpoint serves it at ``/metrics`` in the Prometheus text format
(``text/plain; version=0.0.4``) — scrapeable by any Prometheus without a
client library in the image.
"""

import bisect
import math
import re
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

# Prometheus-convention default buckets (seconds-scale latencies).
DEFAULT_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
    30.0, 60.0, 120.0, 300.0,
)

LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, str]) -> LabelKey:
    for k in labels:
        if not _LABEL_RE.match(k):
            raise ValueError(f"invalid label name {k!r}")
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _fmt_labels(key: LabelKey) -> str:
    if not key:
        return ""
    inner = ",".join(
        '{}="{}"'.format(
            k, v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
        )
        for k, v in key
    )
    return "{" + inner + "}"


def _fmt_value(v: float) -> str:
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class _Metric:
    type_name = ""

    def __init__(self, name: str, help_text: str = ""):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        self.name = name
        self.help = help_text
        self._lock = threading.Lock()

    def samples(self) -> Iterable[Tuple[str, LabelKey, float]]:
        raise NotImplementedError

    def series_count(self) -> int:
        raise NotImplementedError


class Counter(_Metric):
    type_name = "counter"

    def __init__(self, name: str, help_text: str = ""):
        super().__init__(name, help_text)
        self._values: Dict[LabelKey, float] = {}

    def inc(self, value: float = 1.0, **labels: str):
        if value < 0:
            raise ValueError("counters only go up")
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + value

    def value(self, **labels: str) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    def samples(self):
        with self._lock:
            return [
                (self.name, key, v) for key, v in self._values.items()
            ]

    def series_count(self) -> int:
        with self._lock:
            return len(self._values)


class Gauge(_Metric):
    type_name = "gauge"

    def __init__(self, name: str, help_text: str = ""):
        super().__init__(name, help_text)
        self._values: Dict[LabelKey, float] = {}

    def set(self, value: float, **labels: str):
        with self._lock:
            self._values[_label_key(labels)] = float(value)

    def inc(self, value: float = 1.0, **labels: str):
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + value

    def dec(self, value: float = 1.0, **labels: str):
        self.inc(-value, **labels)

    def value(self, **labels: str) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    def samples(self):
        with self._lock:
            return [
                (self.name, key, v) for key, v in self._values.items()
            ]

    def series_count(self) -> int:
        with self._lock:
            return len(self._values)


class Histogram(_Metric):
    type_name = "histogram"

    def __init__(
        self,
        name: str,
        help_text: str = "",
        buckets: Tuple[float, ...] = DEFAULT_BUCKETS,
    ):
        super().__init__(name, help_text)
        self.buckets = tuple(sorted(buckets))
        # per label-set: (bucket counts, sum, count)
        self._series: Dict[LabelKey, Tuple[List[int], float, int]] = {}
        # per label-set: bucket index -> (exemplar trace_id, value, t).
        # The LAST sampled observation that landed in each bucket — the
        # link from "p99 spiked" to one reconstructable trace
        # (/trace.json?id=...).  Index len(buckets) is the +Inf bucket.
        self._exemplars: Dict[LabelKey, Dict[int, Tuple[str, float, float]]] = {}

    def observe(
        self, value: float, exemplar: Optional[str] = None, **labels: str
    ):
        key = _label_key(labels)
        with self._lock:
            counts, total, n = self._series.get(
                key, ([0] * len(self.buckets), 0.0, 0)
            )
            for i, le in enumerate(self.buckets):
                if value <= le:
                    counts[i] += 1
            self._series[key] = (counts, total + value, n + 1)
            if exemplar:
                idx = len(self.buckets)
                for i, le in enumerate(self.buckets):
                    if value <= le:
                        idx = i
                        break
                self._exemplars.setdefault(key, {})[idx] = (
                    str(exemplar), float(value), time.time()
                )

    def samples(self):
        out = []
        with self._lock:
            for key, (counts, total, n) in self._series.items():
                for le, c in zip(self.buckets, counts):
                    out.append(
                        (
                            self.name + "_bucket",
                            key + (("le", _fmt_value(le)),),
                            float(c),
                        )
                    )
                out.append(
                    (
                        self.name + "_bucket",
                        key + (("le", "+Inf"),),
                        float(n),
                    )
                )
                out.append((self.name + "_sum", key, total))
                out.append((self.name + "_count", key, float(n)))
        return out

    def series_count(self) -> int:
        with self._lock:
            return len(self._series)

    def snapshot(self) -> Dict[LabelKey, Tuple[Tuple[int, ...], float, int]]:
        """Immutable copy of every series' (cumulative bucket counts,
        sum, count) — what the SLO engine diffs for sliding windows."""
        with self._lock:
            return {
                key: (tuple(counts), total, n)
                for key, (counts, total, n) in self._series.items()
            }

    def quantile(self, q: float, **labels: str) -> float:
        """Bucket-interpolated quantile over one series (0.0 when the
        series has no observations)."""
        with self._lock:
            counts, _total, n = self._series.get(
                _label_key(labels), ([0] * len(self.buckets), 0.0, 0)
            )
            return quantile_from_cumulative(self.buckets, counts, n, q)

    def summary(
        self,
        qs: Sequence[float] = (0.5, 0.95, 0.99),
        **labels: str,
    ) -> Dict[str, float]:
        """{"p50": ..., "p95": ..., "p99": ..., "count": n, "sum": s}
        for one series — the /servz and /kvz latency block."""
        with self._lock:
            counts, total, n = self._series.get(
                _label_key(labels), ([0] * len(self.buckets), 0.0, 0)
            )
        out: Dict[str, float] = {}
        for q in qs:
            out[f"p{round(q * 100)}"] = quantile_from_cumulative(
                self.buckets, counts, n, q
            )
        out["count"] = float(n)
        out["sum"] = float(total)
        return out

    def exemplars(self, **labels: str) -> List[Dict[str, Any]]:
        """Per-bucket exemplars for one series, slowest bucket last:
        [{"le": ..., "trace_id": ..., "value": ..., "t": ...}]."""
        with self._lock:
            per_bucket = dict(self._exemplars.get(_label_key(labels), {}))
        out = []
        for idx in sorted(per_bucket):
            tid, value, t = per_bucket[idx]
            le = (
                self.buckets[idx] if idx < len(self.buckets)
                else float("inf")
            )
            out.append(
                {"le": le, "trace_id": tid, "value": value, "t": t}
            )
        return out

    def all_exemplars(self) -> List[Dict[str, Any]]:
        """Exemplars across every label-set, slowest bucket last."""
        with self._lock:
            keys = list(self._exemplars)
        out: List[Dict[str, Any]] = []
        for key in keys:
            for ex in self.exemplars(**dict(key)):
                ex["labels"] = dict(key)
                out.append(ex)
        out.sort(key=lambda e: e["le"])
        return out


def merge_cumulative(
    series: Sequence[Tuple[Sequence[float], Sequence[float], float]],
) -> Tuple[Tuple[float, ...], Tuple[float, ...], float]:
    """Merge Prometheus-style CUMULATIVE bucket series into ONE series.

    ``series`` is a sequence of ``(uppers, cumulative_counts, total)``
    triples — one per label set, per process, or per scrape source.
    Returns the merged ``(uppers, cumulative, total)`` on the union of
    all finite bucket bounds, ready for
    :func:`quantile_from_cumulative`.

    When every input shares one bucket axis (the repo-wide norm — each
    metric name declares its buckets once) the merge is EXACT: the
    cumulative count at each bound is the plain sum.  With differing
    axes, a series' count at a foreign bound is read at its own largest
    bound ≤ that bound (a floor step-function), which under-counts
    inside a bucket but preserves monotonicity and the per-bucket
    totals — fleet quantiles stay within one bucket boundary of truth,
    the same resolution any single cumulative histogram has.

    Shared by ``/servz`` and ``/kvz`` (via :func:`aggregate_summary`)
    and the fleet observer's federation (observer/federation.py), so
    fleet-wide p50/p95/p99 come out of the exact same math as the
    per-process views.
    """
    axes = []
    for uppers, _counts, _n in series:
        axes.append([float(u) for u in uppers if not math.isinf(u)])
    union = sorted({u for axis in axes for u in axis})
    merged = [0.0] * len(union)
    total = 0.0
    for (uppers, counts, n), axis in zip(series, axes):
        total += float(n)
        counts = list(counts)
        if not axis:
            continue
        for i, u in enumerate(union):
            j = bisect.bisect_right(axis, u) - 1
            if 0 <= j < len(counts):
                merged[i] += float(counts[j])
    return tuple(union), tuple(merged), total


def aggregate_summary(
    hist: "Histogram", qs: Sequence[float] = (0.5, 0.95, 0.99)
) -> Dict[str, float]:
    """Quantile summary over ALL of a histogram's label-sets combined
    (the /servz and /kvz view: one number per percentile regardless of
    how the series are labelled)."""
    snap = hist.snapshot()
    total = sum(s for _counts, s, _c in snap.values())
    uppers, counts, n = merge_cumulative(
        [(hist.buckets, bucket_counts, c)
         for bucket_counts, _s, c in snap.values()]
    )
    out: Dict[str, float] = {}
    for q in qs:
        out[f"p{round(q * 100)}"] = quantile_from_cumulative(
            uppers, counts, n, q
        )
    out["count"] = float(n)
    out["sum"] = float(total)
    return out


def quantile_from_cumulative(
    uppers: Sequence[float],
    cumulative: Sequence[int],
    total: int,
    q: float,
) -> float:
    """Shared quantile estimator over Prometheus-style CUMULATIVE
    bucket counts (each entry counts observations <= its upper bound).

    Linear interpolation inside the target bucket, the same model as
    PromQL's ``histogram_quantile``; observations past the last finite
    bucket clamp to its upper bound.  Returns 0.0 for an empty series.
    """
    if total <= 0 or not uppers:
        return 0.0
    q = min(max(float(q), 0.0), 1.0)
    rank = q * total
    prev_upper, prev_cum = 0.0, 0
    for upper, cum in zip(uppers, cumulative):
        if cum >= rank:
            if cum == prev_cum:
                return float(upper)
            frac = (rank - prev_cum) / (cum - prev_cum)
            return prev_upper + (float(upper) - prev_upper) * frac
        prev_upper, prev_cum = float(upper), int(cum)
    return float(uppers[-1])


class MetricsRegistry:
    """Name → metric map with idempotent getters (registering the same
    name twice returns the existing metric — adapters in long-lived
    singletons must not fight over ownership)."""

    def __init__(self):
        self._metrics: Dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def _get_or_create(self, cls, name, help_text, **kwargs):
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if not isinstance(existing, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{existing.type_name}"
                    )
                return existing
            metric = cls(name, help_text, **kwargs)
            self._metrics[name] = metric
            return metric

    def counter(self, name: str, help_text: str = "") -> Counter:
        return self._get_or_create(Counter, name, help_text)

    def gauge(self, name: str, help_text: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help_text)

    def histogram(
        self,
        name: str,
        help_text: str = "",
        buckets: Tuple[float, ...] = DEFAULT_BUCKETS,
    ) -> Histogram:
        return self._get_or_create(
            Histogram, name, help_text, buckets=buckets
        )

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def unregister(self, name: str):
        with self._lock:
            self._metrics.pop(name, None)

    def clear(self):
        with self._lock:
            self._metrics.clear()

    def counts(self) -> Dict[str, int]:
        """{metric name: series count}."""
        with self._lock:
            metrics = list(self._metrics.values())
        return {m.name: m.series_count() for m in metrics}

    def render(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        with self._lock:
            metrics = sorted(self._metrics.values(), key=lambda m: m.name)
        return render_subset(metrics)


def render_subset(metrics: Iterable[_Metric]) -> str:
    """Prometheus text exposition (0.0.4) over an explicit metric list.

    Endpoints that must expose ONLY their own metrics — the kv shard's
    mini-httpd in a process that may host other subsystems in the same
    default registry — render their subset here, so a federating
    scraper never double-counts a series it already collected from
    another endpoint of the same process."""
    lines: List[str] = []
    for m in metrics:
        if m.help:
            lines.append(
                "# HELP {} {}".format(
                    m.name,
                    m.help.replace("\\", "\\\\").replace("\n", "\\n"),
                )
            )
        lines.append(f"# TYPE {m.name} {m.type_name}")
        for name, key, value in m.samples():
            lines.append(
                f"{name}{_fmt_labels(key)} {_fmt_value(value)}"
            )
    return "\n".join(lines) + "\n"


# The process-wide default registry (what /metrics serves).
REGISTRY = MetricsRegistry()


def counter(name: str, help_text: str = "") -> Counter:
    return REGISTRY.counter(name, help_text)


def gauge(name: str, help_text: str = "") -> Gauge:
    return REGISTRY.gauge(name, help_text)


def histogram(
    name: str, help_text: str = "", buckets: Tuple[float, ...] = DEFAULT_BUCKETS
) -> Histogram:
    return REGISTRY.histogram(name, help_text, buckets=buckets)


def render_metrics() -> str:
    return REGISTRY.render()
