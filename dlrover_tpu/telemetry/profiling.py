"""Per-step phase breakdown, device-memory watermarks, trace capture.

The goodput accountant (``telemetry/goodput.py``) explains where *wall-clock* went
between steps; this module explains where time goes *inside* a step.
Three instruments, cheapest first:

* :class:`StepPhaseProfiler` — splits each step into host/data wait
  (blocking on the input pipeline), dispatch (tracing + enqueue of the
  jitted step, returns before the device finishes) and device compute
  (the block-until-ready delta when the loss is realized).  Emitted as
  an annotation-only ``step_phase`` telemetry event and observed into
  ``dlrover_step_time_seconds`` per-phase histograms.  When the
  weight-update-sharding overlap scheduler is active the device phase
  further splits into ``device_compute``/``device_collective`` via a
  cost-model fraction (``set_collective_fraction`` — modeled, labeled).
* :func:`update_memory_watermarks` — high-water-mark gauges from
  ``device.memory_stats()`` (TPU/GPU backends; CPU devices without the
  API are skipped silently).
* :func:`capture_trace` — on-demand ``jax.profiler`` trace window,
  triggered by the master's ``/profile`` endpoint (httpd.py).  Traces
  land under ``<telemetry_dir>/profiles/`` so crash bundles pick them
  up (bundle.py ships the directory).

Everything here is advisory: failures are logged-and-swallowed, never
raised into the training loop.
"""

import os
import threading
import time
from typing import Any, Dict, Optional

from dlrover_tpu.common.log import logger
from dlrover_tpu.telemetry import events as tevents
from dlrover_tpu.telemetry import metrics as tmetrics

PHASES = ("data_wait", "dispatch", "device", "total")

# Finer split of ``device``, active only when a collective fraction has
# been installed (``set_collective_fraction``) — the wall clock can't
# see inside one XLA program, so the split is *modeled* (cost-model
# collective bytes / interconnect bandwidth) and every record carries
# its source label so nobody mistakes it for a measurement.
DEVICE_SPLIT_PHASES = ("device_compute", "device_collective")

ENV_STEP_PHASE_INTERVAL = "DLROVER_STEP_PHASE_INTERVAL"

# Step-scale buckets: sub-ms host overheads up to multi-minute stalls.
STEP_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
)


def _histogram() -> "tmetrics.Histogram":
    return tmetrics.histogram(
        "dlrover_step_time_seconds",
        "Per-step time split by phase (data_wait/dispatch/device/total).",
        buckets=STEP_BUCKETS,
    )


class StepPhaseProfiler:
    """Mark the three boundaries of a training step, then record.

    Usage (the trainer loop)::

        prof.begin_step()
        batch = next(it)          # host/data wait
        prof.mark_data()
        state, metrics = step(...)  # dispatch (async under jit)
        prof.mark_dispatch()
        loss = float(metrics["loss"])  # block-until-ready
        prof.end_step(step_no)

    Missing marks degrade gracefully (phases report 0.0) so a loop that
    bails out mid-step never corrupts the next record.  ``end_step``
    emits one ``step_phase`` event every ``emit_interval`` steps
    (default 1, ``DLROVER_STEP_PHASE_INTERVAL`` overrides) and always
    feeds the histograms.
    """

    def __init__(self, emit_interval: Optional[int] = None):
        if emit_interval is None:
            emit_interval = int(
                os.environ.get(ENV_STEP_PHASE_INTERVAL, "1") or 1
            )
        self.emit_interval = max(1, emit_interval)
        self._t0: Optional[float] = None
        self._t_data: Optional[float] = None
        self._t_dispatch: Optional[float] = None
        self._steps = 0
        # Running totals for summary() — host-side only, single thread.
        self._totals = {p: 0.0 for p in PHASES + DEVICE_SPLIT_PHASES}
        self.last: Dict[str, float] = {}
        self._collective_fraction: Optional[float] = None
        self._collective_source = ""
        self._packed_prediction: Optional[Dict[str, float]] = None
        self._packed_source = ""

    def set_collective_fraction(
        self, fraction: Optional[float], source: str = "costmodel"
    ):
        """Install the modeled fraction of device time spent in
        collectives; subsequent steps split ``device`` into
        ``device_compute``/``device_collective``.  Used when the
        weight-update-sharding overlap scheduler is active
        (``parallel/wus.py``): the trainer derives the fraction from the
        cost model's predicted collective bytes.  ``None`` turns the
        split off."""
        if fraction is None:
            self._collective_fraction = None
            self._collective_source = ""
            return
        self._collective_fraction = min(1.0, max(0.0, float(fraction)))
        self._collective_source = str(source)

    def set_packed_prediction(
        self,
        packed_tps: Optional[float],
        dense_tps: Optional[float] = None,
        source: str = "costmodel",
    ):
        """Install the cost model's packed-vs-dense predicted tokens/s
        (``pack_sequences`` runs): both numbers ride every subsequent
        ``step_phase`` event so the warehouse can compare the honest
        mask-aware prediction against the dense-causal one a naive MFU
        report would use.  ``None`` turns the annotation off."""
        if packed_tps is None:
            self._packed_prediction = None
            self._packed_source = ""
            return
        pred = {"packed_pred_tok_s": float(packed_tps)}
        if dense_tps is not None:
            pred["dense_pred_tok_s"] = float(dense_tps)
        self._packed_prediction = pred
        self._packed_source = str(source)

    def begin_step(self):
        self._t0 = time.perf_counter()
        self._t_data = None
        self._t_dispatch = None

    def mark_data(self):
        self._t_data = time.perf_counter()

    def mark_dispatch(self):
        self._t_dispatch = time.perf_counter()

    def end_step(self, step: int):
        if self._t0 is None:
            return
        now = time.perf_counter()
        t_data = self._t_data if self._t_data is not None else self._t0
        t_disp = self._t_dispatch if self._t_dispatch is not None else t_data
        rec = {
            "data_wait": max(0.0, t_data - self._t0),
            "dispatch": max(0.0, t_disp - t_data),
            "device": max(0.0, now - t_disp),
            "total": max(0.0, now - self._t0),
        }
        frac = self._collective_fraction
        if frac is not None:
            rec["device_collective"] = rec["device"] * frac
            rec["device_compute"] = rec["device"] - rec["device_collective"]
        self._t0 = None
        self._steps += 1
        self.last = rec
        try:
            hist = _histogram()
            for phase, value in rec.items():
                self._totals[phase] += value
                hist.observe(value, phase=phase)
        except Exception:  # noqa: BLE001 — advisory only
            logger.exception("step-phase histogram update failed")
        if self._steps % self.emit_interval == 0:
            try:
                extra = {}
                # Piggyback the device-memory high-water mark so the
                # telemetry warehouse gets its device_mem records from
                # the same shipped event (CPU backends have no
                # memory_stats — the fields are simply absent).
                peaks = update_memory_watermarks()
                if peaks:
                    extra["mem_peak_bytes"] = max(peaks.values())
                    extra["mem_devices"] = len(peaks)
                if frac is not None:
                    extra["device_compute_s"] = round(
                        rec["device_compute"], 6
                    )
                    extra["device_collective_s"] = round(
                        rec["device_collective"], 6
                    )
                    extra["collective_split"] = self._collective_source
                if self._packed_prediction is not None:
                    for key, value in self._packed_prediction.items():
                        extra[key] = round(value, 3)
                    extra["packed_prediction"] = self._packed_source
                tevents.emit(
                    "step_phase",
                    step=int(step),
                    data_wait_s=round(rec["data_wait"], 6),
                    dispatch_s=round(rec["dispatch"], 6),
                    device_s=round(rec["device"], 6),
                    total_s=round(rec["total"], 6),
                    **extra,
                )
            except Exception:  # noqa: BLE001 — advisory only
                logger.exception("step_phase emit failed")

    @property
    def steps(self) -> int:
        return self._steps

    def summary(self) -> Dict[str, Any]:
        """Mean seconds per phase over every recorded step."""
        n = max(1, self._steps)
        phases = PHASES + (
            DEVICE_SPLIT_PHASES if self._collective_fraction is not None
            else ()
        )
        return {
            "steps": self._steps,
            "mean_s": {p: self._totals[p] / n for p in phases},
        }


# The process's default profiler — the trainer grabs this so tests and
# a harness can read the same instance's summary.
_default_profiler: Optional[StepPhaseProfiler] = None
_default_lock = threading.Lock()


def get_step_profiler() -> StepPhaseProfiler:
    global _default_profiler
    with _default_lock:
        if _default_profiler is None:
            _default_profiler = StepPhaseProfiler()
        return _default_profiler


def reset_step_profiler():
    global _default_profiler
    with _default_lock:
        _default_profiler = None


# ----------------------------------------------------------------------
# Device-memory watermarks


def update_memory_watermarks(devices=None) -> Dict[str, float]:
    """Publish ``device.memory_stats()`` high-water marks as gauges.

    Returns the per-device peaks that were published (empty when the
    backend has no memory_stats — CPU — or jax is unavailable).  Safe to
    call from the training loop at log cadence.
    """
    out: Dict[str, float] = {}
    if devices is None:
        try:
            import jax

            devices = jax.local_devices()
        except Exception:  # noqa: BLE001 — no backend, nothing to do
            return out
    gauge = tmetrics.gauge(
        "dlrover_device_memory_bytes",
        "Device memory from memory_stats(), by device and kind "
        "(in_use / peak).",
    )
    for d in devices:
        stats_fn = getattr(d, "memory_stats", None)
        if stats_fn is None:
            continue
        try:
            stats = stats_fn() or {}
        except Exception:  # noqa: BLE001 — backend quirk, skip device
            continue
        dev = str(getattr(d, "id", 0))
        in_use = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use")
        if in_use is not None:
            gauge.set(float(in_use), device=dev, kind="in_use")
        if peak is not None:
            gauge.set(float(peak), device=dev, kind="peak")
            out[dev] = float(peak)
    return out


# ----------------------------------------------------------------------
# On-demand jax.profiler trace capture (the /profile endpoint's engine)


def profiles_dir() -> str:
    return os.path.join(tevents.telemetry_dir(), "profiles")


_trace_lock = threading.Lock()
_trace_state: Dict[str, Any] = {"active": False, "dir": "", "captures": 0}

MAX_TRACE_SECONDS = 120.0
DEFAULT_TRACE_SECONDS = 5.0


def trace_status() -> Dict[str, Any]:
    with _trace_lock:
        return dict(_trace_state)


def capture_trace(
    seconds: float = DEFAULT_TRACE_SECONDS,
    out_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """Start a ``jax.profiler`` trace for ``seconds``, stopping on a
    timer thread.  One capture at a time; a second request while one is
    running is refused (409 at the endpoint).  The trace directory is
    returned immediately — callers poll :func:`trace_status` or just
    wait ``seconds``.
    """
    seconds = max(0.1, min(float(seconds), MAX_TRACE_SECONDS))
    with _trace_lock:
        if _trace_state["active"]:
            return {
                "ok": False,
                "error": "trace already active",
                "dir": _trace_state["dir"],
            }
        if out_dir is None:
            out_dir = os.path.join(
                profiles_dir(),
                "trace_%d_%d" % (int(time.time()), os.getpid()),
            )
        try:
            os.makedirs(out_dir, exist_ok=True)
            import jax

            jax.profiler.start_trace(out_dir)
        except Exception as e:  # noqa: BLE001 — report, don't raise
            logger.warning("trace capture failed to start: %s", e)
            return {"ok": False, "error": str(e), "dir": out_dir}
        _trace_state.update(active=True, dir=out_dir)

    def _stop():
        time.sleep(seconds)
        with _trace_lock:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — already stopped
                logger.warning("trace capture stop failed: %s", e)
            _trace_state.update(
                active=False, captures=_trace_state["captures"] + 1
            )
        logger.info("profiler trace written to %s", out_dir)

    threading.Thread(target=_stop, name="trace-capture", daemon=True).start()
    return {"ok": True, "dir": out_dir, "seconds": seconds}
