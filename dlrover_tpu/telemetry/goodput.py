"""Online goodput accountant: event stream → wall-clock attribution.

This module computes goodput live, continuously, from the telemetry
event stream — per rank, aggregated on the master (servicer ``report``
RPC feeds
:meth:`GoodputAccountant.ingest`, the telemetry HTTP endpoint serves
:meth:`summary` at ``/goodput.json``).

Attribution model — a state machine per (role, rank) stream.  Each
interval between consecutive events is charged to the phase the stream
is in *after* the earlier event:

========================  =========================================
after event               phase charged until the next event
========================  =========================================
process_start             rendezvous   (booting + joining the world)
rendezvous / reform       rendezvous
world_init                idle         (formed, not yet stepping)
restore_begin             restore
compile_begin             compile
restore_end / compile_end idle
step                      productive
stall                     stalled
preempt / exit            detect_respawn
========================  =========================================

with one override: the interval *ending* at a ``process_start`` is
always detect+respawn — a SIGKILLed incarnation leaves no terminal
event, so the gap between its last event and the replacement's first is
the detection + respawn cost by definition.

``goodput_pct`` divides productive time by the window starting at the
stream's FIRST step (matching the offline harness, whose wall clock
starts at the first completed step: incarnation 0's cold compile is a
fixed cost, not a preemption loss).  Only ``role == "worker"`` streams
enter the aggregate — agent/master streams appear in the trace but do
not train.
"""

import threading
from typing import Any, Dict, Iterable, List, Optional, Tuple

PHASES = (
    "productive",
    "detect_respawn",
    "rendezvous",
    "compile",
    "restore",
    "stalled",
    "idle",
)

# State entered AFTER each event (see module docstring).
_STATE_AFTER = {
    "process_start": "rendezvous",
    "rendezvous": "rendezvous",
    "reform": "rendezvous",
    "world_init": "idle",
    "restore_begin": "restore",
    "restore_end": "idle",
    "compile_begin": "compile",
    "compile_end": "idle",
    "step": "productive",
    "stall": "stalled",
    "preempt": "detect_respawn",
    "exit": "detect_respawn",
    # save_* and generic spans annotate the timeline without changing
    # the attribution phase (saves are async off the critical path).
    # Likewise verdict/bundle/fault: diagnosis conclusions, bundle
    # captures and injected chaos markers are annotations on the
    # timeline, never attribution states.
}


class GoodputAccountant:
    """Incremental, duplicate- and disorder-tolerant accountant.

    ``ingest`` may receive events out of order (per-rank files shipped
    in file-name order, RPC retries re-sending a batch): events are
    deduplicated on (role, rank, pid, mono, ev) and kept sorted per
    stream; attribution is recomputed on demand — streams are small
    (steps dominate; a day-long run is O(10^5) events).
    """

    def __init__(self, max_events_per_stream: int = 200_000):
        self._streams: Dict[Tuple[str, int], List[dict]] = {}
        self._seen: Dict[Tuple[str, int], set] = {}
        self._max = max_events_per_stream
        self._lock = threading.Lock()
        self.events_ingested = 0

    # -- ingest -----------------------------------------------------------
    def ingest(self, events: Iterable[Dict[str, Any]]) -> int:
        """Fold a batch into the per-stream timelines; returns the number
        of NEW (non-duplicate) events accepted."""
        accepted = 0
        with self._lock:
            for e in events:
                if not isinstance(e, dict) or "ev" not in e:
                    continue
                role = str(e.get("role", "worker"))
                try:
                    rank = int(e.get("rank", 0))
                except (TypeError, ValueError):
                    rank = 0
                key = (role, rank)
                dedup = (
                    e.get("pid", 0),
                    round(float(e.get("mono", e.get("t", 0.0))), 6),
                    e["ev"],
                )
                seen = self._seen.setdefault(key, set())
                if dedup in seen:
                    continue
                seen.add(dedup)
                stream = self._streams.setdefault(key, [])
                stream.append(e)
                if len(stream) > self._max:
                    del stream[: len(stream) - self._max]
                accepted += 1
                self.events_ingested += 1
        return accepted

    # -- attribution ------------------------------------------------------
    @staticmethod
    def _attribute(
        stream: List[dict],
    ) -> Tuple[Dict[str, float], List[dict], Optional[float], float]:
        """One stream → (phase seconds, merged segments, first-step t,
        last-event t).  Pure function of the sorted event list."""
        events = sorted(stream, key=lambda e: float(e.get("t", 0.0)))
        phases = {p: 0.0 for p in PHASES}
        segments: List[dict] = []
        first_step_t: Optional[float] = None
        state = None
        prev_t = None
        for e in events:
            ev = e["ev"]
            t = float(e.get("t", 0.0))
            if ev == "step" and first_step_t is None:
                first_step_t = t
            if prev_t is not None and state is not None and t > prev_t:
                # Override: the gap before a process_start is detection
                # + respawn regardless of how the previous incarnation
                # went away (SIGKILL leaves no terminal event).
                phase = (
                    "detect_respawn" if ev == "process_start" else state
                )
                dur = t - prev_t
                phases[phase] += dur
                if segments and segments[-1]["phase"] == phase:
                    segments[-1]["end"] = t
                    segments[-1]["dur"] += dur
                else:
                    segments.append(
                        {
                            "phase": phase,
                            "start": prev_t,
                            "end": t,
                            "dur": dur,
                        }
                    )
            new_state = _STATE_AFTER.get(ev)
            if new_state is not None:
                state = new_state
            prev_t = t
        last_t = prev_t if prev_t is not None else 0.0
        return phases, segments, first_step_t, last_t

    @staticmethod
    def _pct(
        phases: Dict[str, float],
        segments: List[dict],
        first_step_t: Optional[float],
        last_t: float,
    ) -> Optional[float]:
        """Productive share of the window starting at the first step."""
        if first_step_t is None or last_t <= first_step_t:
            return None
        window = last_t - first_step_t
        productive = sum(
            (
                min(s["end"], last_t) - max(s["start"], first_step_t)
                for s in segments
                if s["phase"] == "productive" and s["end"] > first_step_t
            ),
            0.0,
        )
        return 100.0 * max(0.0, min(productive / window, 1.0))

    def attribution(self) -> Dict[str, float]:
        """Aggregate phase seconds across worker streams."""
        return self.summary(detail=False)["phases"]

    def summary(self, detail: bool = True) -> Dict[str, Any]:
        with self._lock:
            streams = {k: list(v) for k, v in self._streams.items()}
            n_ingested = self.events_ingested
        total = {p: 0.0 for p in PHASES}
        ranks: Dict[str, Any] = {}
        agg_productive = 0.0
        agg_window = 0.0
        for (role, rank), stream in sorted(streams.items()):
            phases, segments, first_step_t, last_t = self._attribute(
                stream
            )
            pct = self._pct(phases, segments, first_step_t, last_t)
            entry: Dict[str, Any] = {
                "role": role,
                "rank": rank,
                "events": len(stream),
                "phases": {
                    p: round(v, 3) for p, v in phases.items() if v > 0
                },
                "goodput_pct": round(pct, 2) if pct is not None else None,
            }
            if detail:
                entry["segments"] = [
                    {
                        "phase": s["phase"],
                        "start": round(s["start"], 3),
                        "dur": round(s["dur"], 3),
                    }
                    for s in segments
                ]
            ranks[f"{role}{rank}"] = entry
            if role != "worker":
                continue
            for p, v in phases.items():
                total[p] += v
            if first_step_t is not None and last_t > first_step_t:
                window = last_t - first_step_t
                agg_window += window
                agg_productive += (pct or 0.0) / 100.0 * window
        goodput_pct = (
            round(100.0 * agg_productive / agg_window, 2)
            if agg_window > 0
            else None
        )
        return {
            "goodput_pct": goodput_pct,
            "window_s": round(agg_window, 3),
            "phases": {p: round(v, 3) for p, v in total.items()},
            "ranks": ranks,
            "events_ingested": n_ingested,
        }
