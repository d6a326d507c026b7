"""What-if capacity planner behind ``python -m dlrover_tpu.brain plan``.

Prices a *proposed* fleet — replica count, standby pool, chip
generation — against the traffic the warehouse actually recorded, in
the same currency the doctor prices incidents: servput points.  The
per-replica capacity comes from the newest measured serve record when
one exists (the gateway's own tokens/s) and falls back to the
calibrated roofline (``predict_serving_tokens_per_sec``) for chip
generations never benched.  The replay drill then runs the recorded
trace through the proposed fleet both reactively and predictively and
reports the points each policy loses to ``queue_wait``.

The agentic rung (arXiv 2606.15994): every plan carries a drafted
config diff — the ``TrainingArguments``/fleet knobs to change, as
"-/+" lines — which the doctor attaches to incident reports so the
operator reviews a change, not a dashboard.
"""

from typing import Any, Dict, List, Optional

from dlrover_tpu.telemetry import costmodel

from .forecast import fit_traffic
from .replay import predictive_vs_reactive, ramp_start

# Roofline defaults when no serve record pins the capacity: a 1B-class
# decode at the serve-bench shape.
_DEFAULT_N_PARAMS = 1_000_000_000
_DEFAULT_PROMPT = 1024
_DEFAULT_GEN = 64
_DEFAULT_SLOTS = 8


def replica_capacity(
    warehouse: Optional[Any] = None,
    chip_gen: str = "v5e",
    n_params: int = _DEFAULT_N_PARAMS,
    repo: Optional[str] = None,
) -> Dict[str, Any]:
    """Tokens/s one replica sustains: measured serve record first,
    calibrated roofline otherwise."""
    if warehouse is not None:
        try:
            rows = warehouse.serve_trend(limit=1000)
        except Exception:
            rows = []
        for row in reversed(rows):
            rate = row.get("tokens_per_sec")
            if isinstance(rate, (int, float)) and rate > 0:
                return {
                    "tokens_per_sec": float(rate),
                    "source": "serve_record",
                    "measured": bool(row.get("measured")),
                    "record_t": row.get("t"),
                }
    pred = costmodel.predict_serving_tokens_per_sec(
        n_params=n_params, prompt_tokens=_DEFAULT_PROMPT,
        gen_tokens=_DEFAULT_GEN, slots=_DEFAULT_SLOTS,
        backend=chip_gen, repo=repo,
    )
    return {
        "tokens_per_sec": float(pred["predicted_tokens_per_sec"]),
        "source": "roofline",
        "measured": False,
        "mfu_used": pred["mfu_used"],
        "calibration_source": pred["calibration_source"],
    }


def plan_capacity(
    warehouse: Any,
    *,
    replicas: int,
    standbys: int,
    chip_gen: str = "v5e",
    job_uid: str = "",
    n_params: int = _DEFAULT_N_PARAMS,
    lead_s: float = 30.0,
    period_s: float = 3600.0,
    n_bins: int = 60,
    repo: Optional[str] = None,
    autoscaler_factory: Optional[Any] = None,
) -> Dict[str, Any]:
    """The what-if plan: proposed fleet × recorded traffic → pricing.

    Returns a JSON-able dict; ``render_plan_markdown`` turns it into
    the human report and ``draft_config_diff`` output rides along for
    the doctor.
    """
    replicas = max(1, int(replicas))
    standbys = max(0, int(standbys))
    traffic = list(warehouse.records(job_uid=job_uid, kind="traffic",
                                     limit=5000))
    cap = replica_capacity(warehouse, chip_gen=chip_gen,
                           n_params=n_params, repo=repo)
    per_replica = cap["tokens_per_sec"]
    fleet_capacity = per_replica * replicas

    rates = []
    for rec in traffic:
        p = rec.get("payload") or {}
        r = p.get("tokens_per_sec")
        if isinstance(r, (int, float)):
            rates.append(float(r))
    peak = max(rates) if rates else 0.0
    mean = sum(rates) / len(rates) if rates else 0.0

    drill = None
    if traffic and per_replica > 0:
        if autoscaler_factory is None:
            from dlrover_tpu.serving.fleet import FleetAutoscaler

            def autoscaler_factory():
                return FleetAutoscaler(
                    min_replicas=1, max_replicas=replicas,
                    tokens_per_replica=max(per_replica, 1.0),
                    up_dwell_s=0.0, down_dwell_s=60.0,
                    cooldown_s=0.0,
                )
        drill = predictive_vs_reactive(
            traffic, autoscaler_factory,
            period_s=period_s, n_bins=n_bins, lead_s=lead_s,
            capacity_tokens_per_s=per_replica,
            standbys=standbys, initial_live=1,
        )

    headroom = (
        (fleet_capacity - peak) / fleet_capacity
        if fleet_capacity > 0 else None
    )
    if not rates:
        verdict = "no_traffic"
    elif peak > fleet_capacity:
        verdict = "under_provisioned"
    elif headroom is not None and headroom > 0.5 and replicas > 1:
        verdict = "over_provisioned"
    else:
        verdict = "fits"

    proposed = {
        "max_replicas": replicas,
        "standby_target": standbys,
        "chip_gen": chip_gen,
    }
    plan = {
        "schema_version": 1,
        "proposed": proposed,
        "capacity": {
            "per_replica_tokens_per_sec": round(per_replica, 2),
            "fleet_tokens_per_sec": round(fleet_capacity, 2),
            "source": cap["source"],
            "measured": cap.get("measured", False),
        },
        "traffic": {
            "windows": len(rates),
            "mean_tokens_per_sec": round(mean, 2),
            "peak_tokens_per_sec": round(peak, 2),
            "ramp_start_t": ramp_start(traffic) if traffic else None,
        },
        "headroom_pct": (
            round(100.0 * headroom, 1) if headroom is not None else None
        ),
        "verdict": verdict,
        "drill": drill,
    }
    plan["config_draft"] = draft_config_diff(
        current={"max_replicas": 1, "standby_target": 0,
                 "chip_gen": "v5e"},
        proposed=proposed,
        reason=f"capacity plan verdict: {verdict}",
    )
    return plan


def draft_config_diff(
    current: Dict[str, Any],
    proposed: Dict[str, Any],
    reason: str = "",
    title: str = "fleet",
) -> Dict[str, Any]:
    """The drafted config change: "-/+" lines over the knob dicts.

    Only knobs that actually change produce lines; knobs present in
    one side only show as pure additions/removals.  The dict shape
    (``title``/``reason``/``lines``/``current``/``proposed``) is what
    the doctor renders under "Drafted config change".
    """
    lines: List[str] = []
    keys = sorted(set(current) | set(proposed))
    for k in keys:
        cur, new = current.get(k), proposed.get(k)
        if cur == new:
            continue
        if k in current:
            lines.append(f"- {k} = {cur!r}")
        if k in proposed:
            lines.append(f"+ {k} = {new!r}")
    return {
        "title": title,
        "reason": reason,
        "lines": lines,
        "current": dict(current),
        "proposed": dict(proposed),
    }


def _fmt(v: Any, nd: int = 1) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.{nd}f}"
    return str(v)


def render_plan_markdown(plan: Dict[str, Any]) -> str:
    """The ``brain plan`` report."""
    p = plan.get("proposed", {})
    cap = plan.get("capacity", {})
    tr = plan.get("traffic", {})
    out = [
        "# Capacity plan",
        "",
        f"Proposed fleet: **{p.get('max_replicas')} replicas / "
        f"{p.get('standby_target')} standbys** on "
        f"`{p.get('chip_gen')}`.",
        "",
        "## Capacity",
        "",
        "| Metric | Value |",
        "|---|---|",
        f"| Per-replica tokens/s | "
        f"{_fmt(cap.get('per_replica_tokens_per_sec'))} |",
        f"| Fleet tokens/s | "
        f"{_fmt(cap.get('fleet_tokens_per_sec'))} |",
        f"| Capacity source | {cap.get('source', '—')}"
        f"{' (measured)' if cap.get('measured') else ''} |",
        "",
        "## Recorded traffic",
        "",
        "| Metric | Value |",
        "|---|---|",
        f"| Windows | {tr.get('windows', 0)} |",
        f"| Mean tokens/s | {_fmt(tr.get('mean_tokens_per_sec'))} |",
        f"| Peak tokens/s | {_fmt(tr.get('peak_tokens_per_sec'))} |",
        f"| Headroom | {_fmt(plan.get('headroom_pct'))}% |",
        "",
        f"**Verdict: `{plan.get('verdict')}`**",
    ]
    drill = plan.get("drill")
    if drill:
        out += [
            "",
            "## Replay pricing (servput points)",
            "",
            "| Policy | Servput % | Lost to queue_wait |",
            "|---|---|---|",
        ]
        for mode in ("reactive", "predictive"):
            d = drill.get(mode) or {}
            out.append(
                f"| {mode} | {_fmt(d.get('servput_pct'))} | "
                f"{_fmt(d.get('lost_points'))} |"
            )
        out.append("")
        out.append(
            f"Predictive pre-warm saves "
            f"**{_fmt(drill.get('points_saved'))} servput points**"
            + (
                " and grows before the recorded ramp."
                if drill.get("prewarmed_before_ramp")
                else "."
            )
        )
    draft = plan.get("config_draft")
    if draft and draft.get("lines"):
        out += ["", "## Drafted config change", ""]
        if draft.get("reason"):
            out.append(f"_{draft['reason']}_")
            out.append("")
        out.append("```diff")
        out.extend(draft["lines"])
        out.append("```")
    out.append("")
    return "\n".join(out)
