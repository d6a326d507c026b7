"""Fleet report: render the telemetry warehouse as markdown + JSON.

Consumed by ``python -m dlrover_tpu.brain report``.  The report answers the three questions an operator
asks of fleet history: how is goodput/MFU trending, what keeps going
wrong (incident frequency by trigger), and is it the same hardware every
time (straggler repeat offenders).
"""

import json
import time
from typing import Any, Dict, List

from dlrover_tpu.brain.warehouse import TelemetryWarehouse


def build_report(warehouse: TelemetryWarehouse) -> Dict[str, Any]:
    return warehouse.fleet_report()


def _fmt(v, nd: int = 1) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.{nd}f}"
    return str(v)


def _goodput_section(jobs: Dict[str, Any], lines: List[str]):
    lines.append("## Goodput trend")
    lines.append("")
    lines.append("| job | runs | last goodput % | avg goodput % | "
                 "incidents |")
    lines.append("|---|---|---|---|---|")
    for job_uid, job in sorted(jobs.items()):
        runs = job.get("runs", [])
        avgs = [r["goodput_avg"] for r in runs
                if r.get("goodput_avg") is not None]
        avg = sum(avgs) / len(avgs) if avgs else None
        n_inc = sum(job.get("incidents", {}).values())
        lines.append(
            f"| {job_uid} | {len(runs)} | {_fmt(job.get('goodput_last'))} "
            f"| {_fmt(avg)} | {n_inc} |"
        )
    lines.append("")


def _perf_section(perf: List[dict], lines: List[str]):
    lines.append("## Perf / MFU trend")
    lines.append("")
    if not perf:
        lines.append("(no perf history)")
        lines.append("")
        return
    lines.append("| round | source | backend | tokens/s | MFU | blind |")
    lines.append("|---|---|---|---|---|---|")
    for p in perf[-25:]:
        lines.append(
            f"| {p.get('round') or '—'} | {p.get('source') or '—'} "
            f"| {p.get('backend') or '—'} "
            f"| {_fmt(p.get('tokens_per_sec'), 0)} "
            f"| {_fmt(p.get('mfu'), 3)} "
            f"| {'yes' if p.get('blind') else 'no'} |"
        )
    lines.append("")


def _kv_section(kv: List[dict], lines: List[str]):
    lines.append("## Embedding traffic (kv service)")
    lines.append("")
    if not kv:
        lines.append("(no kv bench history)")
        lines.append("")
        return
    lines.append("| source | shards | rows/s | scaling | note |")
    lines.append("|---|---|---|---|---|")
    for p in kv[-25:]:
        if p.get("event") == "reshard_drill":
            note = (
                f"reshard drill: recovery {_fmt(p.get('recovery_s'), 3)}s, "
                f"lost rows {p.get('lost_rows', '?')}"
            )
            lines.append(
                f"| {p.get('source') or '—'} | — | — | — | {note} |"
            )
            continue
        lines.append(
            f"| {p.get('source') or '—'} | {p.get('shards') or '—'} "
            f"| {_fmt(p.get('rows_per_s'), 0)} "
            f"| {_fmt(p.get('scaling_vs_1shard'), 2)} | |"
        )
    lines.append("")


def _hot_key_section(hot: List[dict], lines: List[str]):
    lines.append("## Hot keys (per-shard skew)")
    lines.append("")
    if not hot:
        lines.append("(no hot-key history)")
        lines.append("")
        return
    lines.append("| owner | rows | skew | hottest keys |")
    lines.append("|---|---|---|---|")
    for p in hot[-25:]:
        top = ", ".join(
            f"{k}×{n}" for k, n in (p.get("top") or [])[:4]
        ) or "—"
        lines.append(
            f"| {p.get('owner') or '—'} "
            f"| {p.get('rows') if p.get('rows') is not None else '—'} "
            f"| {_fmt(p.get('hot_key_skew'), 3)} | {top} |"
        )
    lines.append("")


def _serve_section(serve: List[dict], lines: List[str]):
    lines.append("## Serving traffic (inference gateway)")
    lines.append("")
    if not serve:
        lines.append("(no serving bench history)")
        lines.append("")
        return
    lines.append("| source | tokens/s | vs legacy | servput % | "
                 "TTFT s | TPOT s | blind |")
    lines.append("|---|---|---|---|---|---|---|")
    for p in serve[-25:]:
        lines.append(
            f"| {p.get('source') or '—'} "
            f"| {_fmt(p.get('tokens_per_sec'), 1)} "
            f"| {_fmt(p.get('speedup_vs_legacy'), 2)} "
            f"| {_fmt(p.get('servput_pct'), 1)} "
            f"| {_fmt(p.get('ttft_s'), 3)} "
            f"| {_fmt(p.get('tpot_s'), 4)} "
            f"| {'yes' if p.get('blind') else 'no'} |"
        )
    lines.append("")


def _traffic_section(traffic: List[dict], lines: List[str]):
    lines.append("## Traffic shape (gateway arrivals)")
    lines.append("")
    if not traffic:
        lines.append("(no recorded traffic windows)")
        lines.append("")
        return
    rates = [t["tokens_per_sec"] for t in traffic
             if t.get("tokens_per_sec") is not None]
    if rates:
        lines.append(
            f"{len(traffic)} windows · mean "
            f"{_fmt(sum(rates) / len(rates))} tokens/s · peak "
            f"{_fmt(max(rates))} tokens/s"
        )
        lines.append("")
    lines.append("| source | requests | tokens | window s | tokens/s |")
    lines.append("|---|---|---|---|---|")
    for p in traffic[-25:]:
        lines.append(
            f"| {p.get('source') or '—'} "
            f"| {p.get('requests') if p.get('requests') is not None else '—'} "
            f"| {_fmt(p.get('tokens'), 0)} "
            f"| {_fmt(p.get('window_s'), 1)} "
            f"| {_fmt(p.get('tokens_per_sec'), 1)} |"
        )
    lines.append("")


def _slo_section(slo: List[dict], lines: List[str]):
    lines.append("## SLO error budgets")
    lines.append("")
    if not slo:
        lines.append("(no SLO history)")
        lines.append("")
        return
    lines.append("| run | budget remaining | tightest SLO | burn alert |")
    lines.append("|---|---|---|---|")
    for p in slo[-25:]:
        lines.append(
            f"| {p.get('run') or '—'} "
            f"| {_fmt(p.get('budget_remaining'), 3)} "
            f"| {p.get('tightest_slo') or '—'} "
            f"| {p.get('alert') or '—'} |"
        )
    lines.append("")


def _observer_section(fleet: List[dict], lines: List[str]):
    lines.append("## Fleet observer")
    lines.append("")
    if not fleet:
        lines.append("(no fleet snapshots)")
        lines.append("")
        return
    lines.append(
        "| run | live sources | canary fail/probes | burning "
        "| anomalies | correlated | divergences |"
    )
    lines.append("|---|---|---|---|---|---|---|")
    for p in fleet[-25:]:
        burning = ", ".join(p.get("slo_burning") or []) or "—"
        lines.append(
            f"| {p.get('run') or '—'} "
            f"| {_fmt(p.get('live_sources'), 0)} "
            f"| {p.get('canary_failures', 0)}"
            f"/{p.get('canary_probes', 0)} "
            f"| {burning} "
            f"| {p.get('anomalies', 0)} "
            f"| {p.get('correlated', 0)} "
            f"| {p.get('divergences', 0)} |"
        )
    lines.append("")


def _incident_section(freq: Dict[str, int], lines: List[str]):
    lines.append("## Incident frequency by trigger")
    lines.append("")
    if not freq:
        lines.append("(no incidents on record)")
        lines.append("")
        return
    lines.append("| trigger | count |")
    lines.append("|---|---|")
    for trigger, count in freq.items():
        lines.append(f"| {trigger} | {count} |")
    lines.append("")


def _offender_section(offenders: Dict[str, int], lines: List[str]):
    lines.append("## Straggler repeat offenders")
    lines.append("")
    if not offenders:
        lines.append("(no straggler history)")
        lines.append("")
        return
    lines.append("| node | incidents |")
    lines.append("|---|---|")
    for node, count in offenders.items():
        lines.append(f"| {node} | {count} |")
    lines.append("")


def render_markdown(report: Dict[str, Any]) -> str:
    jobs = report.get("jobs", {})
    n_records = sum(
        len(j.get("goodput_trend", [])) for j in jobs.values()
    )
    lines = [
        "# Fleet report — telemetry warehouse",
        "",
        f"- db: `{report.get('db', '?')}` "
        f"(schema v{report.get('schema_version', '?')})",
        f"- generated: "
        f"{time.strftime('%Y-%m-%d %H:%M:%S', time.gmtime(report.get('generated_at', 0)))}Z",
        f"- jobs: {len(jobs)} · goodput intervals shown: {n_records} "
        f"· perf entries: {len(report.get('perf_trend', []))} "
        f"· kv entries: {len(report.get('kv_trend', []))} "
        f"· serve entries: {len(report.get('serve_trend', []))}",
        "",
    ]
    _goodput_section(jobs, lines)
    _perf_section(report.get("perf_trend", []), lines)
    _kv_section(report.get("kv_trend", []), lines)
    _hot_key_section(report.get("kv_hot_keys", []), lines)
    _serve_section(report.get("serve_trend", []), lines)
    _traffic_section(report.get("traffic_trend", []), lines)
    _slo_section(report.get("slo_trend", []), lines)
    _observer_section(report.get("observer_trend", []), lines)
    _incident_section(report.get("incident_frequency", {}), lines)
    _offender_section(report.get("straggler_offenders", {}), lines)
    return "\n".join(lines) + "\n"


def render_json(report: Dict[str, Any]) -> str:
    return json.dumps(report, indent=2, sort_keys=True, default=str)
