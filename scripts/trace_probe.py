"""Trace/SLO observability probe for the round gate (report-only).

Drives a sampled traffic burst through the paged gateway with head
sampling forced to 1.0, then answers the three questions the round
record asks of the tracing stack:

* did every request produce spans (count by span name)?
* does ``tracing.reconstruct`` rebuild a request's timeline in causal
  order (parents before children)?
* does the SLO engine produce a coherent ``/slo.json`` snapshot off the
  burst's metrics?

Prints one JSON line; ``ok`` means all three held.  Never touches a
chip — tiny CPU model, in-process LocalReplica.

Usage: python scripts/trace_probe.py [--requests 12] [--gen-budget 4]
"""

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Every request sampled: the probe asserts on spans, not on sampling
# statistics (tests/test_tracing.py owns the probabilistic behavior).
os.environ["DLROVER_TRACE_SAMPLE_RATE"] = "1.0"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def log(msg):
    print(f"[trace_probe] {msg}", file=sys.stderr, flush=True)


def causal(spans):
    """Parents must appear before their children in reconstruct order."""
    seen = set()
    for s in spans:
        parent = s.get("parent", "")
        if parent and any(
            parent == other.get("span") for other in spans
        ) and parent not in seen:
            return False
        seen.add(s.get("span"))
    return True


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--gen-budget", type=int, default=4)
    ap.add_argument("--timeout-s", type=float, default=120.0)
    args = ap.parse_args()

    from dlrover_tpu.serving.engine import PagedServingEngine
    from dlrover_tpu.serving.gateway import InferenceGateway, LocalReplica
    from dlrover_tpu.serving.worker import build_tiny_model
    from dlrover_tpu.telemetry import events as _events
    from dlrover_tpu.telemetry import slo as _slo
    from dlrover_tpu.telemetry import tracing as _tracing

    out = {"probe": "trace", "requests": args.requests, "ok": False}
    with tempfile.TemporaryDirectory(prefix="trace_probe_") as events_dir:
        _events.configure(directory=events_dir, role="gateway", rank=0)
        _tracing.clear_recent()
        model, params = build_tiny_model(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=2, num_kv_heads=2, max_seq_len=64,
            seed=0,
        )

        def factory():
            return LocalReplica(PagedServingEngine(
                model, params, slots=4, max_len=64, block_size=16,
                temperature=1e-6, seed=0,
            ), ticks_per_poll=4)

        # Short windows so the burst itself populates the frames.
        slo = _slo.SloEngine(
            windows=((2.0, 0.5, 1.5),), interval_s=0.05,
        )
        gw = InferenceGateway(
            factory, default_gen_budget=args.gen_budget, slo_engine=slo,
        )
        try:
            rng = np.random.RandomState(0)
            t0 = time.time()
            rids = [
                gw.submit(
                    [int(t) for t in rng.randint(1, 64, size=8)],
                    gen_budget=args.gen_budget,
                )["request_id"]
                for _ in range(args.requests)
            ]
            done = sum(
                1 for rid in rids
                if gw.get(rid, timeout_s=args.timeout_s).get("ok")
            )
            out["completed"] = done
            out["burst_s"] = round(time.time() - t0, 3)
        finally:
            gw.stop()

        spans = _tracing.recent_spans()
        counts = {}
        for s in spans:
            counts[s.get("name", "?")] = counts.get(s.get("name", "?"), 0) + 1
        out["span_total"] = len(spans)
        out["span_counts"] = dict(sorted(counts.items()))
        out["sampled_traces"] = len(_tracing.recent_trace_ids(limit=1000))

        # Reconstruct the richest trace and check causal order.
        by_trace = {}
        for s in spans:
            by_trace.setdefault(s.get("trace"), []).append(s)
        recon = {"found": False}
        if by_trace:
            tid = max(by_trace, key=lambda t: len(by_trace[t]))
            recon = _tracing.reconstruct(tid, events_dir=events_dir)
            recon = {
                "trace_id": tid,
                "found": recon["found"],
                "span_count": recon["span_count"],
                "causal": causal(recon["spans"]),
                "names": [s["name"] for s in recon["spans"]][:16],
            }
        out["reconstruction"] = recon

        slo.tick()
        snap = slo.snapshot()
        out["slo"] = {
            name: {
                "kind": s.get("kind"),
                "target": s.get("target"),
                "alerts": s.get("alerts"),
                "budget_remaining": (s.get("budget") or {}).get("remaining"),
            }
            for name, s in snap.get("slos", {}).items()
        }

        out["ok"] = bool(
            out["completed"] == args.requests
            and out["sampled_traces"] >= args.requests
            and recon.get("found")
            and recon.get("span_count", 0) >= 5
            and recon.get("causal")
            and len(out["slo"]) >= 4
        )

    log(f"completed={out.get('completed')} spans={out['span_total']} "
        f"traces={out['sampled_traces']} "
        f"recon_spans={recon.get('span_count')} causal={recon.get('causal')}")
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
