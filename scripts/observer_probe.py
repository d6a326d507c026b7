"""Fleet-observer probe (report-only).

Stands up a miniature fleet — two fake worker telemetry endpoints with
known metric values, a fake serve gateway (scripted ``/generate`` +
``/healthz``), and a real kv shard when the kv service imports — then
points an :class:`ObserverDaemon` at it and answers the four questions
an operator asks of the observability plane:

* does federation reproduce the hand-merged oracle (counters summed,
  fleet p99 from merged cumulative buckets)?
* do the black-box canaries go green against a healthy fleet?
* when the gateway starts shedding while ``/healthz`` still reads
  ready, does the canary burn produce a ``canary_divergence`` verdict?
* do ``/fleetz.json`` and the ``top`` renderer serve the result?

Prints one JSON line; ``ok`` means all four held.  Never touches a
chip — scripted HTTP sources, loopback only, no model, no jax compute.

Usage: python scripts/observer_probe.py [--baseline-ticks 3]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg):
    print(f"[observer_probe] {msg}", file=sys.stderr, flush=True)


def _worker_registry(n_req, lat_values):
    from dlrover_tpu.telemetry.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("probe_requests_total", "requests").inc(n_req, result="ok")
    h = reg.histogram(
        "probe_lat_seconds", "latency", buckets=(0.1, 0.5, 1.0, 5.0)
    )
    for v in lat_values:
        h.observe(v)
    return reg


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline-ticks", type=int, default=3)
    args = ap.parse_args()

    from dlrover_tpu.observer.daemon import ObserverDaemon
    from dlrover_tpu.observer.dashboard import render_top
    from dlrover_tpu.observer.federation import ScrapeClient
    from dlrover_tpu.telemetry.httpd import TelemetryHTTPServer
    from dlrover_tpu.telemetry.metrics import (
        quantile_from_cumulative,
    )

    out = {"probe": "observer", "ok": False}
    state = {"mode": "ok"}

    def generate(prompt, budget, timeout):
        if state["mode"] == "shed":
            return {"ok": False, "shed": True, "reason": "queue_full"}
        return {"ok": True, "tokens": [1], "trace_id": "t-probe"}

    # Two workers with known values: the federation oracle is computable
    # by hand.
    w_lat = ([0.05, 0.3, 0.7], [0.2, 2.0])
    servers = []
    kv = None
    try:
        for i, vals in enumerate(w_lat):
            s = TelemetryHTTPServer(
                registry=_worker_registry(3 + i, vals),
                port=0, role="worker", uid=f"w{i}",
            )
            servers.append((s, s.start()))
        gw_http = TelemetryHTTPServer(
            port=0, role="serve", uid="probe-gw",
            serve_sources={
                "generate": generate,
                "healthz": lambda: {"ready": True},
            },
        )
        servers.append((gw_http, gw_http.start()))
        gw_addr = servers[-1][1]

        kv_endpoints = []
        try:
            from dlrover_tpu.kv_service.server import KvShardServer

            kv = KvShardServer(
                "probe-kv", dim=8, http_port=0, canary_keys=4
            ).start()
            kv_endpoints = [f"127.0.0.1:{kv.http_port}"]
        except Exception as e:  # noqa: BLE001 — kv tier is optional here
            log(f"kv shard unavailable, probing without it: {e}")
        out["kv_tier"] = bool(kv_endpoints)

        daemon = ObserverDaemon(
            endpoints=[addr for _, addr in servers[:2]],
            serve_endpoint=gw_addr,
            kv_endpoints=kv_endpoints,
            client=ScrapeClient(timeout_s=5.0, retries=0),
            canary_deadline_s=2.0,
            job_uid=f"obs-probe-{os.getpid()}",
        )
        obs_http = None
        try:
            t0 = time.time()
            probes_ok = True
            for i in range(max(1, args.baseline_ticks)):
                tick = daemon.tick(t0 + 10.0 * i)
                probes_ok = probes_ok and all(
                    p["ok"] for p in tick["probes"]
                )
            out["baseline_probes_ok"] = probes_ok
            out["scraped"] = tick["scraped"]
            out["whitebox_green"] = daemon.whitebox_green()

            # Federation vs hand-merged oracle.
            counters = daemon.registry.counters()
            total = sum(
                counters.get("probe_requests_total", {}).values()
            )
            out["counter_sum"] = total
            counter_ok = total == float(3 + 4)
            combined = sorted(w_lat[0] + w_lat[1])
            uppers, cum, n, _ = daemon.registry.histogram_fleet(
                "probe_lat_seconds"
            )
            p50 = quantile_from_cumulative(uppers, cum, n, 0.5)
            # Oracle: hand-merge the two workers' observations into one
            # cumulative curve on the shared bucket axis.
            o_uppers = (0.1, 0.5, 1.0, 5.0)
            o_cum = tuple(
                float(sum(1 for v in combined if v <= u))
                for u in o_uppers
            )
            oracle_p50 = quantile_from_cumulative(
                o_uppers, o_cum, float(len(combined)), 0.5
            )
            out["fleet_p50"] = p50
            out["oracle_p50"] = oracle_p50
            hist_ok = n == len(combined) and p50 == oracle_p50

            # Incident: shed while healthz stays green -> divergence.
            state["mode"] = "shed"
            for i in range(3):
                daemon.tick(t0 + 100.0 + 10.0 * i)
            div = [
                e for e in daemon.events
                if e["action"] == "canary_divergence"
            ]
            out["divergence_verdicts"] = len(div)
            out["serve_canary"] = daemon.serve_canary.status()

            # Serving surface: /fleetz.json over HTTP + top renderer.
            obs_http = TelemetryHTTPServer(
                port=0, role="observer", uid="obs-probe",
                serve_sources=daemon.http_sources(),
            )
            obs_addr = obs_http.start()
            import urllib.request

            with urllib.request.urlopen(
                f"http://{obs_addr}/fleetz.json", timeout=10
            ) as resp:
                fleetz = json.loads(resp.read().decode())
            out["fleetz_sources"] = len(fleetz.get("sources", []))
            top = render_top(fleetz, clear=False)
            out["top_renders"] = "fleet observer" in top

            out["ok"] = bool(
                probes_ok
                and out["whitebox_green"]
                and counter_ok
                and hist_ok
                and div
                and out["fleetz_sources"] >= 3
                and out["top_renders"]
            )
        finally:
            if obs_http is not None:
                obs_http.stop()
            daemon.stop()
    finally:
        for s, _ in servers:
            s.stop()
        if kv is not None:
            kv.stop()

    log(f"probes_ok={out.get('baseline_probes_ok')} "
        f"counter_sum={out.get('counter_sum')} "
        f"fleet_p50={out.get('fleet_p50')} "
        f"divergence={out.get('divergence_verdicts')} "
        f"sources={out.get('fleetz_sources')}")
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
