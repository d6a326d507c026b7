#!/usr/bin/env python
"""N-real-process sharded KvVariable benchmark (the PR's headline).

Spawns 1/2/4 genuine shard server processes (own GIL, own C++ store —
``python -m dlrover_tpu.kv_service``), drives remote gather batches
through :class:`ShardedKvClient` (cache off: every row crosses the
wire), and records per-shard-count:

* ``client_rows_per_s``      — wall-clock rows/s observed by this one
  client process.
* ``aggregate_rows_per_s``   — Σ per-shard service capacity
  (``served_rows / busy_seconds`` measured shard-side around the table
  op only).  **This is the headline scaling metric.**  On this CI
  container every process time-slices ONE core, so client wall-clock
  cannot scale past 1×; service capacity is what N dedicated hosts
  would serve, the same calibrated-proxy honesty contract as the blind
  TPU entries in perf_history.jsonl (docs/KV_SERVICE.md §Bench
  methodology).  Entries carry ``cores``/``colocated``/``aggregation``
  flags so nobody mistakes one for the other.
* gather latency histogram (client-observed p50/p90/p99 per batch).

``--reshard`` additionally runs the failover drill: seed under
``durability=apply``, SIGKILL one owner, respawn it from its delta
chain, and record recovery + membership-switch time and the lost-row
count versus a host-side oracle (must be zero).

Each run appends ``kind="kv"`` entries to perf_history.jsonl and writes
``KV_BENCH_DIST.json``; ``round_gate.py --kv`` fronts a small
configuration of this same harness.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from dlrover_tpu.kv_service import (  # noqa: E402
    KvReshardManager,
    ShardedKvClient,
)
from dlrover_tpu.telemetry import costmodel  # noqa: E402


def spawn_shard(name, dim, workdir, chain_dir=None, durability="none",
                save_every=64, seed=0, timeout=30.0):
    """Start one real shard process; returns (Popen, ready-info dict)."""
    ready = os.path.join(workdir, f"ready-{name}-{time.time_ns()}.json")
    cmd = [
        sys.executable, "-m", "dlrover_tpu.kv_service",
        "--name", name, "--dim", str(dim),
        "--ready-file", ready, "--seed", str(seed),
    ]
    if chain_dir:
        cmd += ["--chain-dir", chain_dir, "--durability", durability,
                "--save-every", str(save_every)]
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(cmd, cwd=_REPO, env=env)
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(ready):
            with open(ready) as f:
                info = json.load(f)
            return proc, info
        if proc.poll() is not None:
            raise RuntimeError(f"shard {name} died during startup")
        time.sleep(0.05)
    proc.kill()
    raise RuntimeError(f"shard {name} did not come up in {timeout}s")


def spawn_world(n, dim, workdir, **kw):
    procs, owners = {}, {}
    for i in range(n):
        name = f"kv-{i}"
        proc, info = spawn_shard(name, dim, workdir, **kw)
        procs[name] = proc
        owners[name] = f"127.0.0.1:{info['port']}"
    return procs, owners


def stop_world(procs):
    for p in procs.values():
        if p.poll() is None:
            p.terminate()
    for p in procs.values():
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


def bench_shard_count(n, dim, keyspace, batch, iters, workdir):
    """One shard-count point: remote gathers, capacity + latency."""
    procs, owners = spawn_world(n, dim, workdir)
    try:
        client = ShardedKvClient(owners, dim=dim, cache_rows=0)
        rng = np.random.RandomState(42)
        # Seed the keyspace (gather_or_init initializes shard-side) and
        # warm every channel before the timed window.
        seed_keys = np.arange(keyspace, dtype=np.int64)
        for off in range(0, keyspace, 65536):
            client.gather_or_init(seed_keys[off:off + 65536])
        client.shard_stats(reset_busy=True)

        latencies = []
        total_rows = 0
        t0 = time.perf_counter()
        for _ in range(iters):
            keys = rng.randint(0, keyspace, size=batch).astype(np.int64)
            bt = time.perf_counter()
            client.gather_or_init(keys)
            latencies.append(time.perf_counter() - bt)
            total_rows += batch
        wall = time.perf_counter() - t0

        stats = client.shard_stats()
        capacity = 0.0
        per_shard = {}
        for name, st in stats.items():
            busy = st.busy_s.get("gather", 0.0)
            rows = st.served_rows.get("gather", 0)
            rate = rows / busy if busy > 0 else 0.0
            capacity += rate
            per_shard[name] = {
                "rows": rows,
                "busy_s": round(busy, 6),
                "rows_per_s": round(rate, 1),
                "rpcs": st.rpcs.get("gather", 0),
            }
        lat = np.array(latencies)
        client.close()
        return {
            "shards": n,
            "batch": batch,
            "iters": iters,
            "keyspace": keyspace,
            "client_rows_per_s": round(total_rows / wall, 1),
            "aggregate_rows_per_s": round(capacity, 1),
            "per_shard": per_shard,
            "latency_ms": {
                "p50": round(float(np.percentile(lat, 50)) * 1e3, 3),
                "p90": round(float(np.percentile(lat, 90)) * 1e3, 3),
                "p99": round(float(np.percentile(lat, 99)) * 1e3, 3),
                "mean": round(float(lat.mean()) * 1e3, 3),
            },
        }
    finally:
        stop_world(procs)


def reshard_drill(dim, keyspace, workdir):
    """Kill-one-owner failover: chain restore + zero-lost-rows check."""
    chains = {f"kv-{i}": os.path.join(workdir, f"chain-{i}")
              for i in range(2)}
    procs, owners = {}, {}
    for i in range(2):
        name = f"kv-{i}"
        proc, info = spawn_shard(
            name, dim, workdir, chain_dir=chains[name],
            durability="apply",
        )
        procs[name] = proc
        owners[name] = f"127.0.0.1:{info['port']}"
    try:
        client = ShardedKvClient(owners, dim=dim, cache_rows=0)
        keys = np.arange(keyspace, dtype=np.int64)
        rng = np.random.RandomState(7)
        oracle = rng.randn(keyspace, dim).astype(np.float32)
        for off in range(0, keyspace, 4096):
            client.insert(keys[off:off + 4096], oracle[off:off + 4096])

        victim = "kv-0"
        procs[victim].kill()
        procs[victim].wait()
        t0 = time.perf_counter()
        proc, info = spawn_shard(
            victim, dim, workdir, chain_dir=chains[victim],
            durability="apply",
        )
        procs[victim] = proc
        mgr = KvReshardManager(client)
        summary = mgr.replace_shard(victim, f"127.0.0.1:{info['port']}")
        detect_to_serving_s = time.perf_counter() - t0

        lost = 0
        for off in range(0, keyspace, 4096):
            got, found = client.lookup(keys[off:off + 4096])
            sl = slice(off, off + len(got))
            bad = ~found | ~np.all(
                np.isclose(got, oracle[sl], atol=1e-6), axis=1
            )
            lost += int(bad.sum())
        client.close()
        return {
            "victim": victim,
            "restored_rows": summary["restored_rows"],
            "chain_length": summary["chain_length"],
            "recovery_s": round(summary["recovery_s"], 4),
            "switch_s": round(summary["switch_s"], 4),
            "detect_to_serving_s": round(detect_to_serving_s, 4),
            "lost_rows": lost,
        }
    finally:
        stop_world(procs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--keyspace", type=int, default=200_000)
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--shards", default="1,2,4",
                    help="comma-separated shard counts")
    ap.add_argument("--reshard", action="store_true",
                    help="also run the kill-one failover drill")
    ap.add_argument("--out", default="KV_BENCH_DIST.json")
    ap.add_argument("--no-ledger", action="store_true")
    args = ap.parse_args()

    cores = os.cpu_count() or 1
    workdir = tempfile.mkdtemp(prefix="kv_bench_dist_")
    result = {
        "bench": "kv_bench_dist",
        "dim": args.dim,
        "cores": cores,
        "colocated": True,
        "aggregation": "per_shard_service_capacity",
        "points": [],
    }
    try:
        for n in [int(s) for s in args.shards.split(",") if s]:
            point = bench_shard_count(
                n, args.dim, args.keyspace, args.batch, args.iters,
                workdir,
            )
            result["points"].append(point)
            print(json.dumps({
                "shards": n,
                "aggregate_rows_per_s": point["aggregate_rows_per_s"],
                "client_rows_per_s": point["client_rows_per_s"],
                "p50_ms": point["latency_ms"]["p50"],
            }), flush=True)

        by_n = {p["shards"]: p for p in result["points"]}
        if 1 in by_n:
            floor = by_n[1]["aggregate_rows_per_s"]
            result["floor_1shard_rows_per_s"] = floor
            for p in result["points"]:
                p["scaling_vs_1shard"] = round(
                    p["aggregate_rows_per_s"] / floor, 3
                ) if floor else 0.0

        if args.reshard:
            result["reshard"] = reshard_drill(
                args.dim, min(args.keyspace, 20_000), workdir
            )
            print(json.dumps({"reshard": result["reshard"]}), flush=True)

        if not args.no_ledger:
            for p in result["points"]:
                costmodel.append_ledger({
                    "kind": "kv",
                    "source": "kv_bench_dist",
                    "measured": True,
                    "cores": cores,
                    "colocated": True,
                    "aggregation": "per_shard_service_capacity",
                    "shards": p["shards"],
                    "dim": args.dim,
                    "batch": p["batch"],
                    "aggregate_rows_per_s": p["aggregate_rows_per_s"],
                    "client_rows_per_s": p["client_rows_per_s"],
                    "p50_ms": p["latency_ms"]["p50"],
                    "p99_ms": p["latency_ms"]["p99"],
                    "scaling_vs_1shard": p.get("scaling_vs_1shard"),
                })
            if args.reshard:
                costmodel.append_ledger({
                    "kind": "kv",
                    "source": "kv_bench_dist",
                    "measured": True,
                    "event": "reshard_drill",
                    "recovery_s": result["reshard"]["recovery_s"],
                    "detect_to_serving_s":
                        result["reshard"]["detect_to_serving_s"],
                    "lost_rows": result["reshard"]["lost_rows"],
                    "restored_rows": result["reshard"]["restored_rows"],
                })

        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps({
            "out": args.out,
            "points": len(result["points"]),
            "scaling_4v1": by_n.get(4, {}).get("scaling_vs_1shard"),
        }), flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    main()
