"""KvVariable sparse-path scale benchmark.

Reference scale intent: ``tfplus/kv_variable/kernels/hashmap.h:1-1030``
(the libcuckoo-backed store is sized for 1e7-1e9 rows).  This measures the
C++ store (``native/kv_store/kv_variable.cc``) at 10M rows x dim 64:

- bulk insert (gather_or_init on fresh keys) rows/s;
- random-batch gather rows/s + effective GB/s;
- sparse Adam apply rows/s (read-modify-write of emb + m + v);
- hot/cold tiering under zipf churn: spill count/rate, cold->hot
  promote-on-access gather, post-churn eviction;
- the full JAX io_callback round trip (device program -> host gather ->
  host adam apply) steps/s at a training-like batch.

Row-layout design assumptions being validated (kv_variable.cc:1-23):
per-row contiguous [emb|m|v] keeps one cache-line-friendly allocation per
row so apply_adam's 3x traffic stays ~1/3 the gather rate, and 64-way
lock striping keeps single-thread overhead negligible (this image has 1
core — striping cost shows up as pure overhead here, contention wins
need multi-core).

Usage: python scripts/kv_bench.py [--rows 10000000] [--dim 64]
Writes KV_BENCH.json and prints one JSON line.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg):
    print(f"[kv_bench +{time.time() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.time()


def bench_insert(kv, rows, dim, chunk=1_000_000, reserve=True):
    rng = np.random.RandomState(0)
    if reserve:
        kv.reserve(rows)  # pre-size: skips the rehash cascade (kv_reserve)
    t0 = time.perf_counter()
    for lo in range(0, rows, chunk):
        n = min(chunk, rows - lo)
        keys = np.arange(lo, lo + n, dtype=np.int64)
        kv.import_rows(
            keys,
            rng.randn(n, (1 + kv.slots) * dim).astype(np.float32) * 0.01,
        )
        log(f"  inserted {lo + n:,}/{rows:,}")
    dt = time.perf_counter() - t0
    return rows / dt


def bench_gather(kv, rows, dim, batch=65536, iters=50):
    rng = np.random.RandomState(1)
    batches = [
        rng.randint(0, rows, size=batch).astype(np.int64)
        for _ in range(iters)
    ]
    t0 = time.perf_counter()
    for keys in batches:
        kv.gather_or_init(keys)
    dt = time.perf_counter() - t0
    rows_s = batch * iters / dt
    return rows_s, rows_s * dim * 4 / 1e9


def bench_adam(kv, rows, dim, batch=65536, iters=20):
    rng = np.random.RandomState(2)
    batches = [
        (rng.randint(0, rows, size=batch).astype(np.int64),
         rng.randn(batch, dim).astype(np.float32))
        for _ in range(iters)
    ]
    t0 = time.perf_counter()
    for keys, grads in batches:
        kv.apply_adam(keys, grads, lr=1e-3)
    dt = time.perf_counter() - t0
    return batch * iters / dt


def bench_tiering(kv, rows, dim, tmpdir):
    """Zipf churn: hot head keeps being touched, tail spills cold; then a
    cold batch is gathered (promote-on-access) and the tail evicted."""
    rng = np.random.RandomState(3)
    # mark a 1% head hot via real lookups (freq >= 2)
    head = rng.randint(0, rows // 100, size=200_000).astype(np.int64)
    kv.gather_or_init(head)
    kv.gather_or_init(head)

    path = os.path.join(tmpdir, "kv_cold.bin")
    kv.enable_cold_tier(path, hot_min_freq=2)
    t0 = time.perf_counter()
    spilled = kv.spill_cold()
    spill_dt = time.perf_counter() - t0

    # promote-on-access: gather purely-cold keys vs hot keys
    cold_keys = np.unique(
        rng.randint(rows // 2, rows, size=65536).astype(np.int64)
    )
    t0 = time.perf_counter()
    kv.gather_or_init(cold_keys)
    cold_gather_s = len(cold_keys) / (time.perf_counter() - t0)
    hot_keys = np.unique(head)[:len(cold_keys)]
    t0 = time.perf_counter()
    kv.gather_or_init(hot_keys)
    hot_gather_s = len(hot_keys) / (time.perf_counter() - t0)

    t0 = time.perf_counter()
    evicted = kv.evict_below_frequency(2)
    evict_dt = time.perf_counter() - t0
    return {
        "spilled_rows": int(spilled),
        "spill_rows_per_s": round(spilled / max(spill_dt, 1e-9)),
        "cold_promote_gather_rows_per_s": round(cold_gather_s),
        "hot_gather_rows_per_s": round(hot_gather_s),
        "evicted_rows": int(evicted),
        "evict_rows_per_s": round(evicted / max(evict_dt, 1e-9)),
        "cold_file_mb": round(os.path.getsize(path) / 2**20, 1),
    }


def bench_io_callback(kv, rows, dim, batch=8192, iters=30):
    """Training-shaped round trip: jitted program whose embedding lookup
    and sparse apply run on host via io_callback."""
    import jax
    import jax.numpy as jnp

    # Host-side bench: CPU regardless of the ambient platform.
    jax.config.update("jax_platforms", "cpu")

    from dlrover_tpu.native.kv_variable import (
        apply_gradients,
        embedding_lookup,
    )

    def step(keys, target):
        emb = embedding_lookup(kv, keys)
        loss = jnp.mean((jnp.sum(emb, -1) - target) ** 2)
        grad = jax.grad(
            lambda e: jnp.mean((jnp.sum(e, -1) - target) ** 2)
        )(emb)
        apply_gradients(kv, keys, grad, optimizer="adam")
        return loss

    jitted = jax.jit(step)
    rng = np.random.RandomState(4)
    keys = jnp.asarray(rng.randint(0, rows, size=batch).astype(np.int64))
    target = jnp.asarray(rng.randn(batch).astype(np.float32))
    float(jitted(keys, target))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = jitted(keys, target)
    float(loss)
    dt = time.perf_counter() - t0
    return iters / dt, batch * iters / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--out", default="KV_BENCH.json")
    ap.add_argument("--no-reserve", action="store_true",
                    help="measure the unreserved rehash-cascade insert")
    ap.add_argument("--insert-only", action="store_true")
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from dlrover_tpu.native.kv_variable import KvVariable

    import tempfile

    tmpdir = tempfile.mkdtemp(prefix="kv_bench_")
    kv = KvVariable(dim=args.dim, slots=2, init_scale=0.01)

    log(f"insert {args.rows:,} rows x dim {args.dim} (emb+m+v, "
        f"reserve={not args.no_reserve})")
    insert_s = bench_insert(kv, args.rows, args.dim,
                            reserve=not args.no_reserve)
    log(f"insert {insert_s:,.0f} rows/s; table size {len(kv):,}")
    if args.insert_only:
        print(json.dumps({"metric": "kv_insert_rows_per_s",
                          "value": round(insert_s),
                          "reserve": not args.no_reserve}), flush=True)
        return

    gather_s, gather_gb = bench_gather(kv, args.rows, args.dim)
    log(f"gather {gather_s:,.0f} rows/s ({gather_gb:.2f} GB/s)")

    adam_s = bench_adam(kv, args.rows, args.dim)
    log(f"apply_adam {adam_s:,.0f} rows/s")

    tier = bench_tiering(kv, args.rows, args.dim, tmpdir)
    log(f"tiering: {tier}")

    steps_s, rt_rows_s = bench_io_callback(kv, args.rows, args.dim)
    log(f"io_callback round trip {steps_s:.1f} steps/s "
        f"({rt_rows_s:,.0f} rows/s)")

    result = {
        "metric": "kv_gather_rows_per_s",
        "value": round(gather_s),
        "unit": "rows/s",
        "rows": args.rows,
        "dim": args.dim,
        "slots": 2,
        "insert_rows_per_s": round(insert_s),
        "gather_gb_per_s": round(gather_gb, 2),
        "adam_apply_rows_per_s": round(adam_s),
        "io_callback_steps_per_s": round(steps_s, 1),
        "io_callback_rows_per_s": round(rt_rows_s),
        **{f"tier_{k}": v for k, v in tier.items()},
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)

    # Every run lands in the perf ledger (kind "kv") so single-node KV
    # regressions surface like step-perf ones; `bench.py probe_kv`
    # fronts the history.
    from dlrover_tpu.telemetry import costmodel

    costmodel.append_ledger({
        "kind": "kv",
        "source": "kv_bench",
        "measured": True,
        "rows": args.rows,
        "dim": args.dim,
        "gather_rows_per_s": round(gather_s),
        "insert_rows_per_s": round(insert_s),
        "adam_apply_rows_per_s": round(adam_s),
        "io_callback_rows_per_s": round(rt_rows_s),
    })


if __name__ == "__main__":
    main()
