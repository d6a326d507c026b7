"""Serving bench: legacy slot-pool engine vs paged+chunked gateway.

The inference-tier twin of kv_bench: one mixed prompt-length workload
(lognormal, the mean-1k mixture of ``bench.py probe_packed`` scaled to
the CPU harness model, plus a shared system-prompt prefix fraction)
generated twice — once through the legacy ``ContinuousBatchingEngine``
(rl/serving.py: every prefill pads to the full ``max_prompt`` width,
cache memory is ``slots * max_len`` regardless of actual lengths) and
once through the ``InferenceGateway`` over ``PagedServingEngine``
(block-granular chunked prefill, hash-consed prefix cache, paged pool).
Both runs use greedy decoding on the same model/params, so the paged
engine's speedup is pure scheduling + cache economics, not different
math.

Timing protocol: pass 1 runs the full workload on both engines to warm
the jit caches (the ``_build_*_fns`` builders are lru_cached per trace
shape, so fresh pass-2 engines hit them); pass 2 re-runs on fresh
engines and is the timed measurement.  Acceptance (ISSUE PR 13): the
gateway clears >= 2x generated-tokens/s vs legacy at this mixture.

The default workload is the production mixture scaled ~1/18 to the
harness model: lognormal mean-1k prompts against a 16k-class context
window becomes mean-32 against a 576-token window, with 80% of
requests opening with a shared 64-token system prompt.  The window —
``--max-prompt`` — is the service's *advertised* limit, not the
observed p100: the legacy engine must provision (and pad every prefill
to) the worst admissible prompt, which is exactly the cost the paged
cache exists to avoid.

Results go to SERVE_BENCH.json and perf_history.jsonl (kind="serve"),
including the calibrated *blind* TPU serving prediction from
``costmodel.predict_serving_tokens_per_sec`` for the flagship bench
config — the number a TPU round can reconcile against.

Usage: python scripts/serve_bench.py [--requests 64] [--mean-prompt 32]
           [--gen-budget 4] [--out SERVE_BENCH.json] [--no-ledger]
"""

import argparse
import json
import math
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def log(msg):
    print(f"[serve_bench] {msg}", file=sys.stderr, flush=True)


def build_workload(args):
    """Prompt list: lognormal lengths (the probe_packed mean-1k shape
    scaled by ``mean_prompt/1024``), a ``prefix_frac`` share opening
    with the same system-prompt tokens."""
    rng = np.random.RandomState(args.seed)
    mu = math.log(args.mean_prompt) - args.sigma ** 2 / 2.0
    prefix = [
        int(t) for t in rng.randint(1, args.vocab, size=args.prefix_len)
    ]
    prompts = []
    for i in range(args.requests):
        n = int(rng.lognormal(mu, args.sigma))
        n = max(8, min(n, args.max_prompt))
        body = [int(t) for t in rng.randint(1, args.vocab, size=n)]
        if rng.rand() < args.prefix_frac:
            prompts.append((prefix + body)[: args.max_prompt])
        else:
            prompts.append(body)
    return prompts


def run_legacy(model, params, prompts, args):
    from dlrover_tpu.rl.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(
        model, params,
        slots=args.slots,
        max_len=args.max_prompt + args.gen_budget + 8,
        max_prompt=args.max_prompt,
        temperature=1e-6,
        seed=args.seed,
    )
    t0 = time.time()
    done = eng.generate(prompts, gen_budget=args.gen_budget,
                        timeout_s=args.timeout_s)
    wall = time.time() - t0
    gen = sum(len(c.tokens) - c.prompt_len for c in done.values())
    return {"wall_s": wall, "generated_tokens": gen,
            "tokens_per_sec": gen / wall if wall > 0 else 0.0,
            "completions": len(done)}


def run_gateway(model, params, prompts, args):
    from dlrover_tpu.serving.engine import PagedServingEngine
    from dlrover_tpu.serving.gateway import InferenceGateway, LocalReplica

    engines = []

    def factory():
        eng = PagedServingEngine(
            model, params,
            slots=args.slots,
            max_len=args.max_prompt + args.gen_budget + 8,
            block_size=args.block_size,
            chunk_size=args.chunk_size or None,
            temperature=1e-6,
            seed=args.seed,
        )
        engines.append(eng)
        return LocalReplica(eng, ticks_per_poll=4)

    gw = InferenceGateway(factory, max_queue_tokens=10 ** 9,
                          default_gen_budget=args.gen_budget)
    t0 = time.time()
    rids = [
        gw.submit(p, gen_budget=args.gen_budget)["request_id"]
        for p in prompts
    ]
    gen = 0
    for rid, prompt in zip(rids, prompts):
        res = gw.get(rid, timeout_s=args.timeout_s)
        if not res.get("ok"):
            raise RuntimeError(f"request {rid} failed: {res}")
        gen += len(res["tokens"]) - len(prompt)
    wall = time.time() - t0
    servz = gw.servz()
    stats = engines[-1].stats() if engines else {}
    gw.stop()
    return {
        "wall_s": wall,
        "generated_tokens": gen,
        "tokens_per_sec": gen / wall if wall > 0 else 0.0,
        "completions": len(rids),
        "servput_pct": servz["servput"].get("servput_pct"),
        "servput_phases_pct": servz["servput"].get("pct"),
        "kv_occupancy_ratio": stats.get("occupancy_ratio"),
        "kv_blocks_total": stats.get("blocks_total"),
        "prefix_hits": stats.get("prefix_hits"),
        "prefix_hit_tokens": stats.get("prefix_hit_tokens"),
        "prefill_tokens": stats.get("prefill_tokens"),
        "preemptions": stats.get("preemptions"),
    }


def tpu_prediction():
    """Blind calibrated serving prediction for the flagship bench model
    (the config bench.py measures training throughput on)."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.telemetry import costmodel

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=768, intermediate_size=2048,
        num_layers=12, num_heads=12, num_kv_heads=12, max_seq_len=2048,
    )
    shapes = jax.eval_shape(
        LlamaModel(cfg).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32),
    )
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)
    )
    head_dim = cfg.hidden_size // cfg.num_heads
    kv_bytes = 2 * cfg.num_layers * cfg.num_kv_heads * head_dim * 2
    pred = costmodel.predict_serving_tokens_per_sec(
        n_params, prompt_tokens=1024, gen_tokens=128, slots=8,
        backend="v5e", kv_bytes_per_token=float(kv_bytes), repo=REPO,
    )
    pred["n_params"] = n_params
    return pred


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--mean-prompt", type=int, default=32,
                    help="lognormal mean (the mean-1k mixture scaled "
                         "to the harness model)")
    ap.add_argument("--sigma", type=float, default=1.0)
    ap.add_argument("--max-prompt", type=int, default=576,
                    help="advertised context window both engines must "
                         "provision for (legacy pads every prefill to "
                         "this width)")
    ap.add_argument("--prefix-frac", type=float, default=0.8)
    ap.add_argument("--prefix-len", type=int, default=64)
    ap.add_argument("--gen-budget", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--chunk-size", type=int, default=96,
                    help="prefill chunk width (0 = block size)")
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=192)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--heads", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout-s", type=float, default=600.0)
    ap.add_argument("--out", default=os.path.join(REPO, "SERVE_BENCH.json"))
    ap.add_argument("--no-ledger", action="store_true")
    args = ap.parse_args()

    import jax

    from dlrover_tpu.serving.worker import build_tiny_model
    from dlrover_tpu.telemetry import costmodel

    backend = jax.default_backend()
    blind = backend != "tpu"
    model, params = build_tiny_model(
        vocab_size=args.vocab, hidden_size=args.hidden,
        intermediate_size=2 * args.hidden, num_layers=args.layers,
        num_heads=args.heads, num_kv_heads=args.heads,
        max_seq_len=args.max_prompt + args.gen_budget + 8,
        seed=args.seed,
    )
    prompts = build_workload(args)
    log(f"workload: {len(prompts)} prompts, "
        f"lens p50={int(np.median([len(p) for p in prompts]))} "
        f"max={max(len(p) for p in prompts)}, "
        f"gen_budget={args.gen_budget}")

    log("pass 1 (jit warmup): legacy")
    run_legacy(model, params, prompts, args)
    log("pass 1 (jit warmup): gateway")
    run_gateway(model, params, prompts, args)

    log("pass 2 (timed): legacy")
    legacy = run_legacy(model, params, prompts, args)
    log(f"legacy: {legacy['tokens_per_sec']:.1f} tok/s "
        f"({legacy['wall_s']:.2f}s)")
    log("pass 2 (timed): gateway")
    gateway = run_gateway(model, params, prompts, args)
    log(f"gateway: {gateway['tokens_per_sec']:.1f} tok/s "
        f"({gateway['wall_s']:.2f}s), "
        f"servput={gateway['servput_pct']}%, "
        f"prefix_hit_tokens={gateway['prefix_hit_tokens']}")

    speedup = (
        gateway["tokens_per_sec"] / legacy["tokens_per_sec"]
        if legacy["tokens_per_sec"] > 0 else 0.0
    )
    pred = tpu_prediction()
    payload = {
        "bench": "serve_bench",
        "backend": backend,
        "blind": blind,
        "requests": len(prompts),
        "mean_prompt": args.mean_prompt,
        "sigma": args.sigma,
        "prefix_frac": args.prefix_frac,
        "gen_budget": args.gen_budget,
        "slots": args.slots,
        "block_size": args.block_size,
        "legacy": legacy,
        "gateway": gateway,
        "speedup_vs_legacy": round(speedup, 3),
        "ok": speedup >= 2.0,
        "tpu_prediction": pred,
        "unix": round(time.time(), 1),
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    log(f"wrote {args.out}")

    if not args.no_ledger:
        costmodel.append_ledger({
            "kind": "serve",
            "source": "serve_bench",
            "measured": True,       # CPU wall-clock, both engines
            "blind": blind,         # not a TPU number
            "backend": backend,
            "requests": len(prompts),
            "mean_prompt": args.mean_prompt,
            "gen_budget": args.gen_budget,
            "slots": args.slots,
            "tokens_per_sec": round(gateway["tokens_per_sec"], 2),
            "gateway_tokens_per_sec": round(gateway["tokens_per_sec"], 2),
            "legacy_tokens_per_sec": round(legacy["tokens_per_sec"], 2),
            "speedup_vs_legacy": round(speedup, 3),
            "servput_pct": gateway["servput_pct"],
            "kv_occupancy_ratio": gateway["kv_occupancy_ratio"],
            "prefix_hit_tokens": gateway["prefix_hit_tokens"],
            "predicted_tokens_per_sec":
                round(pred["predicted_tokens_per_sec"], 1),
            "predicted_ttft_s": pred["ttft_s"],
            "predicted_tpot_s": pred["tpot_s"],
            "calibration_source": pred["calibration_source"],
        })
        log("appended kind=serve ledger entry")

    print(json.dumps(payload), flush=True)
    return 0 if payload["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
