"""Continuous-batching generation serving — the vLLM-backend analog demo.

Requests of different prompt lengths and budgets arrive STAGGERED (some
submitted only after others are mid-decode); the slot pool absorbs them
with no batch barrier: finished requests free their slot immediately and
the next queued request prefills into it while the rest keep decoding.

What it asserts (the demo's own learning signal):
  * every request completes with exactly its generation budget;
  * more requests complete than there are slots (turnover happened);
  * the total tick count is far below serial decode (batching happened);
  * greedy output for the first request is identical whether it ran
    alone or amid the staggered traffic (isolation).

Run: JAX_PLATFORMS=cpu python examples/rlhf/serve_continuous.py --smoke
Reference analog: atorch's vLLM generation backend
(``atorch/atorch/rl/model_engine/vllm_backend.py:49``), re-designed as a
static-shape TPU slot pool (``dlrover_tpu/rl/serving.py``).
"""

import argparse
import os
import sys
import time

sys.path.insert(
    0,
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="tiny CI run")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--requests", type=int, default=10)
    p.add_argument("--gen-budget", type=int, default=12)
    args = p.parse_args(argv)
    if args.smoke:
        args.requests, args.gen_budget = 6, 6

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.rl.serving import ContinuousBatchingEngine

    cfg = LlamaConfig.tiny(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32, scan_layers=False,
        attention_impl="dot",
    )
    model = LlamaModel(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    def make_engine():
        return ContinuousBatchingEngine(
            model, params, slots=args.slots, max_len=48, max_prompt=12,
            temperature=1e-6,  # greedy: deterministic, assertable
        )

    rng = np.random.RandomState(0)
    prompts = [
        list(rng.randint(1, 128, size=3 + i % 5))
        for i in range(args.requests)
    ]

    # Reference: request 0 decoded alone.
    ref = make_engine().generate([prompts[0]], args.gen_budget)
    solo_tokens = next(iter(ref.values())).tokens

    # Staggered arrival: half the requests submit up front, the rest
    # join one per tick while earlier ones are mid-decode.
    engine = make_engine()
    t0 = time.time()
    first = args.requests // 2
    ids = [engine.submit(p, args.gen_budget) for p in prompts[:first]]
    done = []
    late = iter(prompts[first:])
    while len(done) < args.requests:
        nxt = next(late, None)
        if nxt is not None:
            ids.append(engine.submit(nxt, args.gen_budget))
        done.extend(engine.step())
    dt = time.time() - t0

    by_id = {c.request_id: c for c in done}
    assert sorted(by_id) == sorted(ids)
    for c in done:
        assert len(c.tokens) - c.prompt_len == args.gen_budget, c
    assert by_id[ids[0]].tokens == solo_tokens, (
        "request 0 diverged when sharing the pool"
    )
    assert args.requests > args.slots  # turnover genuinely exercised
    serial_ticks = args.requests * args.gen_budget
    assert engine.ticks < serial_ticks
    tok_s = engine.generated_tokens / max(dt, 1e-9)
    print(
        f"{args.requests} requests through {args.slots} slots: "
        f"{engine.ticks} ticks (serial would be {serial_ticks}), "
        f"{engine.generated_tokens} tokens, {tok_s:,.0f} tok/s, "
        f"solo-vs-shared outputs identical"
    )


if __name__ == "__main__":
    main()
