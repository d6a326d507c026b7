"""Long-context training: the sequence dimension sharded over `sp`.

Reference analog: atorch's two sequence-parallel paths — Ulysses
(``sequence_parallel_optimization.py``, all-to-all head swap) and
ring/blockwise exact attention (``distributed_transformer/
distributed_attention.py``).  Here both are ``attention_impl`` choices
behind one strategy entry: activations carry ``seq -> sp`` in the rule
table, and the ring path streams K/V blocks around the ``sp`` axis with
``ppermute`` + an online softmax (`parallel/ring_attention.py`) so
sequences longer than one chip's memory train exactly, no
approximation.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/long_context/train_ring.py --impl ring --sp 2
"""

import argparse
import os
import sys

sys.path.insert(
    0,
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
)

import numpy as np


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="tiny CI run")
    p.add_argument("--impl", choices=["ring", "ulysses"], default="ring")
    p.add_argument("--sp", type=int, default=2)
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--steps", type=int, default=20)
    args = p.parse_args(argv)
    if args.smoke:
        args.seq, args.steps = 64, 4

    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.auto import auto_accelerate
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig(
        vocab_size=2048,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        max_seq_len=args.seq,
        scan_layers=False,
        attention_impl="dot",  # the strategy swaps it
        dtype=jnp.float32,
    )
    n_dev = len(jax.devices())
    batch = max(n_dev // args.sp, 1) * 2  # divisible by the data extent
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, args.seq + 1))
    sample = {
        "input_ids": ids[:, :-1].astype(np.int32),
        "labels": ids[:, 1:].astype(np.int32),
    }

    ok, result, strategy = auto_accelerate(
        LlamaModel(cfg),
        optimizer=optax.adamw(1e-3),
        sample_batch=sample,
        load_strategy=[
            ("sequence_parallel", {"sp_size": args.sp, "impl": args.impl}),
        ],
    )
    assert ok, f"auto_accelerate failed: {strategy}"
    print(f"strategy={strategy.opt_names()} impl={args.impl} sp={args.sp}")

    # proof the activations are genuinely sequence-sharded: the sharded
    # batch's seq dim (dim 1) lives on sp
    sharded = result.shard_batch(sample)
    seq_axes = sharded["input_ids"].sharding.spec
    flat = [
        a for part in seq_axes[1:2]
        for a in (part if isinstance(part, tuple) else (part,))
    ]
    assert "sp" in flat, f"seq dim not on sp: {seq_axes}"
    print(f"batch sharding: {seq_axes}")

    state = result.state
    losses = []
    for _ in range(args.steps):
        state, metrics = result.train_step(state, sharded)
        losses.append(float(metrics["loss"]))
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0], "loss did not fall"
    return losses[-1]


if __name__ == "__main__":
    main()
