"""Mixture-of-Experts pretraining with expert parallelism.

Reference analog: atorch's MoE module + expert-parallel groups
(``atorch/modules/moe/moe_layer.py``).  Here the MoE decoder is the
llama family with ``num_experts``: top-k routing with load-balancing +
z losses, capacity-based dense dispatch, and the expert dimension
sharded over the ``ep`` mesh axis — XLA derives the token all-to-alls
from the rule table, no hand-written dispatch collectives.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/moe/pretrain_moe.py --ep 4 --fsdp 2
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
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--experts", type=int, default=4)
    p.add_argument("--topk", type=int, default=2)
    p.add_argument("--ep", type=int, default=4)
    p.add_argument("--fsdp", type=int, default=2)
    args = p.parse_args(argv)
    if args.smoke:
        args.seq, args.steps = 32, 4

    import jax
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
        num_experts=args.experts,
        num_experts_per_token=args.topk,
        scan_layers=False,
        attention_impl="dot",
    )
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(args.batch, args.seq + 1))
    batch = {
        "input_ids": ids[:, :-1].astype(np.int32),
        "labels": ids[:, 1:].astype(np.int32),
    }

    ok, result, strategy = auto_accelerate(
        LlamaModel(cfg),
        optimizer=optax.adamw(1e-3),
        sample_batch=batch,
        load_strategy=[
            ("expert_parallel", {"ep_size": args.ep}),
            ("fsdp", {"fsdp_size": args.fsdp}),
        ],
    )
    assert ok, f"auto_accelerate failed: {strategy}"
    print(f"strategy={strategy.opt_names()} mesh ep={args.ep} fsdp={args.fsdp}")

    # proof the experts are genuinely sharded over ep (the expert dim is
    # the leading axis of every moe_mlp kernel)
    expert_sharded = [
        jax.tree_util.keystr(path)
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            result.state.params
        )[0]
        if "moe_mlp" in jax.tree_util.keystr(path)
        and any(
            a == "ep" or (isinstance(a, tuple) and "ep" in a)
            for a in getattr(leaf.sharding, "spec", [])
        )
    ]
    print(f"expert tensors sharded over ep: {len(expert_sharded)}")

    state = result.state
    sharded = result.shard_batch(batch)
    losses = []
    for _ in range(args.steps):
        state, metrics = result.train_step(state, sharded)
        losses.append(float(metrics["loss"]))
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} (includes aux+z)")
    assert losses[-1] < losses[0], "MoE loss did not fall"
    assert expert_sharded, "no expert tensor landed on the ep axis"
    return losses[-1]


if __name__ == "__main__":
    main()
