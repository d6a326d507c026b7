"""auto_accelerate: strategy search, and the planner on unannotated models.

Reference analog: ``atorch/examples/auto_accelerate/train.py`` (the
``--load_strategy`` / fully-automatic modes).  Two demos:

1. **Search** on the in-tree llama (logical-axis annotated): the engine
   enumerates mesh factorizations + strategy combos, analytically ranks
   them, dry-run MEASURES the top k, and returns the winner.
2. **Planner** on a plain flax transformer written with zero sharding
   annotations: the jaxpr planner traces the model, decides
   column/row/replicate per matmul from communication costs, and
   auto_accelerate trains it sharded — the analog of the reference's
   MIP tensor-parallel shard planner on a traced FX graph.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/auto_accelerate/train.py
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
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--measure-top-k", type=int, default=2)
    args = p.parse_args(argv)
    if args.smoke:
        args.steps, args.measure_top_k = 3, 1

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.auto.accelerate import auto_accelerate
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

    rng = np.random.RandomState(0)

    # ---- 1. strategy SEARCH on the annotated flagship -----------------
    cfg = LlamaConfig.tiny()
    ids = rng.randint(0, cfg.vocab_size, size=(8, cfg.max_seq_len + 1))
    lm_batch = {
        "input_ids": jnp.asarray(ids[:, :-1], jnp.int32),
        "labels": jnp.asarray(ids[:, 1:], jnp.int32),
    }
    ok, result, strategy = auto_accelerate(
        LlamaModel(cfg),
        optimizer=optax.adamw(1e-3),
        sample_batch=lm_batch,
        load_strategy=None,  # search
        measure_top_k=args.measure_top_k,
    )
    assert ok, f"search failed: {strategy}"
    print(f"searched strategy: {strategy.opt_names()}")
    state = result.state
    batch = result.shard_batch(lm_batch)
    for _ in range(args.steps):
        state, metrics = result.train_step(state, batch)
    print(f"llama loss after {args.steps} steps: {float(metrics['loss']):.3f}")

    # ---- 2. PLANNER on an unannotated plain-flax model ----------------
    class Plain(nn.Module):
        """No logical axes, no partitioning hints — nothing to hang a
        preset rule table on.  The planner derives the plan from the
        traced jaxpr instead."""

        hidden: int = 64
        vocab: int = 512

        @nn.compact
        def __call__(self, input_ids, labels=None):
            x = nn.Embed(self.vocab, self.hidden)(input_ids)
            for _ in range(2):
                h = nn.LayerNorm()(x)
                q = nn.Dense(self.hidden)(h)
                k = nn.Dense(self.hidden)(h)
                v = nn.Dense(self.hidden)(h)
                a = nn.softmax(
                    q @ k.swapaxes(-1, -2) / np.sqrt(self.hidden), axis=-1
                )
                x = x + nn.Dense(self.hidden)(a @ v)
                h = nn.LayerNorm()(x)
                x = x + nn.Dense(self.hidden)(
                    nn.gelu(nn.Dense(4 * self.hidden)(h))
                )
            return nn.Dense(self.vocab)(nn.LayerNorm()(x))

    pids = rng.randint(0, 512, size=(8, 16))
    plain_batch = {
        "input_ids": jnp.asarray(pids, jnp.int32),
        "labels": jnp.asarray(pids, jnp.int32),
    }

    def lm_loss(logits, batch):
        oh = jax.nn.one_hot(batch["labels"], logits.shape[-1])
        return -jnp.mean(
            jnp.sum(oh * jax.nn.log_softmax(logits, axis=-1), axis=-1)
        )

    ok, result, strategy = auto_accelerate(
        Plain(),
        optimizer=optax.adamw(1e-3),
        sample_batch=plain_batch,
        loss_fn=lm_loss,
        load_strategy=["fsdp", "tensor_parallel"],
    )
    assert ok, f"planner path failed: {strategy}"
    state = result.state
    sharded = result.shard_batch(plain_batch)
    for _ in range(args.steps):
        state, metrics = result.train_step(state, sharded)
    print(
        f"unannotated model trained sharded: loss="
        f"{float(metrics['loss']):.3f}"
    )
    # proof it actually sharded: at least one param is not fully
    # replicated across the mesh
    specs = {
        str(p): getattr(x, "sharding", None)
        for p, x in jax.tree_util.tree_flatten_with_path(state.params)[0]
    }
    partitioned = [
        k for k, s in specs.items()
        if s is not None and any(axis is not None for axis in s.spec)
    ]
    print(f"partitioned params: {len(partitioned)}/{len(specs)}")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
