"""DeepFM-style sparse CTR training on the C++ KvVariable store.

Reference analog: ``examples/tensorflow/deepfm_tf/`` + the tfplus
KvVariable op surface.  The TPU-native shape of the sparse product:

- embeddings live in the host-side C++ KvVariable (lock-striped hash
  table, gather-or-init, freq/age eviction, hot/cold tiers) — unbounded
  vocab, no dense [vocab, dim] tensor anywhere;
- the jitted step gathers rows via the io_callback bridge — including
  a variable-length tag bag combined with the sparse-bag lookup ops
  (``native/embedding_ops.py``) — runs the FM (2nd-order
  interactions) + deep tower on device, and sparse-applies Adagrad
  back into the tables;
- the table checkpoints incrementally (full + delta chains);
- under ``tpurun`` the master's dynamic sharding hands out file ranges
  (see ``tests/test_ps_file_reader.py`` for that full flow).

    python examples/recsys_deepfm/train.py
"""

import argparse
import os
import sys

sys.path.insert(
    0,
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
)

import numpy as np


def synth_ctr(n, n_users=200, n_items=500, n_tags=50, seed=0):
    """Clicks driven by latent user/item affinities, a price effect, and
    a variable-length tag bag (1-3 tags per example, padded with -1) —
    learnable signal for the FM term, the deep tower, AND the sparse-bag
    lookup."""
    rng = np.random.RandomState(seed)
    u_lat = rng.randn(n_users, 4) * 0.7
    i_lat = rng.randn(n_items, 4) * 0.7
    t_eff = rng.randn(n_tags) * 0.8
    users = rng.randint(0, n_users, size=n)
    items = rng.randint(0, n_items, size=n)
    price = rng.rand(n).astype(np.float32)
    tags = rng.randint(0, n_tags, size=(n, 3)).astype(np.int64)
    n_valid = rng.randint(1, 4, size=n)  # ragged bags
    tags[np.arange(3)[None, :] >= n_valid[:, None]] = -1
    tag_mean = np.where(tags >= 0, t_eff[np.clip(tags, 0, None)], 0.0)
    tag_mean = tag_mean.sum(-1) / n_valid
    logit = (
        (u_lat[users] * i_lat[items]).sum(-1)
        - 1.2 * (price - 0.5)
        + tag_mean
    )
    clicks = (logit + rng.randn(n) * 0.3 > 0).astype(np.float32)
    return users.astype(np.int64), items.astype(np.int64), price, tags, clicks


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="tiny CI run")
    p.add_argument("--samples", type=int, default=8192)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--dim", type=int, default=8)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--ckpt-dir", default="")
    args = p.parse_args(argv)
    if args.smoke:
        args.samples, args.epochs = 1024, 2

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.native.embedding_ops import (
        apply_gradients_masked,
        embedding_lookup_masked,
    )
    from dlrover_tpu.native.kv_variable import (
        KvVariable,
        apply_gradients,
        embedding_lookup,
    )

    if args.samples < args.batch_size:
        raise SystemExit(
            f"--samples ({args.samples}) must be >= --batch-size "
            f"({args.batch_size}): the jitted step is compiled for one "
            "static batch size and ragged tails are dropped"
        )
    users, items, price, tags, clicks = synth_ctr(args.samples)
    dim = args.dim
    kv_user = KvVariable(dim=dim, slots=1, seed=1, init_scale=0.05)
    kv_item = KvVariable(dim=dim, slots=1, seed=2, init_scale=0.05)
    kv_tag = KvVariable(dim=dim, slots=1, seed=3, init_scale=0.05)
    batch = args.batch_size

    trng = np.random.RandomState(7)
    tower = {
        "w1": jnp.asarray(trng.randn(3 * dim + 1, 32) * 0.2, jnp.float32),
        "b1": jnp.zeros((32,), jnp.float32),
        "w2": jnp.asarray(trng.randn(32) * 0.2, jnp.float32),
    }
    # one flat (nnz,) id stream + segment ids for the tag bags
    tag_seg = jnp.asarray(np.repeat(np.arange(batch), 3), jnp.int32)

    @jax.jit
    def train_step(tower, uids, iids, tag_flat, price, labels):
        ue = embedding_lookup(kv_user, uids)
        ie = embedding_lookup(kv_item, iids)
        # sparse-bag feature: mean of each example's 1-3 tag rows
        # (padding -1 never touches the table).  Rows stay the
        # differentiable leaf so cotangents can be sparse-applied.
        te_rows, tvalid = embedding_lookup_masked(kv_tag, tag_flat)

        def loss_fn(tower, ue, ie, te_rows):
            w = tvalid.astype(jnp.float32)
            tsum = jax.ops.segment_sum(te_rows * w[:, None], tag_seg, batch)
            tcnt = jax.ops.segment_sum(w, tag_seg, batch)
            tbag = tsum / jnp.maximum(tcnt, 1e-12)[:, None]
            # FM second-order term: <u, i> interaction
            fm = jnp.sum(ue * ie, axis=-1)
            # deep tower over the concatenated features
            x = jnp.concatenate([ue, ie, tbag, price[:, None]], axis=-1)
            h = jnp.tanh(x @ tower["w1"] + tower["b1"])
            logits = fm + h @ tower["w2"]
            return jnp.mean(
                jnp.maximum(logits, 0)
                - logits * labels
                + jnp.log1p(jnp.exp(-jnp.abs(logits)))
            )

        loss, (gt, gue, gie, gte) = jax.value_and_grad(
            loss_fn, argnums=(0, 1, 2, 3)
        )(tower, ue, ie, te_rows)
        # sparse apply: only the touched rows update, host-side
        apply_gradients(kv_user, uids, gue, "adagrad", lr=0.15)
        apply_gradients(kv_item, iids, gie, "adagrad", lr=0.15)
        # masked: the -1 padding entries must not become table rows
        apply_gradients_masked(kv_tag, tag_flat, gte, "adagrad", lr=0.15)
        tower = jax.tree.map(lambda p, g: p - 0.15 * g, tower, gt)
        return tower, loss

    losses = []
    for epoch in range(args.epochs):
        order = np.random.RandomState(epoch).permutation(args.samples)
        # drop a ragged tail: the jitted step (and the tag segment
        # map) is compiled for one static batch size
        for lo in range(0, args.samples - batch + 1, batch):
            sel = order[lo : lo + batch]
            tower, loss = train_step(
                tower,
                jnp.asarray(users[sel]),
                jnp.asarray(items[sel]),
                jnp.asarray(tags[sel].reshape(-1)),
                jnp.asarray(price[sel]),
                jnp.asarray(clicks[sel]),
            )
            losses.append(float(loss))
        print(
            f"epoch {epoch}: loss {np.mean(losses[-8:]):.4f} "
            f"(table rows: user={len(kv_user)} item={len(kv_item)})"
        )
    jax.effects_barrier()
    assert np.mean(losses[-8:]) < 0.95 * np.mean(losses[:8]), "did not learn"

    if args.ckpt_dir:
        from dlrover_tpu.checkpoint.kv_checkpoint import KvCheckpointManager

        for name, table in (
            ("user", kv_user), ("item", kv_item), ("tag", kv_tag)
        ):
            mgr = KvCheckpointManager(
                table, os.path.join(args.ckpt_dir, name), full_interval=10
            )
            mgr.save(step=1)
        print(f"kv checkpoint chains (user+item+tag) written under {args.ckpt_dir}")

    out = float(np.mean(losses[-8:]))
    kv_user.close()
    kv_item.close()
    kv_tag.close()
    return out


if __name__ == "__main__":
    main()
