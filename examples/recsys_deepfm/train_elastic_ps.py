"""Elastic PS-style training: master-held shards + executor + failover.

Reference analog: the TF estimator examples
(``examples/tensorflow/criteo_deeprec``, ``iris``) whose elasticity
comes from `dlrover.trainer`'s estimator executor.  The TPU-native
shape: a job master hands out file-record shards (dynamic sharding, so
a restarted worker never re-reads finished work), ``PsTrainerExecutor``
drives the training loop with PS-cluster version polling, and the
embeddings live in the C++ KvVariable store.

This example runs the whole control plane IN PROCESS (LocalJobMaster),
like a single-node ``tpurun`` would; under K8s the same code runs
against the real master.

    python examples/recsys_deepfm/train_elastic_ps.py
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(
    0,
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
)

import numpy as np


def write_csv(path: str, n: int, seed: int = 0) -> str:
    """user,item,price,label rows with a learnable latent structure."""
    rng = np.random.RandomState(seed)
    su, si = rng.randn(24), rng.randn(40)
    with open(path, "w") as f:
        for _ in range(n):
            u, i = rng.randint(0, 24), rng.randint(0, 40)
            price = rng.rand()
            label = int(su[u] + si[i] > 0)
            f.write(f"{u},{i},{price:.4f},{label}\n")
    return path


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="tiny CI run")
    p.add_argument("--rows", type=int, default=2048)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=32)
    args = p.parse_args(argv)
    if args.smoke:
        args.rows, args.epochs = 256, 2

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.data.file_reader import FileReader
    from dlrover_tpu.master.local_master import LocalJobMaster
    from dlrover_tpu.native.kv_variable import (
        KvVariable,
        apply_gradients,
        embedding_lookup,
    )
    from dlrover_tpu.trainer.ps_trainer import PsTrainerExecutor

    csv = write_csv(
        os.path.join(tempfile.mkdtemp(prefix="elastic_ps_"), "train.csv"),
        args.rows,
    )
    schema = [
        ("user", "id"), ("item", "id"), ("price", "float"),
        ("label", "label"),
    ]
    reader = FileReader(csv, schema)

    master = LocalJobMaster(port=0, node_num=1)
    master.run(blocking=False)
    client = MasterClient(master.addr, 0, "worker")
    assert client.ready(10)

    dim = 8
    kv_user = KvVariable(dim=dim, slots=1, seed=1, init_scale=0.05)
    kv_item = KvVariable(dim=dim, slots=1, seed=2, init_scale=0.05)
    trng = np.random.RandomState(7)
    tower = {
        "w1": jnp.asarray(trng.randn(2 * dim + 1, 16) * 0.2, jnp.float32),
        "w2": jnp.asarray(trng.randn(16) * 0.2, jnp.float32),
    }

    @jax.jit
    def train_step(tower, uids, iids, price, labels):
        ue = embedding_lookup(kv_user, uids)
        ie = embedding_lookup(kv_item, iids)

        def loss_fn(tower, ue, ie):
            x = jnp.concatenate([ue, ie, price[:, None]], axis=-1)
            h = jnp.tanh(x @ tower["w1"])
            logits = h @ tower["w2"]
            return jnp.mean(
                jnp.maximum(logits, 0) - logits * labels
                + jnp.log1p(jnp.exp(-jnp.abs(logits)))
            )

        loss, (gt, gue, gie) = jax.value_and_grad(
            loss_fn, argnums=(0, 1, 2)
        )(tower, ue, ie)
        apply_gradients(kv_user, uids, gue, "adagrad", lr=0.2)
        apply_gradients(kv_item, iids, gie, "adagrad", lr=0.2)
        tower = jax.tree.map(lambda p, g: p - 0.2 * g, tower, gt)
        return tower, loss

    losses = []

    def train_fn(shard, ps_addrs):
        nonlocal tower
        # the master handed us [shard.start, shard.end) — a restarted
        # worker resumes at the next unfinished shard automatically
        for batch in reader.batches(shard.start, shard.end, 16):
            tower, loss = train_step(
                tower,
                jnp.asarray(batch["user"]),
                jnp.asarray(batch["item"]),
                jnp.asarray(batch["price"]),
                jnp.asarray(batch["label"]),
            )
            losses.append(float(loss))

    executor = PsTrainerExecutor(
        client,
        train_fn=train_fn,
        dataset_name="elastic-ps-demo",
        dataset_size=len(reader),
        batch_size=args.batch_size,
        num_epochs=args.epochs,
    )
    steps = executor.run()
    jax.effects_barrier()
    first, last = np.mean(losses[:4]), np.mean(losses[-4:])
    print(
        f"shards consumed to completion: {steps} steps, "
        f"loss {first:.4f} -> {last:.4f}, "
        f"tables user={len(kv_user)} item={len(kv_item)}"
    )
    reader.close()
    kv_user.close()
    kv_item.close()
    master.stop()
    assert last < 0.95 * first, "did not learn"
    return float(last)


if __name__ == "__main__":
    main()
