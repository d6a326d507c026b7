"""Hello world: elastic training of a tiny MLP classifier.

The smallest complete product demo (reference analog:
``examples/pytorch/mnist/cnn_train.py``): a flax MLP on a synthetic
two-moons-style dataset, with

- **dynamic data sharding** when launched under ``tpurun`` (the master
  hands out record ranges; a restarted worker never re-reads finished
  shards) and a plain local loop when run standalone;
- **flash checkpointing** every step to shared memory plus periodic disk
  persists — kill the process mid-run and rerun to watch it resume.

Run it:

    python examples/mlp_elastic/train.py
    python -m dlrover_tpu.launch.elastic_run --nnodes 1 \
        examples/mlp_elastic/train.py
"""

import argparse
import os
import sys

sys.path.insert(
    0,
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
)

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training.train_state import TrainState

from dlrover_tpu.agent.master_client import build_master_client
from dlrover_tpu.agent.sharding.client import ShardingClient
from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType


class Mlp(nn.Module):
    hidden: int = 32

    @nn.compact
    def __call__(self, x):
        x = nn.tanh(nn.Dense(self.hidden)(x))
        x = nn.tanh(nn.Dense(self.hidden)(x))
        return nn.Dense(1)(x)[..., 0]


def make_dataset(n: int, seed: int = 0):
    """Two interleaved half-circles — learnable by a small MLP, not by a
    linear model, so falling loss proves the net is actually training."""
    rng = np.random.RandomState(seed)
    theta = rng.rand(n) * np.pi
    label = rng.randint(0, 2, size=n)
    r = 1.0 + rng.randn(n) * 0.08
    x = np.stack(
        [
            r * np.cos(theta + label * np.pi) + 0.5 * label,
            r * np.sin(theta + label * np.pi) - 0.25 * label,
        ],
        axis=1,
    ).astype(np.float32)
    return x, label.astype(np.float32)


@jax.jit
def train_step(state, x, y):
    def loss_fn(params):
        logits = state.apply_fn({"params": params}, x)
        return jnp.mean(
            jnp.maximum(logits, 0)
            - logits * y
            + jnp.log1p(jnp.exp(-jnp.abs(logits)))
        )

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    return state.apply_gradients(grads=grads), loss


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="tiny CI run")
    p.add_argument("--samples", type=int, default=4096)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--ckpt-dir", default="/tmp/dlrover_tpu_mlp_ckpt")
    args = p.parse_args(argv)
    if args.smoke:
        args.samples, args.epochs = 512, 2

    x_all, y_all = make_dataset(args.samples)
    model = Mlp()
    state = TrainState.create(
        apply_fn=model.apply,
        params=model.init(jax.random.key(0), x_all[:2])["params"],
        tx=optax.adam(3e-3),
    )

    # Under tpurun, DLROVER_MASTER_ADDR is set and the master shards the
    # dataset; a worker that dies and restarts resumes at the next
    # unfinished shard.  Standalone, iterate locally.
    client = build_master_client()
    ckpt = Checkpointer(args.ckpt_dir, start_saver=client is None)
    start_step, restored = ckpt.load_checkpoint(
        {"params": state.params, "opt_state": state.opt_state}
    )
    if start_step is not None:
        state = state.replace(
            params=restored["params"], opt_state=restored["opt_state"]
        )
        print(f"resumed from checkpointed step {start_step}")

    step = int(start_step or 0)
    last_loss = None

    def run_range(start, end):
        nonlocal state, step, last_loss
        for lo in range(start, end, args.batch_size):
            hi = min(lo + args.batch_size, end)
            state, loss = train_step(state, x_all[lo:hi], y_all[lo:hi])
            step += 1
            last_loss = float(loss)
            ckpt.save_checkpoint(
                step,
                {"params": state.params, "opt_state": state.opt_state},
                StorageType.DISK if step % 50 == 0 else StorageType.MEMORY,
            )

    if client is not None:
        sc = ShardingClient(
            dataset_name="mlp-moons",
            batch_size=args.batch_size,
            num_epochs=args.epochs,
            dataset_size=args.samples,
            master_client=client,
        )
        while True:
            shard = sc.fetch_shard()
            if shard is None:
                break
            run_range(shard.start, shard.end)
            sc.report_batch_done(shard.end - shard.start)
    else:
        for epoch in range(args.epochs):
            run_range(0, args.samples)
            print(f"epoch {epoch}: loss={last_loss:.4f} step={step}")

    logits = state.apply_fn({"params": state.params}, x_all)
    acc = float(np.mean((np.asarray(logits) > 0) == (y_all > 0.5)))
    # last_loss is None for a late-joining elastic worker that found all
    # shards already consumed — it trained nothing, which is fine.
    loss_str = "n/a" if last_loss is None else f"{last_loss:.4f}"
    print(f"final loss={loss_str} accuracy={acc:.3f} steps={step}")
    ckpt.wait_staging(timeout=30)
    ckpt.close()
    assert acc > 0.9, "MLP failed to learn the moons"
    return acc


if __name__ == "__main__":
    main()
