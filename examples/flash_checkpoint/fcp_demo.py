"""Flash Checkpoint demo: what "0.2 s saves" means in practice.

Reference analog: ``examples/pytorch/fcp_demo.py``.  Trains a small
model and times three save flavors on your machine:

- MEMORY (async): snapshot to host shm, drain in a background thread —
  the per-step cost is dispatch only; this is what lets the product
  checkpoint EVERY step;
- DISK (async): same snapshot, the drain also persists + commits with a
  ``.done`` barrier;
- DISK (block=True): the synchronous save other frameworks make you pay.

Then it kills the "process" state and restores from the freshest copy
(shm first, disk fallback) — the recovery path the benchmark's
`mistral7b.preempt` cell measures under a real SIGKILL.

    python examples/flash_checkpoint/fcp_demo.py
"""

import argparse
import os
import sys
import time

sys.path.insert(
    0,
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
)

import numpy as np


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="tiny CI run")
    p.add_argument("--hidden", type=int, default=512)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--ckpt-dir", default="/tmp/dlrover_tpu_fcp_demo")
    args = p.parse_args(argv)
    if args.smoke:
        args.hidden, args.layers, args.steps = 128, 2, 2

    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.checkpoint import Checkpointer, StorageType
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.trainer.step import (
        create_sharded_state,
        data_sharding,
        make_train_step,
    )

    cfg = LlamaConfig(
        vocab_size=8192,
        hidden_size=args.hidden,
        intermediate_size=args.hidden * 8 // 3,
        num_layers=args.layers,
        num_heads=max(args.hidden // 64, 1),
        num_kv_heads=max(args.hidden // 64, 1),
        max_seq_len=128,
        scan_layers=False,
        attention_impl="dot",
    )
    model = LlamaModel(cfg)
    mesh = build_mesh(MeshConfig(dp=-1), jax.devices())
    rules = PRESET_RULES["dp"]
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(8, 129))
    batch = jax.device_put(
        {
            "input_ids": jnp.asarray(ids[:, :-1], jnp.int32),
            "labels": jnp.asarray(ids[:, 1:], jnp.int32),
        },
        data_sharding(mesh, rules),
    )
    state, shardings = create_sharded_state(
        model, optax.adamw(1e-3), mesh, rules, jax.random.key(0), batch
    )
    step_fn = make_train_step(model, mesh, rules, shardings)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(state.params)
    )
    print(f"model: {n_params:,} params")

    def view(s):
        return {"params": s.params, "opt_state": s.opt_state, "step": s.step}

    ckpt = Checkpointer(args.ckpt_dir, start_saver=True)
    ckpt.warmup(view(state))  # compile the snapshot path off the clock

    save_seq = [0]

    def timed(label, **kw):
        save_seq[0] += 1
        t0 = time.perf_counter()
        ok = ckpt.save_checkpoint(
            int(state.step) + save_seq[0], view(state), **kw
        )
        dt = time.perf_counter() - t0
        print(f"  {label:<22} blocking cost {dt * 1e3:8.1f} ms (ok={ok})")
        ckpt.wait_staging(timeout=120)  # settle before the next flavor

    for i in range(args.steps):
        state, metrics = step_fn(state, batch)
    print(f"trained to step {int(state.step)}, loss={float(metrics['loss']):.3f}")

    print("save flavors:")
    timed("MEMORY (async)", storage_type=StorageType.MEMORY)
    timed("DISK (async)", storage_type=StorageType.DISK)
    timed("DISK (blocking)", storage_type=StorageType.DISK, block=True)
    assert ckpt.wait(timeout=120)

    # -- recovery: fresh process state, restore from the freshest copy --
    fresh, _ = create_sharded_state(
        model, optax.adamw(1e-3), mesh, rules, jax.random.key(9), batch
    )
    t0 = time.perf_counter()
    got_step, restored = ckpt.load_checkpoint(view(fresh), view(shardings))
    dt = time.perf_counter() - t0
    print(f"restore: step {got_step} in {dt * 1e3:.1f} ms")
    assert got_step is not None
    np.testing.assert_array_equal(
        np.asarray(restored["step"]), np.asarray(state.step)
    )
    ckpt.close()
    return dt


if __name__ == "__main__":
    main()
