"""Measure Flash Checkpoint blocking vs background time on the real chip.

Prints save-dispatch blocking time (what the training thread pays), total
staging latency (background drain), training-overlap evidence (steps run
while the drain is in flight), and restore latency.

Run: python scripts/ckpt_bench.py   (uses the ambient backend; works on CPU
too, just with small numbers).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.checkpoint import Checkpointer, StorageType
from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import PRESET_RULES
from dlrover_tpu.trainer.step import create_sharded_state


def main():
    devices = jax.devices()
    mesh = build_mesh(MeshConfig(dp=-1), devices[:1])
    # the bench.py flagship (134 M params, ~1.5 GiB f32 train state)
    cfg = LlamaConfig(
        vocab_size=32000,
        hidden_size=768,
        intermediate_size=2048,
        num_layers=12,
        num_heads=12,
        num_kv_heads=12,
        max_seq_len=1024,
        scan_layers=False,
    )
    model = LlamaModel(cfg)
    batch = {
        "input_ids": jnp.zeros((4, 128), jnp.int32),
        "labels": jnp.zeros((4, 128), jnp.int32),
    }
    state, shardings = create_sharded_state(
        model, optax.adam(1e-3), mesh, PRESET_RULES["dp"],
        jax.random.key(0), batch,
    )
    nbytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(state)
        if hasattr(x, "nbytes")
    )

    @jax.jit
    def bump(params):
        return jax.tree.map(
            lambda x: x + jnp.ones((), x.dtype), params
        )

    # warm the bump and snapshot compile paths so we time steady state
    params = bump(state.params)
    jax.block_until_ready(params)
    state = state.replace(params=params)

    ckpt = Checkpointer("/tmp/dlrover_ckpt_bench", start_saver=True)
    # cold save warms the _DeviceSnapshot jit; time the steady-state one
    ckpt.save_checkpoint(1, state, StorageType.MEMORY)
    ckpt.wait_staging()

    t0 = time.time()
    ckpt.save_checkpoint(2, state, StorageType.MEMORY)
    t_block = time.time() - t0

    # overlap evidence: run training steps while the drain is in flight
    steps = 0
    t1 = time.time()
    while steps < 64:
        params = bump(params)
        steps += 1
    jax.block_until_ready(params)
    t_overlap_steps = time.time() - t1
    ok = ckpt.wait_staging()
    t_total = time.time() - t0

    t2 = time.time()
    step, _restored = ckpt.load_checkpoint(state, shardings)
    jax.block_until_ready(_restored.params)
    t_restore = time.time() - t2

    print(json.dumps({
        "state_bytes": nbytes,
        "backend": devices[0].platform,
        "save_blocking_s": round(t_block, 4),
        "staging_total_s": round(t_total, 2),
        "overlap_steps_run": steps,
        "overlap_steps_time_s": round(t_overlap_steps, 2),
        "staging_ok": ok,
        "restore_s": round(t_restore, 2),
        "restored_step": step,
    }))
    ckpt.close()


if __name__ == "__main__":
    main()
