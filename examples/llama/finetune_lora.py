"""LoRA fine-tuning from a selectively restored pretrained checkpoint.

Reference analog: ``examples/pytorch/llama2/fine_tuning.py`` (PEFT LoRA
under dlrover-run) + atorch's ``fsdp_init_util`` pretrained restore.
The TPU-native shape of the same product:

1. "pretrain": train a base model a few steps and flash-save it;
2. selective restore: load the body into a fine-tune world with a
   DIFFERENT mesh/sharding, excluding the lm head (regex), which keeps
   its fresh task init (``checkpoint/pretrained.py``);
3. LoRA: ``create_lora_state`` builds adapter (A, B) factors whose
   shardings are inherited from the base kernels; only adapters are in
   ``TrainState.params``, so the optimizer state is rank-sized and the
   frozen base physically cannot receive updates;
4. fine-tune steps, then ``merge_lora`` folds the adapters back for
   deployment.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/llama/finetune_lora.py
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
    p.add_argument("--rank", type=int, default=8)
    p.add_argument("--pretrain-steps", type=int, default=10)
    p.add_argument("--finetune-steps", type=int, default=20)
    p.add_argument("--ckpt-dir", default="/tmp/dlrover_tpu_lora_pretrain")
    args = p.parse_args(argv)
    if args.smoke:
        args.rank, args.pretrain_steps, args.finetune_steps = 2, 2, 3

    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.checkpoint import Checkpointer, StorageType
    from dlrover_tpu.checkpoint.pretrained import restore_pretrained
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.models.lora import create_lora_state, merge_lora
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.trainer.step import (
        create_sharded_state,
        data_sharding,
        make_train_step,
    )

    devices = jax.devices()
    cfg = LlamaConfig.tiny() if args.smoke else LlamaConfig(
        vocab_size=8192, hidden_size=128, intermediate_size=344,
        num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=128,
        scan_layers=False, attention_impl="dot",
    )
    model = LlamaModel(cfg)
    rng = np.random.RandomState(0)

    # batch divisible by the full (dp, fsdp) data extent (8 devices)
    def make_batch(batch_size=8):
        ids = rng.randint(
            0, cfg.vocab_size, size=(batch_size, cfg.max_seq_len + 1)
        )
        return {
            "input_ids": jnp.asarray(ids[:, :-1], jnp.int32),
            "labels": jnp.asarray(ids[:, 1:], jnp.int32),
        }

    # -- 1. pretrain on an fsdp mesh ------------------------------------
    n = len(devices)
    mesh1 = build_mesh(
        MeshConfig(dp=-1, fsdp=min(2, n)), devices
    )
    rules1 = PRESET_RULES["fsdp"]
    batch = make_batch()
    state, shardings = create_sharded_state(
        model, optax.adamw(1e-3), mesh1, rules1, jax.random.key(0), batch
    )
    step1 = make_train_step(model, mesh1, rules1, shardings)
    for _ in range(args.pretrain_steps):
        state, metrics = step1(
            state, jax.device_put(make_batch(), data_sharding(mesh1, rules1))
        )
    print(f"pretrain done: loss={float(metrics['loss']):.3f}")

    ckpt = Checkpointer(args.ckpt_dir, start_saver=True)
    ckpt.save_checkpoint(
        args.pretrain_steps, {"params": state.params},
        StorageType.DISK, block=True,
    )
    ckpt.wait()
    ckpt.close()

    # -- 2. selective restore into a different mesh ---------------------
    mesh2 = build_mesh(MeshConfig(dp=-1), devices)  # pure dp fine-tune
    rules2 = PRESET_RULES["dp"]
    fresh, fshardings = create_sharded_state(
        model, optax.adamw(1e-3), mesh2, rules2, jax.random.key(7), batch
    )
    restored, got, skipped = restore_pretrained(
        args.ckpt_dir,
        {"params": fresh.params},
        {"params": fshardings.params},
        exclude=[r"lm_head"],  # new-task head keeps its fresh init
    )
    print(f"restored {len(got)} tensors, kept fresh: {len(skipped)}")

    # -- 3. LoRA adapters over the frozen base --------------------------
    lstate, lshardings, spec = create_lora_state(
        model, optax.adam(1e-3), mesh2, rules2,
        restored["params"], jax.random.key(3), rank=args.rank,
    )
    n_adapter = sum(
        int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(lstate.params)
    )
    n_base = sum(
        int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(restored["params"])
    )
    print(f"trainable {n_adapter:,} / frozen {n_base:,} params")

    step2 = make_train_step(model, mesh2, rules2, lshardings)
    for _ in range(args.finetune_steps):
        lstate, metrics = step2(
            lstate, jax.device_put(make_batch(), data_sharding(mesh2, rules2))
        )
    print(f"finetune done: loss={float(metrics['loss']):.3f}")

    # -- 4. merge for deployment ---------------------------------------
    merged = merge_lora(restored["params"], lstate.params, spec)
    assert jax.tree_util.tree_structure(
        merged
    ) == jax.tree_util.tree_structure(restored["params"])
    print("adapters merged into base weights")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
