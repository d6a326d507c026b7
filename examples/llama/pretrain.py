"""Flagship llama pretraining: fsdp × tp, flash attention, grad accum.

Reference analog: ``examples/pytorch/llama2/pretrain.py`` (FSDP llama2
under dlrover-run) and ``atorch/examples/llama2/fsdp_llama2.py``.  Here
the parallelism is one GSPMD rule table over a named mesh — change
``--fsdp/--tp/--sp`` and the same jitted program regrids; no wrapper
modules, no device placement code.

What it demonstrates:

- ``auto_accelerate`` with an explicit strategy (fsdp + tensor_parallel
  + module_replace to the flash/splash attention kernel);
- ``ElasticTrainer`` keeping the GLOBAL batch fixed: grad-accum factor
  recomputed from the data-parallel world size, so a shrunk world sees
  identical learning dynamics;
- flash checkpointing + resume through the high-level ``Trainer``.

    # 8-device virtual mesh on CPU; drop the env on a real slice
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/llama/pretrain.py --fsdp 4 --tp 2 --steps 30
"""

import argparse
import os
import sys

sys.path.insert(
    0,
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
)

import numpy as np

from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.trainer import Trainer, TrainingArguments

SIZES = {
    # hidden, intermediate, layers, heads (tiny defaults train on CPU)
    "nano": (64, 172, 2, 4),
    "small": (768, 2048, 12, 12),
    "7b": (4096, 11008, 32, 32),
}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="tiny CI run")
    p.add_argument("--size", choices=sorted(SIZES), default="nano")
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--micro-batch", type=int, default=4)
    p.add_argument("--global-batch", type=int, default=32)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt-dir", default="")
    args = p.parse_args(argv)
    if args.smoke:
        args.seq, args.steps = 64, 6

    hidden, inter, layers, heads = SIZES[args.size]
    cfg = LlamaConfig(
        vocab_size=8192 if args.size == "nano" else 32000,
        hidden_size=hidden,
        intermediate_size=inter,
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=heads,
        max_seq_len=args.seq,
        scan_layers=False,
        attention_impl="dot",  # module_replace upgrades it on TPU
    )
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"

    # Data-parallel world = the mesh's data extent (dp x fsdp): every
    # device group along it consumes micro_batch samples per step, so
    # one step feeds micro_batch * dp_world rows (sharded over the
    # extent — also what makes the leading dim divisible by the mesh).
    n_dev = len(jax.devices())
    dp_world = max(n_dev // args.tp, 1)
    step_rows = args.micro_batch * dp_world

    # Synthetic token stream (swap batches() for your tokenized corpus).
    rng = np.random.RandomState(0)

    def batches():
        while True:
            ids = rng.randint(
                0, cfg.vocab_size, size=(step_rows, args.seq + 1)
            )
            yield {
                "input_ids": ids[:, :-1].astype(np.int32),
                "labels": ids[:, 1:].astype(np.int32),
            }

    # Grad accumulation from the elasticity contract: global batch stays
    # fixed as the data-parallel world resizes.
    import optax

    elastic = ElasticTrainer(
        global_batch_size=args.global_batch,
        micro_batch_size=args.micro_batch,
        data_parallel_size=dp_world,
        base_learning_rate=args.lr,
    )
    optimizer = elastic.wrap_optimizer(
        optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(args.lr, b2=0.95, weight_decay=0.1),
        )
    )

    strategy = [
        ("fsdp", {"fsdp_size": args.fsdp}),
        ("tensor_parallel", {"tp_size": args.tp}),
    ]
    if on_tpu:
        strategy.append(("module_replace", {"attention_impl": "splash"}))

    targs = TrainingArguments(
        max_steps=args.steps,
        log_interval=max(args.steps // 10, 1),
        load_strategy=strategy,
        save_interval=100 if args.ckpt_dir else 0,
        memory_save_interval=1 if args.ckpt_dir else 0,
    )
    checkpointer = None
    if args.ckpt_dir:
        from dlrover_tpu.checkpoint.checkpointer import Checkpointer

        checkpointer = Checkpointer(args.ckpt_dir, start_saver=True)

    trainer = Trainer(
        LlamaModel(cfg),
        targs,
        batches(),
        optimizer=optimizer,
        checkpointer=checkpointer,
        elastic_trainer=elastic,
    )
    print(
        f"strategy={trainer.strategy.opt_names()} "
        f"accum_steps={elastic.accum_steps} "
        f"effective_batch={elastic.effective_batch_size}"
    )
    state = trainer.train()
    if checkpointer is not None:
        checkpointer.wait_staging(timeout=30)
        checkpointer.close()
    final_loss = state.loss_history[-1]
    print(
        f"steps={state.global_step} tokens={state.tokens_seen} "
        f"final_loss={final_loss:.3f}"
    )
    # Random tokens have no learnable structure beyond the uniform
    # unigram floor — assert the loss is finite and near log(V), which
    # catches divergence/NaN regressions without a flaky "it fell" check.
    assert np.isfinite(final_loss) and final_loss < 1.2 * np.log(
        cfg.vocab_size
    ), f"pretrain loss diverged: {final_loss}"
    return state


if __name__ == "__main__":
    main()
