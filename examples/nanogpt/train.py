"""nanoGPT-style char-LM pretraining with the high-level Trainer.

Reference analog: ``examples/pytorch/nanogpt/train.py`` — a small
decoder trained on character data, elastically.  Differences that matter
here: the model is the in-tree llama family at nano scale (byte-level
vocab), ``auto_accelerate`` picks/applies the sharding strategy, data
order comes from the world-size-aware ``ElasticSampler`` (its
``state_dict`` is what a resumed worker restores so no window repeats
within an epoch), and the whole thing is one jitted SPMD program.

The corpus is generated, not shipped: arithmetic lines ("37+58=95\n")
— structured enough that a 2-layer model's loss visibly collapses from
~4.8 (uniform over bytes) to under 1, and free of licensing baggage.

    python examples/nanogpt/train.py
    python -m dlrover_tpu.launch.elastic_run --nnodes 1 \
        examples/nanogpt/train.py
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
from dlrover_tpu.trainer.elastic import ElasticDataLoader, ElasticSampler
from dlrover_tpu.trainer.trainer import Trainer, TrainingArguments


def build_corpus(n_lines: int, seed: int = 0) -> np.ndarray:
    rng = np.random.RandomState(seed)
    a = rng.randint(0, 100, size=n_lines)
    b = rng.randint(0, 100, size=n_lines)
    text = "".join(f"{x}+{y}={x + y}\n" for x, y in zip(a, b))
    return np.frombuffer(text.encode(), dtype=np.uint8).astype(np.int32)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="tiny CI run")
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--lines", type=int, default=20000)
    p.add_argument("--ckpt-dir", default="")
    args = p.parse_args(argv)
    if args.smoke:
        # batch must stay divisible by the (dp, fsdp) mesh extent
        args.seq, args.batch_size, args.steps, args.lines = 32, 8, 8, 500

    data = build_corpus(args.lines)
    n_windows = (len(data) - 1) // args.seq

    cfg = LlamaConfig(
        vocab_size=256,
        hidden_size=128 if not args.smoke else 64,
        intermediate_size=344 if not args.smoke else 172,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        max_seq_len=args.seq,
        scan_layers=False,
        attention_impl="dot",
    )

    # The elastic sampler shards windows over data-parallel ranks;
    # record_batch advances the cross-replica cursor so a rejoining
    # worker (restored via sampler.load_state_dict) never re-reads
    # finished windows.
    sampler = ElasticSampler(n_windows, shuffle=True, seed=0)

    def read_window(i: int):
        lo = i * args.seq
        chunk = data[lo : lo + args.seq + 1]
        return {"input_ids": chunk[:-1], "labels": chunk[1:]}

    loader = ElasticDataLoader(read_window, sampler, batch_size=args.batch_size)

    def batches():
        epoch = 0
        while True:
            sampler.set_epoch(epoch)
            for b in loader:
                yield b
                sampler.record_batch(args.batch_size)
            epoch += 1

    targs = TrainingArguments(
        max_steps=args.steps,
        log_interval=max(args.steps // 10, 1),
        load_strategy=["fsdp"],
        save_interval=50 if args.ckpt_dir else 0,
        memory_save_interval=1 if args.ckpt_dir else 0,
        ckpt_dir=args.ckpt_dir,
    )
    checkpointer = None
    if args.ckpt_dir:
        from dlrover_tpu.checkpoint.checkpointer import Checkpointer

        checkpointer = Checkpointer(args.ckpt_dir, start_saver=True)
    trainer = Trainer(
        LlamaModel(cfg), targs, batches(), checkpointer=checkpointer
    )
    state = trainer.train()
    if checkpointer is not None:
        checkpointer.wait_staging(timeout=30)
        checkpointer.close()

    first = np.mean(state.loss_history[:3])
    last = np.mean(state.loss_history[-3:])
    print(
        f"steps={state.global_step} loss {first:.3f} -> {last:.3f} "
        f"(spikes={state.spikes})"
    )
    assert last < first, "char-LM loss did not fall"
    return last


if __name__ == "__main__":
    main()
