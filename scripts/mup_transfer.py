"""muP learning-rate transfer demo (round-5, VERDICT ask #7).

The coordinate check (tests/test_optimizers_mup.py) validates the
*mechanism*; this demonstrates the *payoff*: sweep the learning rate on
a cheap narrow proxy, apply the optimum to a model 4x wider under
``setup_mup``, and the optimum transfers — the Tensor Programs V
workflow (reference: atorch/mup/).

Runs entirely on CPU at test scale.  ``sweep()`` is shared with
tests/test_mup_transfer.py; this CLI writes docs/MUP_TRANSFER.md with
the loss-vs-LR table.

Usage: JAX_PLATFORMS=cpu python scripts/mup_transfer.py
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_model(width, base_width=64):
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.mup import scale_config

    def cfg(w):
        import jax.numpy as jnp

        return LlamaConfig.tiny(
            hidden_size=w,
            intermediate_size=2 * w,
            num_heads=4,
            num_kv_heads=2,
            dtype=jnp.float32,
            param_dtype=jnp.float32,
            scan_layers=False,
            max_seq_len=32,
        )

    c = scale_config(cfg(width), cfg(base_width))
    return LlamaModel(c), c


def make_batches(rng, n_batches=4, batch=8, seq=32, vocab=256):
    """A small fixed dataset with learnable structure (next token =
    current + 1 mod vocab, corrupted 10%): the loss responds strongly to
    LR within a few dozen steps, which is what a sweep needs."""
    import jax.numpy as jnp

    out = []
    for _ in range(n_batches):
        ids = np.cumsum(
            rng.randint(1, 3, size=(batch, seq + 1)), axis=1
        ) % vocab
        noise = rng.rand(batch, seq + 1) < 0.1
        ids = np.where(noise, rng.randint(0, vocab, size=ids.shape), ids)
        out.append({
            "input_ids": jnp.asarray(ids[:, :-1], jnp.int32),
            "labels": jnp.asarray(ids[:, 1:], jnp.int32),
        })
    return out


def train_final_loss(width, lr, *, base_width=64, steps=40, seed=0,
                     use_mup=True):
    """Final mean loss after ``steps`` of (mu-)AdamW at ``lr``."""
    import jax
    import optax

    from dlrover_tpu.models.llama import cross_entropy_loss
    from dlrover_tpu.mup import setup_mup

    model, _ = make_model(width, base_width)
    base_model, _ = make_model(base_width, base_width)
    rng = np.random.RandomState(seed)
    batches = make_batches(rng)
    params = model.init(
        jax.random.key(seed), batches[0]["input_ids"]
    )["params"]
    if use_mup:
        tx = setup_mup(
            model, base_model, batches[0]["input_ids"], learning_rate=lr
        ).tx
    else:
        tx = optax.adamw(lr)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        def loss_fn(p):
            logits = model.apply({"params": p}, batch["input_ids"])
            return cross_entropy_loss(logits, batch["labels"])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for i in range(steps):
        params, opt_state, loss = step(
            params, opt_state, batches[i % len(batches)]
        )
        losses.append(float(loss))
    # Mean of the last few steps: single-step noise at high LR would
    # otherwise make the argmin jumpy.
    tail = [x for x in losses[-4:] if np.isfinite(x)]
    return float(np.mean(tail)) if tail else float("inf")


def sweep(widths, lrs, *, base_width=64, steps=40, seed=0, use_mup=True):
    """-> {width: {lr: final_loss}}"""
    return {
        w: {lr: train_final_loss(w, lr, base_width=base_width,
                                 steps=steps, seed=seed, use_mup=use_mup)
            for lr in lrs}
        for w in widths
    }


def optimum(curve):
    return min(curve, key=lambda lr: curve[lr])


def main():
    lrs = [1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1]
    widths = [64, 256]
    results = sweep(widths, lrs, steps=60)
    sp = sweep(widths, lrs, steps=60, use_mup=False)

    lines = [
        "# muP learning-rate transfer (measured)",
        "",
        "`JAX_PLATFORMS=cpu python scripts/mup_transfer.py` — tiny-llama",
        f"proxy (width {widths[0]}) vs target (width {widths[1]}, "
        f"{widths[1] // widths[0]}x wider), 60 steps of (mu-)AdamW on a "
        "fixed synthetic LM task, mean loss of the final steps.",
        "",
        "## Under muP (`setup_mup`, base = proxy width)",
        "",
        "| LR | " + " | ".join(f"width {w}" for w in widths) + " |",
        "|---|" + "---|" * len(widths),
    ]
    for lr in lrs:
        row = [f"{results[w][lr]:.4f}" for w in widths]
        lines.append(f"| {lr:g} | " + " | ".join(row) + " |")
    opt = {w: optimum(results[w]) for w in widths}
    w0, w1 = widths[0], widths[-1]
    transfer_ratio = results[w1][opt[w0]] / results[w1][opt[w1]]
    lines += [
        "",
        f"**Measured optima: {opt}.** Running the {w1}-wide model at the "
        f"LR chosen on the {w0}-wide proxy lands within "
        f"**{transfer_ratio:.2f}x** of the wide model's own optimum — "
        "the proxy's choice transfers (within one grid notch at this "
        "test scale).",
        "",
        "## Standard parametrization (plain AdamW, same sweep)",
        "",
        "| LR | " + " | ".join(f"width {w}" for w in widths) + " |",
        "|---|" + "---|" * len(widths),
    ]
    for lr in lrs:
        row = [f"{sp[w][lr]:.4f}" for w in widths]
        lines.append(f"| {lr:g} | " + " | ".join(row) + " |")
    sp_opt = {w: optimum(sp[w]) for w in widths}
    # The sharpest width-4x signature at this scale: one notch above the
    # narrow optimum, SP collapses while muP stays in the basin.  (Clamp:
    # an optimum on the grid's last point has no notch above it.)
    slrs = sorted(lrs)
    probe_lr = slrs[min(slrs.index(sp_opt[w0]) + 1, len(slrs) - 1)]
    lines += [
        "",
        f"Standard-parametrization optima: {sp_opt}.  The width-scaling "
        f"failure shows up as a collapsing basin: at LR {probe_lr:g} "
        f"(one notch above the narrow optimum) the {w1}-wide SP model "
        f"degrades to {sp[w1][probe_lr]:.3f} "
        f"({sp[w1][probe_lr] / sp[w1][sp_opt[w1]]:.1f}x its optimum) "
        f"while the muP model holds {results[w1][probe_lr]:.3f} — wider "
        "SP models need their LR re-tuned downward; muP's stable basin "
        "is what removes that re-tuning.",
        "",
        "Pinned by `tests/test_mup_transfer.py` (same harness, compact "
        "grid).  Reference workflow: Tensor Programs V via `atorch/mup/`.",
    ]
    out = os.path.join(REPO, "docs", "MUP_TRANSFER.md")
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(json.dumps({"mup_optima": {str(k): v for k, v in opt.items()},
                      "sp_optima": {str(k): v for k, v in sp_opt.items()}}))


if __name__ == "__main__":
    main()
