"""Logits of a layer-pattern model against its plain reference at a
benchmark configuration's widths, outside the harness (one process, forward
only).

    chiprun -- python3 scripts/logits_check.py --config granite-4.0-h-micro
    chiprun -- python3 scripts/logits_check.py --config lfm2-8b-a1b
    chiprun -- python3 scripts/logits_check.py --config trinity-mini

For each seed: the program's forward (bf16 compute, the configuration's
kernels) on one row against the configuration's ``reference``
(``logits_of_row``: float32, highest matmul precision); then the same
program with one piece computed in a lower precision than the
configuration states (its ``controls``), which the tolerance has to catch.
Prints one JSON line a seed and a verdict; exits non-zero where a reading
is on the wrong side of the configuration's tolerance.  ``loss_rel`` is
what the benchmark's ``correct`` compares (the row's mean loss against the
reference's, relative; its limit is 2^-10): printed to show what it
separates, and judged by nothing.

A configuration with routed experts is held twice.  Over the whole row:
the share of (token, routed layer) pairs whose picks differ between program
and reference (``flipped``: a near-tied 4th and 5th score swap under bf16
hidden states, and a swapped pick is a whole expert's output, so it is
routing and not rounding; bounded and recorded), the logits over the tokens
whose picks agree in every layer (``share_agreeing``; attention and the
convolutions carry a flipped token's change to its neighbours, so this band
is wide and tells no precision from another), each layer's ``moe_load``
(pairs on each held expert, then elsewhere) and ``moe_compact`` (the routed
layers whose load fit one pass over the small pairs buffer).  Layer by layer,
which is what separates the precisions: each routed layer of the program
alone, fed the reference's own input to that layer rounded to bf16, against
the reference's layer on the same input (``layer_rel_l2``, the largest
|program - reference| / |reference| over the routed layers): same input,
so a float32 router picks as the reference does, a bf16 router does not,
and what is left of the difference is the products' rounding.

A configuration whose attention layers differ by kind (a sliding window
and rotary positions on some, neither on others) has each attention layer
held alone the same way (``attn_rel_l2``, the largest over the layers): a
wrong mask or a position term on the wrong kind of layer changes one
layer's output by tens of percent there, where the whole row's logits
would show it as one more flipped pick.  ``--window`` narrows the sliding
window for a CPU run at a ``--seq`` the published window would cover whole
(``--seq 512 --window 128``): without it the window-ignored control has
nothing to ignore and the script fails.
"""

import argparse
import importlib
import importlib.util
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)


def _bf16_decay_cumsum():
    import jax.numpy as jnp

    from dlrover_tpu.ops import ssd

    def cumsum(a):
        return jnp.cumsum(a.astype(jnp.bfloat16), axis=2).astype(jnp.float32)

    return ssd, "_decay_cumsum", cumsum


def _bf16_router():
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import moe

    def scores(tokens, router):
        return jax.nn.sigmoid(jnp.dot(
            tokens.astype(jnp.bfloat16), router.astype(jnp.bfloat16)
        )).astype(jnp.float32)

    return moe, "router_scores", scores


def _bf16_expert_accumulation(chunk=128):
    """The grouped products with their partial sums kept in bf16: the
    contraction cut into chunks of 128, each chunk's product and every
    partial sum rounded to bf16's 8 bits (``reduce_precision``: a chain of
    bf16 additions alone is fused and carried in float32 on the chip, and
    then reads as the program does: 0.0057 against 0.0049, PERF.md)."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import moe
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul

    def product(lhs, rhs, sizes):
        out = jnp.zeros((lhs.shape[0], rhs.shape[-1]), jnp.float32)
        for c in range(0, lhs.shape[1], chunk):
            part = grouped_matmul(
                lhs[:, c:c + chunk], rhs[:, c:c + chunk], sizes)
            out = jax.lax.reduce_precision(
                out + part.astype(jnp.float32), exponent_bits=8,
                mantissa_bits=7)
        return out.astype(lhs.dtype)

    return moe, "grouped_matmul", product


def _window_ignored():
    """The sliding layers given the global layers' causal mask."""
    from dlrover_tpu.models import hybrid

    stated = hybrid.splash_attention_gqa

    def causal_only(q, k, v, window=None, **kw):
        return stated(q, k, v, **kw)

    return hybrid, "splash_attention_gqa", causal_only


def _rotary_on_every_layer():
    """Rotary positions on the global layers too."""
    from dlrover_tpu.models.hybrid import HybridConfig

    return HybridConfig, "rotary", (
        lambda self, kind: self.rope_theta is not None)


# Largest |program - reference| over a row's logits, as a share of the
# largest |reference logit|, and the lower-precision controls it has to
# fail.  Each tolerance lies between two readings on a v5e at 8192 tokens,
# three seeds.
# granite-4.0-h-micro (PERF.md, PR 28): the program (bf16 matmul operands,
# f32 accumulation, the decay's logarithms and their sums in f32) reads
# 0.0053-0.0056; with the decay's cumulative sum in bf16 0.045-0.063.
# lfm2-8b-a1b (PERF.md, PR 34): see CHECKS["lfm2-8b-a1b"].
CHECKS = {
    "granite-4.0-h-micro": {
        "tolerance": 0.02,
        "controls": {"decay_cumsum_bf16": _bf16_decay_cumsum},
    },
    "lfm2-8b-a1b": {
        # The whole row, over the tokens whose picks agree: a sanity band
        # (twice the largest reading, 0.17-0.18), not a judge of precision:
        # the controls read the same there.
        "tolerance": 0.35,
        # (token, routed layer) pairs whose picks may differ from the
        # reference's over the whole row (read: 0.070-0.073): bounded at
        # twice that, recorded.
        "most_flipped": 0.15,
        # One routed layer on the reference's own input, relative l2,
        # between two readings: the program 0.0049; the products' partial
        # sums in bf16 0.0102; a bf16 router 0.055-0.063 (three seeds).
        "layer_tolerance": 0.008,
        "controls": {
            "router_bf16": _bf16_router,
            "expert_accumulation_bf16": _bf16_expert_accumulation,
        },
    },
    "trinity-mini": {
        # As for lfm2-8b-a1b, and as wide: a sanity band over the tokens
        # whose picks agree (read 0.014; 0.14 with rotary positions on the
        # global layer), which tells no precision from another.
        "tolerance": 0.35,
        # (token, routed layer) pairs whose eight picks may differ from
        # the reference's over the whole row (read: 0.094): bounded at
        # twice that, recorded.
        "most_flipped": 0.2,
        # One routed layer's 16 held experts on the reference's own input
        # (without the shared expert, which a control does not touch and
        # would dilute: the whole layer reads 0.0043 and 0.0052), relative
        # l2, between two readings on a v5e at 8192 tokens (PERF.md, PR
        # 36): the program 0.0049; the products' partial sums in bf16
        # 0.0098; a bf16 router 0.068.  The whole layer, shared expert and
        # all, is held to the same limit.
        "layer_tolerance": 0.008,
        # One attention layer on the reference's own input, relative l2,
        # between two readings: the program (bf16 operands, f32 softmax)
        # 0.0059; rotary positions on the global layer 0.22; the window
        # ignored 0.56.
        "attention_tolerance": 0.05,
        "controls": {
            "router_bf16": _bf16_router,
            "expert_accumulation_bf16": _bf16_expert_accumulation,
            "window_ignored": _window_ignored,
            "rotary_on_global": _rotary_on_every_layer,
        },
    },
}


def load_object(spec):
    module, name = spec.split(":")
    return getattr(importlib.import_module(module), name)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="granite-4.0-h-micro",
                    choices=sorted(CHECKS))
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--window", type=int, default=None,
                    help="a sliding window narrower than the published "
                         "one, for a CPU run at a short --seq")
    args = ap.parse_args()

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.common.platform import configure_compile_cache
    from dlrover_tpu.telemetry import metrics as tmetrics

    configure_compile_cache()
    check = CHECKS[args.config]
    tolerance = check["tolerance"]
    with open(os.path.join(CHECKOUT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    seq = args.seq or cfg["seq"]
    if args.window:
        cfg["sliding_window"] = args.window
    spec = importlib.util.spec_from_file_location(
        "bench_ref", os.path.join(CHECKOUT, cfg["reference"]))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    kwargs = dict(cfg["model"]["kwargs"], remat_policy="none")
    model = load_object(cfg["model"]["class"])(
        load_object(cfg["model"]["config_class"])(
            **{ours: cfg[theirs]
               for ours, theirs in cfg["model"]["from_source"].items()},
            **kwargs))
    routed = "most_flipped" in check
    by_kind = "attention_tolerance" in check
    device = jax.devices()[0]
    print(json.dumps({"device": device.platform, "kind": device.device_kind,
                      "config": args.config, "seq": seq,
                      "tolerance": tolerance}), flush=True)

    def forward():
        """A new function each call: jit's cache is keyed by the function,
        and a patched piece must be traced afresh.  Returns the row's bf16
        logits as the step's loss reads them, and what the layers sowed."""
        def logits(p, ids):
            out, sown = model.apply(
                {"params": p}, ids[None], mutable=["intermediates"])
            return out[0].astype(jnp.float32), sown.get("intermediates", {})
        return jax.jit(logits)

    reference = jax.jit(lambda p, ids: ref.logits_of_row(cfg, p, ids))
    reference_picks = jax.jit(lambda p, ids: ref.picks_of_row(cfg, p, ids))
    reference_inputs = jax.jit(
        lambda p, ids: ref.routed_inputs_of_row(cfg, p, ids))
    if by_kind:
        reference_attention_inputs = jax.jit(
            lambda p, ids: ref.attention_inputs_of_row(cfg, p, ids))
        reference_attention = jax.jit(
            lambda a, n, kind: ref.attention_layer(cfg, a, n, kind),
            static_argnums=2)

    def attention_alone(params, inputs):
        """Each attention layer of the program on the reference's input to
        it (bf16), against the reference's layer on the same input: the
        largest relative l2, and which layer read it."""
        from dlrover_tpu.models.hybrid import HybridAttention

        worst = (0.0, None)
        for i, (kind, n) in enumerate(zip(cfg["layer_types"], inputs)):
            x = n.astype(jnp.bfloat16)
            weights = params[f"layers_{i}"]["attention"]

            def attention(a, x, kind=kind):  # new each call, as forward()
                return HybridAttention(model.cfg, kind).apply(
                    {"params": a}, x[None])[0]

            got = jax.jit(attention)(weights, x)
            want = reference_attention(weights, x, kind)
            rel = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                        / jnp.linalg.norm(want))
            worst = max(worst, (rel, f"layers_{i}:{kind}"))
        return worst

    def layers_alone(params, inputs):
        """Each routed layer of the program on the reference's input to it
        (bf16), against the reference's layer on the same input: the
        routed experts alone, which is what the lower-precision controls
        touch, and, where the layer has a shared expert, the whole layer
        too (the shared expert, computed as stated, dilutes a control's
        reading there)."""
        import dataclasses

        from dlrover_tpu.models.hybrid import routed_experts

        names = sorted(n for n in params if "experts" in params[n])
        shared = bool(getattr(model.cfg, "num_shared_experts", 0))
        cases = [(dataclasses.replace(model.cfg, num_shared_experts=0),
                  dict(cfg, num_shared_experts=0), False)] if shared else []
        cases.append((model.cfg, cfg, shared))
        worst = {}
        for layer_cfg, ref_cfg, whole in cases:
            key = "layer_whole_rel_l2" if whole else "layer_rel_l2"
            their_layer = jax.jit(
                lambda e, n, c=ref_cfg: ref.routed_layer(c, e, n))
            worst[key] = 0.0
            for name, n in zip(names, inputs):
                x = n.astype(jnp.bfloat16)
                weights = {k: v for k, v in params[name]["experts"].items()
                           if whole or k != "shared"}

                def experts(e, x, c=layer_cfg):  # new each call, as forward()
                    return routed_experts(c).apply({"params": e}, x[None])[0]

                got = jax.jit(experts)(weights, x)
                want = their_layer(weights, x)
                worst[key] = max(worst[key], float(
                    jnp.linalg.norm(got.astype(jnp.float32) - want)
                    / jnp.linalg.norm(want)))
        return worst

    def loss(logits, ids):  # next-token mean loss of the row
        picked = jnp.take_along_axis(logits[:-1], ids[1:, None], axis=-1)
        return float(jnp.mean(
            jax.nn.logsumexp(logits[:-1], axis=-1) - picked[:, 0]))

    def routing(sown, theirs):
        """Per token whether every routed layer's picks are the
        reference's, the share of (token, layer) pairs that are not, each
        layer's load, and the layers whose load fit one pass over the small
        buffer."""
        names = sorted(n for n in sown if "experts" in sown[n])
        agree, flipped, loads, compact = True, [], {}, 0
        for name, mask in zip(names, theirs):
            picks = sown[name]["experts"]["moe_picks"][0]
            ours = jnp.zeros(mask.shape, bool).at[
                jnp.arange(mask.shape[0])[:, None], picks].set(True)
            same = (ours == mask).all(-1)
            agree = agree & same
            flipped.append(1.0 - float(same.mean()))
            loads[name] = [int(n) for n in
                           sown[name]["experts"]["moe_load"][0]]
            compact += int(sown[name]["experts"]["moe_compact"][0])
        return agree, sum(flipped) / len(flipped), loads, compact

    def compare(run, want, ids, theirs):
        logits, sown = run
        top = float(jnp.abs(want).max())
        worst = jnp.abs(logits - want).max(-1)
        out = {"max_abs": float(worst.max()), "max_ref_logit": top,
               "share": float(worst.max()) / top,
               "rel_l2": float(jnp.linalg.norm(logits - want)
                               / jnp.linalg.norm(want)),
               "loss_rel": abs(loss(logits, ids) / loss(want, ids) - 1.0)}
        if routed:
            agree, out["flipped"], out["moe_load"], out["moe_compact"] = (
                routing(sown, theirs))
            out["share_agreeing"] = float(
                jnp.where(agree, worst, 0.0).max()) / top
        return out

    def passes(reading):
        if not routed:
            return reading["share"] < tolerance
        return (reading["share_agreeing"] < tolerance
                and reading["flipped"] < check["most_flipped"]
                and reading["layer_rel_l2"] < check["layer_tolerance"]
                and reading.get("layer_whole_rel_l2", 0.0)
                < check["layer_tolerance"]
                and (not by_kind or reading["attn_rel_l2"]
                     < check["attention_tolerance"]))

    def read(params, ids, want, theirs, inputs, attended=None):
        reading = compare(forward()(params, ids), want, ids, theirs)
        if routed:
            reading.update(layers_alone(params, inputs))
        if by_kind:
            reading["attn_rel_l2"], reading["attn_worst_layer"] = (
                attention_alone(params, attended))
        return reading

    ok = True
    for seed in range(args.seeds):
        ids = jax.random.randint(
            jax.random.key(1000 + seed), (seq,), 0, cfg["vocab_size"])
        params = nn.unbox(jax.jit(model.init)(
            jax.random.key(seed), ids[None]))["params"]
        want = reference(params, ids)
        theirs = reference_picks(params, ids) if routed else None
        inputs = reference_inputs(params, ids) if routed else None
        attended = (reference_attention_inputs(params, ids)
                    if by_kind else None)
        line = {"seed": seed,
                "program": read(params, ids, want, theirs, inputs, attended)}
        ok = ok and passes(line["program"])
        for name, patch in check["controls"].items():
            module, attribute, lowered = patch()
            stated = getattr(module, attribute)
            setattr(module, attribute, lowered)
            try:
                line[name] = read(
                    params, ids, want, theirs, inputs, attended)
            finally:
                setattr(module, attribute, stated)
            ok = ok and not passes(line[name])
        print(json.dumps(line), flush=True)
    counters = {
        name: {dict(key).get("reason", ""): v
               for _n, key, v in tmetrics.REGISTRY.get(name).samples()}
        for name in ("dlrover_attention_fallback_total",
                     "dlrover_moe_fallback_total")
        if tmetrics.REGISTRY.get(name)}
    print(json.dumps({"ok": ok, "fallbacks": counters}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
