"""LFM2's layers on the product's path, on the CPU at a small size: the
grouped product (``ops/grouped_matmul.py``), the dropless routed-expert
layer (``models/moe.py::RoutedExperts``), the gated short convolution and
the QK-normed rotary attention of ``models/hybrid.py``.  The model is held
against the benchmark's plain reference (``benchmarks/ref/lfm2_moe.py``),
which shares no code with it: hidden 64, 8 experts of width 32 with 4 a
token, layers ``conv, full_attention, conv`` (the first dense), vocabulary
256, 32 tokens, seeded random weights."""

import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import moe
from dlrover_tpu.models.hybrid import (
    HybridAttention,
    HybridConfig,
    HybridModel,
    ShortConv,
)
from dlrover_tpu.models.llama import cross_entropy_loss
from dlrover_tpu.ops import grouped_matmul as gm
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh
from dlrover_tpu.parallel.sharding import PRESET_RULES
from dlrover_tpu.telemetry import metrics as tmetrics
from dlrover_tpu.trainer.step import (
    create_sharded_state,
    data_sharding,
    make_train_step,
)

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(CHECKOUT, "benchmarks", "ref", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("lfm2_moe_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _published(cfg):
    """The tiny configuration under the reference's (published) key names."""
    return dict(
        norm_eps=cfg.rms_norm_eps, layer_types=list(cfg.layer_types),
        num_dense_layers=cfg.num_dense_layers,
        num_experts=cfg.experts_held or cfg.num_experts,
        expert_block=cfg.expert_block,
        num_experts_per_tok=cfg.num_experts_per_token,
        rope_theta=cfg.rope_theta,
        routed_scaling_factor=cfg.routed_scaling_factor,
    )


# -- the grouped product -----------------------------------------------------


def _loop(lhs, rhs, sizes):
    """Group by group, the rows past the sizes' sum left at zero."""
    out = jnp.zeros((lhs.shape[0], rhs.shape[-1]), jnp.float32)
    start = 0
    for g, size in enumerate(sizes):
        out = out.at[start:start + size].set(
            lhs[start:start + size] @ rhs[g])
        start += size
    return out


def _product_inputs(m=256, k=128, n=128, g=3, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (m, k)),
            jax.random.normal(keys[1], (g, k, n)) / np.sqrt(k),
            jax.random.normal(keys[2], (m, n)))


class TestGroupedMatmul:
    # The second case leaves a group empty and a third of the rows past the
    # sum; the third sends every row to one group.
    SIZES = [(100, 96, 60), (100, 0, 60), (0, 256, 0)]

    @pytest.mark.parametrize("interpret", [None, True],
                             ids=["ragged_dot", "library-kernel"])
    @pytest.mark.parametrize("sizes", SIZES)
    def test_values_and_gradients_match_a_loop(self, sizes, interpret):
        lhs, rhs, weights = _product_inputs()
        group_sizes = jnp.asarray(sizes, jnp.int32)
        call = lambda a, b: gm.grouped_matmul(
            a, b, group_sizes, interpret=interpret)
        got, want = call(lhs, rhs), _loop(lhs, rhs, sizes)
        # float32 operands: two orders of one sum of 128 terms
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        assert not np.asarray(got[sum(sizes):]).any()
        got_grads = jax.grad(
            lambda a, b: jnp.sum(call(a, b) * weights), (0, 1))(lhs, rhs)
        want_grads = jax.grad(
            lambda a, b: jnp.sum(_loop(a, b, sizes) * weights), (0, 1))(
                lhs, rhs)
        for got_g, want_g in zip(got_grads, want_grads):
            np.testing.assert_allclose(got_g, want_g, atol=1e-4, rtol=1e-4)
        assert not np.asarray(got_grads[0][sum(sizes):]).any()

    def test_bfloat16_operands_accumulate_in_float32(self):
        """512 terms of magnitude ~1/sqrt(512): rounded to bf16 once at the
        end the sum keeps 8 bits; summed in bf16 chunk by chunk it would
        lose about two more."""
        lhs, rhs, _ = _product_inputs(k=512, seed=1)
        sizes = jnp.asarray((100, 96, 60), jnp.int32)
        want = _loop(lhs.astype(jnp.bfloat16).astype(jnp.float32),
                     rhs.astype(jnp.bfloat16).astype(jnp.float32),
                     (100, 96, 60))
        for interpret in (None, True):
            got = gm.grouped_matmul(
                lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16), sizes,
                interpret=interpret)
            assert got.dtype == jnp.bfloat16
            err = np.abs(np.asarray(got, np.float32) - want).max()
            assert err <= 2.0 ** -8 * np.abs(want).max(), interpret

    def test_off_the_tpu_the_fallback_is_counted_and_warned_once(
            self, monkeypatch):
        lhs, rhs, _ = _product_inputs()
        sizes = jnp.asarray((100, 96, 60), jnp.int32)
        warnings = []
        monkeypatch.setattr(gm, "_warned_reasons", set())
        monkeypatch.setattr(
            gm.logger, "warning", lambda *a, **k: warnings.append(a))
        counter = tmetrics.counter("dlrover_moe_fallback_total")
        before = dict((dict(k).get("reason"), v)
                      for _n, k, v in counter.samples()).get("backend", 0)
        gm.grouped_matmul(lhs, rhs, sizes)
        gm.grouped_matmul(lhs, rhs, sizes)
        after = dict((dict(k).get("reason"), v)
                     for _n, k, v in counter.samples())["backend"]
        assert after - before == 2 and len(warnings) == 1
        assert gm.plan(256, 128, 128) == {"path": "ragged_dot", "tiling": None}

    def test_on_a_tpu_nothing_falls_back(self, monkeypatch, devices8):
        """The rule of the module: on the TPU the kernel or an error that
        names the cause (steered here as test_chip_compile.py steers it)."""
        monkeypatch.setattr(gm, "pallas_interpret", lambda: False)
        lhs, rhs, _ = _product_inputs(m=192)
        sizes = jnp.asarray((100, 32, 60), jnp.int32)
        with pytest.raises(ValueError, match="192 rows do not divide"):
            gm.grouped_matmul(lhs, rhs, sizes)
        lhs, rhs, _ = _product_inputs()
        mesh = build_mesh(MeshConfig(dp=2), devices8[:2])
        with use_mesh(mesh), pytest.raises(NotImplementedError, match="ep"):
            gm.grouped_matmul(lhs, rhs, sizes)
        planned = gm.plan(131072, 2048, 3584)
        assert planned["path"] == "megablox"
        assert planned["tiling"] == [list(t) for t in gm.tilings(
            131072, 2048, 3584)]
        for tm, tk, tn in gm.tilings(131072, 2048, 3584):
            assert 131072 % tm == 0 and tk % 128 == 0 and tn % 128 == 0


# -- routing -----------------------------------------------------------------


class TestRouting:
    def _scores(self, t=64, e=32, seed=0):
        return jax.nn.sigmoid(jax.random.normal(jax.random.key(seed), (t, e)))

    def test_weights_come_from_the_scores_and_sum_to_one(self):
        scores = self._scores()
        picks, weights = moe.route(scores, jnp.zeros(32), 4)
        assert picks.shape == weights.shape == (64, 4)
        np.testing.assert_allclose(weights.sum(-1), 1.0, atol=2e-6)
        top = np.sort(np.asarray(scores), -1)[:, -4:][:, ::-1]
        np.testing.assert_allclose(
            weights, top / (top.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
        doubled = moe.route(scores, jnp.zeros(32), 4, scaling=2.0)[1]
        np.testing.assert_allclose(doubled, 2 * weights, rtol=1e-6)

    def test_the_bias_moves_picks_and_not_weights(self):
        scores = self._scores(seed=1)
        bias = jnp.zeros(32).at[5].set(10.0)  # expert 5 wins every token
        picks, weights = moe.route(scores, bias, 4)
        assert bool((picks == 5).any(-1).all())
        assert not bool(
            (moe.route(scores, jnp.zeros(32), 4)[0] == 5).any(-1).all())
        # the weight on expert 5 is its score over the picks' scores: the
        # bias is nowhere in it
        chosen = jnp.take_along_axis(scores, picks, -1)
        np.testing.assert_allclose(
            weights, chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
            rtol=1e-6)
        grad = jax.grad(lambda b: moe.route(scores, b, 4)[1].sum())(bias)
        assert not np.asarray(grad).any()

    @pytest.mark.parametrize("case", ["uniform", "one-expert", "elsewhere"])
    def test_no_pair_is_lost_at_any_imbalance(self, case):
        t, k, first, held = 64, 4, 8, 8
        if case == "uniform":
            picks = jax.random.randint(jax.random.key(2), (t, k), 0, 32)
        elif case == "one-expert":  # every pick of every token on expert 11
            picks = jnp.full((t, k), 11)
        else:  # nothing lands on the held block
            picks = jax.random.randint(jax.random.key(3), (t, k), 16, 32)
        order, position, sizes = moe.sort_pairs(picks, first, held)
        assert int(sizes.sum()) == t * k  # every pair has a slot
        np.testing.assert_array_equal(np.sort(order), np.arange(t * k))
        np.testing.assert_array_equal(np.asarray(order)[position],
                                      np.arange(t * k))
        flat = np.asarray(picks).T.reshape(-1)  # pair p * t + i
        for j in range(held):
            assert int(sizes[j]) == int((flat == first + j).sum())
        sorted_experts = flat[np.asarray(order)]
        here = int(sizes[:held].sum())
        assert (np.diff(sorted_experts[:here]) >= 0).all()
        assert ((sorted_experts[:here] >= first)
                & (sorted_experts[:here] < first + held)).all()
        if case == "one-expert":
            assert int(sizes[3]) == t * k
        if case == "elsewhere":
            assert int(sizes[held]) == t * k

    def test_the_gathers_gradients_match_autodiff(self):
        t, k, h = 16, 4, 8
        picks = jax.random.randint(jax.random.key(4), (t, k), 0, 8)
        order, position, _ = moe.sort_pairs(picks, 0, 8)
        tokens = jax.random.normal(jax.random.key(5), (t, h))
        rows = jax.random.normal(jax.random.key(6), (t * k, h))
        weights = jax.random.normal(jax.random.key(7), (t * k, h))
        np.testing.assert_array_equal(
            moe._rows_of_pairs(tokens, order, position),
            jnp.tile(tokens, (k, 1))[order])
        got = jax.grad(lambda x: jnp.sum(
            moe._rows_of_pairs(x, order, position) * weights))(tokens)
        want = jax.grad(lambda x: jnp.sum(x[order % t] * weights))(tokens)
        np.testing.assert_allclose(got, want, atol=1e-6)
        got = jax.grad(lambda r: jnp.sum(
            moe._unsort(r, order, position) * weights))(rows)
        want = jax.grad(lambda r: jnp.sum(r[position] * weights))(rows)
        np.testing.assert_allclose(got, want, atol=1e-6)


# -- the routed layer --------------------------------------------------------


def _layer_case(seed, h=256, m=128, e=32, k=4, t=512, dtype=jnp.bfloat16,
                **kw):
    layer = moe.RoutedExperts(h, m, e, k, dtype=dtype, **kw)
    x = jax.random.normal(jax.random.key(seed), (1, t, h)).astype(dtype)
    params = nn.unbox(layer.init(jax.random.key(seed + 1), x))["params"]
    return layer, params, x


def _reference_layer(ref, params, x, k, block=0, held=None):
    w = {"router": params["router"], "bias": params["expert_bias"],
         "gate": params["gate_proj"], "up": params["up_proj"],
         "down": params["down_proj"]}
    cfg = dict(num_experts_per_tok=k, routed_scaling_factor=1.0)
    with jax.default_matmul_precision("highest"):
        return ref.experts_of_block(
            cfg, {n: jnp.asarray(v, jnp.float32) for n, v in w.items()},
            jnp.asarray(x[0], jnp.float32), block,
            held or params["gate_proj"].shape[0])[0]


def _rel_l2(got, want):
    got = jnp.asarray(got, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _bf16_router(tokens, router):
    return jax.nn.sigmoid(jnp.dot(
        tokens.astype(jnp.bfloat16), router.astype(jnp.bfloat16)
    )).astype(jnp.float32)


def _bf16_accumulation(lhs, rhs, sizes, chunk=16):
    """The product with its partial sums kept in bf16."""
    out = jnp.zeros((lhs.shape[0], rhs.shape[-1]), jnp.bfloat16)
    for c in range(0, lhs.shape[1], chunk):
        out = out + gm.grouped_matmul(
            lhs[:, c:c + chunk], rhs[:, c:c + chunk], sizes)
    return out


class TestRoutedExperts:
    # |layer - reference| over |reference|, 512 tokens, hidden 256, 32
    # experts of width 128, top-4, the inputs bf16 on both sides.  Read on
    # the CPU over four seeds: the layer (bf16 operands, float32
    # accumulation, float32 router) 0.0057-0.0058; the same with the
    # products' partial sums in bf16, 16 terms a chunk, 0.0102-0.0103; with
    # a bf16 router 0.18-0.22 (a score rounded to 8 bits swaps near-tied
    # picks, and a swapped pick is a whole expert's output).
    TOLERANCE = 0.008

    @pytest.mark.parametrize("seed", [0, 1])
    def test_bfloat16_compute_holds_to_the_reference(self, seed):
        layer, params, x = _layer_case(seed)
        out, sown = layer.apply({"params": params}, x,
                                mutable=["intermediates"])
        assert out.dtype == jnp.bfloat16
        want = _reference_layer(_reference(), params, x, 4)
        assert _rel_l2(out[0], want) < self.TOLERANCE
        load = sown["intermediates"]["moe_load"][0]
        assert load.shape == (33,) and int(load.sum()) == 512 * 4
        assert int(load[32]) == 0  # held = all: nothing lies elsewhere

    @pytest.mark.parametrize("control", ["router", "accumulation"])
    def test_the_tolerance_fails_a_lower_precision(self, control,
                                                   monkeypatch):
        if control == "router":
            monkeypatch.setattr(moe, "router_scores", _bf16_router)
        else:
            monkeypatch.setattr(moe, "grouped_matmul", _bf16_accumulation)
        layer, params, x = _layer_case(0)
        out = layer.apply({"params": params}, x)
        want = _reference_layer(_reference(), params, x, 4)
        assert _rel_l2(out[0], want) > self.TOLERANCE

    def test_four_shares_add_up_to_the_uncut_layer(self):
        """The share test of the model-configs guide: blocks 0-7, 8-15,
        16-23 and 24-31, each computed by a layer that holds only its
        block, add up to what the uncut reference gives."""
        ref = _reference()
        whole, params, x = _layer_case(2, h=64, m=32, t=96,
                                       dtype=jnp.float32)
        want = _reference_layer(ref, params, x, 4)
        total, pairs_here = 0.0, 0
        for block in range(4):
            share = moe.RoutedExperts(
                64, 32, 32, 4, experts_held=8, expert_block=block,
                dtype=jnp.float32)
            held = {name: (value[8 * block:8 * (block + 1)]
                           if name.endswith("_proj") else value)
                    for name, value in params.items()}
            out, sown = share.apply({"params": held}, x,
                                    mutable=["intermediates"])
            np.testing.assert_allclose(
                out[0], _reference_layer(ref, held, x, 4, block, 8),
                atol=2e-5, rtol=2e-5)
            load = sown["intermediates"]["moe_load"][0]
            assert load.shape == (9,) and int(load.sum()) == 96 * 4
            pairs_here += int(load[:8].sum())
            total = total + out[0]
        assert pairs_here == 96 * 4  # every pair is some block's
        np.testing.assert_allclose(total, want, atol=5e-5, rtol=5e-5)
        np.testing.assert_allclose(
            whole.apply({"params": params}, x)[0], want, atol=5e-5,
            rtol=5e-5)

    def test_every_token_on_one_expert_loses_nothing(self):
        """A bias of 10 on experts 0-3 sends every pick of every token to
        them: four groups of 96 rows, four empty, and the output is the
        reference's."""
        layer, params, x = _layer_case(3, h=64, m=32, e=8, t=96,
                                       dtype=jnp.float32)
        params = dict(params, expert_bias=jnp.zeros(8).at[:4].set(10.0))
        out, sown = layer.apply({"params": params}, x,
                                mutable=["intermediates"])
        np.testing.assert_array_equal(
            sown["intermediates"]["moe_load"][0],
            [96, 96, 96, 96, 0, 0, 0, 0, 0])
        np.testing.assert_allclose(
            out[0], _reference_layer(_reference(), params, x, 4),
            atol=2e-5, rtol=2e-5)

    def test_a_block_past_the_router_is_refused(self):
        x = jnp.zeros((1, 8, 64))
        with pytest.raises(ValueError, match="past the router"):
            moe.RoutedExperts(64, 32, 8, 4, experts_held=4,
                              expert_block=2).init(jax.random.key(0), x)
        with pytest.raises(ValueError, match="num_experts_per_token"):
            HybridConfig.tiny_lfm2(num_experts_per_token=0)


# -- the mixers --------------------------------------------------------------


def test_short_conv_matches_shifted_sums():
    """B * x through three taps with neither bias nor activation, gated by
    C: written out as the sum it is."""
    cfg = HybridConfig.tiny_lfm2(dtype=jnp.float32)
    h = jax.random.normal(jax.random.key(0), (2, 12, 64))
    params = nn.unbox(ShortConv(cfg).init(jax.random.key(1), h))["params"]
    assert set(params) == {"b_proj", "c_proj", "x_proj", "conv", "out_proj"}
    assert params["conv"].shape == (3, 64)
    got = ShortConv(cfg).apply({"params": params}, h)
    b, c, x = (np.asarray(h @ params[f"{n}_proj"]["kernel"]) for n in "bcx")
    u, taps = b * x, np.asarray(params["conv"])
    conv = np.zeros_like(u)
    for t in range(12):
        for j in range(3):  # c_t = sum_j w_j * u_{t - 2 + j}
            if t - 2 + j >= 0:
                conv[:, t] += taps[j] * u[:, t - 2 + j]
    np.testing.assert_allclose(
        got, (c * conv) @ np.asarray(params["out_proj"]["kernel"]),
        atol=1e-5, rtol=1e-5)


def test_attention_norms_q_and_k_before_the_rotary_term():
    ref = _reference()
    cfg = HybridConfig.tiny_lfm2(dtype=jnp.float32)
    h = jax.random.normal(jax.random.key(0), (2, 32, 64))
    params = nn.unbox(HybridAttention(cfg).init(jax.random.key(1), h))[
        "params"]
    assert params["q_norm"].shape == params["k_norm"].shape == (16,)
    keys = jax.random.split(jax.random.key(2), 2)
    params = dict(
        params,
        q_norm=1 + 0.3 * jax.random.normal(keys[0], (16,)),
        k_norm=1 + 0.3 * jax.random.normal(keys[1], (16,)))
    got = HybridAttention(cfg).apply({"params": params}, h)
    w = {n: params[f"{n}_proj"]["kernel"] for n in "qkvo"}
    w.update(q_norm=params["q_norm"], k_norm=params["k_norm"])
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([
            ref._attention({"norm_eps": 1e-5, "rope_theta": 1e6}, w, row)
            for row in h])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # positions move the result: the rotary term is there
    shifted = HybridAttention(cfg).apply(
        {"params": params}, h, jnp.arange(32)[None] * 3)
    assert float(jnp.abs(shifted - got).max()) > 1e-3
    # Granite's attention has neither the norms nor the term
    plain = HybridConfig.tiny(dtype=jnp.float32)
    assert set(nn.unbox(HybridAttention(plain).init(
        jax.random.key(1), h))["params"]) == {
            "q_proj", "k_proj", "v_proj", "o_proj"}


# -- the model against the reference -----------------------------------------


def _seeded(cfg, seed=0, b=2, s=32):
    """Model, parameters with every leaf random (the initialisers leave the
    norms at 1), ids and labels.  ``expert_bias`` stays as initialised."""
    model = HybridModel(cfg)
    ids = jax.random.randint(jax.random.key(seed), (b, s + 1), 0,
                             cfg.vocab_size)
    params = nn.unbox(model.init(jax.random.key(seed + 1), ids[:, :-1]))[
        "params"]
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(seed + 2), len(flat))
    params = jax.tree.unflatten(tree, [
        leaf if "expert_bias" in jax.tree_util.keystr(path)
        else leaf + 0.1 * jax.random.normal(key, leaf.shape)
        for (path, leaf), key in zip(flat, keys)])
    return model, params, ids[:, :-1], ids[:, 1:]


def _program_loss(model, params, ids, labels):
    return cross_entropy_loss(model.apply({"params": params}, ids), labels)


def _reference_loss(ref, cfg, params, ids, labels):
    total = sum(ref.loss_of_row(_published(cfg), params, i, l)
                for i, l in zip(ids, labels))
    return total / labels.size


class TestModelAgainstTheReference:
    @pytest.mark.parametrize("held, block", [(None, 0), (4, 1)],
                             ids=["uncut", "share"])
    def test_float32_logits_loss_and_gradients(self, held, block):
        """float32 on both sides: what is left is the order of the sums
        (1e-4 on logits of magnitude ~3, as the Granite test allows)."""
        ref = _reference()
        cfg = HybridConfig.tiny_lfm2(
            dtype=jnp.float32, experts_held=held, expert_block=block)
        model, params, ids, labels = _seeded(cfg)
        logits = model.apply({"params": params}, ids)
        want = jnp.stack(
            [ref.logits_of_row(_published(cfg), params, row) for row in ids])
        np.testing.assert_allclose(logits, want, atol=1e-4, rtol=1e-4)
        loss, grads = jax.value_and_grad(
            lambda p: _program_loss(model, p, ids, labels))(params)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: _reference_loss(ref, cfg, p, ids, labels))(params)
        np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-4,
                                                    rtol=1e-4),
            grads, ref_grads)
        for name in ("layers_1", "layers_2"):
            bias_grad = grads[name]["experts"]["expert_bias"]
            assert bias_grad.shape == (8,) and not np.asarray(bias_grad).any()
            assert float(jnp.abs(grads[name]["experts"]["router"]).max()) > 0

    def test_bfloat16_compute_stays_in_its_band(self):
        """bf16 keeps 8 significant bits: logits of magnitude ~3 may move
        by a few 2^-8 through three layers.  That band is stated over the
        tokens whose picks agree with the reference's in every routed
        layer: a near-tied pick that flips under bf16 hidden states swaps a
        whole expert's output for that token (0.4 on a logit here), which
        is routing and not rounding, so the flips are counted and bounded
        (with 8 experts, 64 tokens and weights moved by 0.1 the 4th and 5th
        scores lie within bf16's step of each other for a few tokens in a
        hundred).  The mean loss averages all of it out and holds to 2^-7
        relative (chip_smoke.py's band)."""
        ref, cfg = _reference(), HybridConfig.tiny_lfm2()
        model, params, ids, labels = _seeded(cfg, seed=5)
        logits, sown = model.apply({"params": params}, ids,
                                   mutable=["intermediates"])
        assert logits.dtype == jnp.bfloat16
        want = jnp.stack(
            [ref.logits_of_row(_published(cfg), params, row) for row in ids])
        agree = jnp.ones(ids.shape, bool)
        for n, name in enumerate(("layers_1", "layers_2")):
            picks = sown["intermediates"][name]["experts"]["moe_picks"][0]
            ours = jnp.zeros((ids.size, 8), bool).at[
                jnp.arange(ids.size)[:, None], picks].set(True)
            theirs = jnp.stack([
                ref.picks_of_row(_published(cfg), params, row)[n]
                for row in ids]).reshape(ids.size, 8)
            agree &= (ours == theirs).all(-1).reshape(ids.shape)
        assert float(agree.mean()) > 0.9
        worst = jnp.abs(logits - want).max(-1)
        assert float(jnp.where(agree, worst, 0).max()) < 0.05 * float(
            jnp.abs(want).max())
        loss = _program_loss(model, params, ids, labels)
        ref_loss = _reference_loss(ref, cfg, params, ids, labels)
        assert abs(float(loss) - float(ref_loss)) < 2.0 ** -7 * float(ref_loss)

    def test_recomputation_changes_nothing(self):
        cfg = HybridConfig.tiny_lfm2(dtype=jnp.float32)
        model, params, ids, labels = _seeded(cfg, seed=7)
        remat = HybridModel(HybridConfig.tiny_lfm2(
            dtype=jnp.float32, remat_policy="full"))
        grads = jax.grad(
            lambda p: _program_loss(model, p, ids, labels))(params)
        again = jax.grad(
            lambda p: _program_loss(remat, p, ids, labels))(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6), grads,
            again)


class TestModelContract:
    def test_ffn_by_layer_and_the_parameter_tree(self):
        cfg = HybridConfig.tiny_lfm2(experts_held=4, expert_block=1)
        params = jax.eval_shape(
            HybridModel(cfg).init, jax.random.key(0),
            jnp.zeros((1, 32), jnp.int32))["params"]
        params = nn.unbox(params)
        assert set(params["layers_0"]) == {
            "input_norm", "conv", "post_norm", "mlp"}
        assert set(params["layers_1"]) == {
            "input_norm", "attention", "post_norm", "experts"}
        assert set(params["layers_2"]) == {
            "input_norm", "conv", "post_norm", "experts"}
        experts = params["layers_1"]["experts"]
        assert experts["router"].shape == (64, 8)  # all the model's experts
        assert experts["expert_bias"].shape == (8,)
        assert experts["gate_proj"].shape == (4, 64, 32)  # the held block
        assert experts["down_proj"].shape == (4, 32, 64)

    def test_segment_ids_raise_where_a_conv_layer_would_leak(self):
        cfg = HybridConfig.tiny_lfm2(dtype=jnp.float32)
        model, params, ids, _ = _seeded(cfg)
        with pytest.raises(ValueError, match="segment_ids"):
            model.apply({"params": params}, ids, None, jnp.ones_like(ids))

    def test_causality(self):
        cfg = HybridConfig.tiny_lfm2(dtype=jnp.float32)
        model, params, ids, _ = _seeded(cfg)
        base = model.apply({"params": params}, ids)
        moved = model.apply(
            {"params": params}, ids.at[:, 20].set((ids[:, 20] + 1) % 256))
        np.testing.assert_allclose(base[:, :20], moved[:, :20], atol=1e-5)
        assert float(jnp.abs(base[:, 20:] - moved[:, 20:]).max()) > 1e-4

    def test_the_named_scopes_reach_the_lowered_program(self):
        cfg = HybridConfig.tiny_lfm2(dtype=jnp.float32)
        model, params, ids, _ = _seeded(cfg)
        lowered = jax.jit(model.apply).lower({"params": params}, ids)
        text = lowered.as_text(debug_info=True)
        for scope in ("moe/router", "moe/sort", "moe/gate_up", "moe/down",
                      "moe/combine", "conv/in_proj", "conv/conv",
                      "conv/out_proj", "hybrid/attention", "hybrid/mlp",
                      "hybrid/head"):
            assert scope in text, scope

    def test_each_lowering_leaves_a_span_in_the_telemetry_directory(
            self, tmp_path, monkeypatch):
        from dlrover_tpu.telemetry import events

        log = events.EventLog(directory=str(tmp_path))
        monkeypatch.setattr(events, "emit", log.emit)
        cfg = HybridConfig.tiny_lfm2(experts_held=4)
        jax.eval_shape(HybridModel(cfg).init, jax.random.key(0),
                       jnp.zeros((2, 32), jnp.int32))
        ends = [e for e in events.read_dir(str(tmp_path))
                if e["ev"] == "span_end" and e.get("name") == "lower"]
        assert len(ends) == 1
        end = ends[0]
        assert end["layer_types"] == {"conv": 2, "full_attention": 1}
        assert (end["num_experts"], end["experts_held"], end["top_k"]) == (
            8, 4, 4)
        assert end["pairs_rows"] == 2 * 32 * 4 and end["routed_layers"] == 2
        assert end["gmm_gate_up"] == end["gmm_down"] == {
            "path": "ragged_dot", "tiling": None}
        assert (end["attention_impl"], end["head_dim"]) == ("dot", 16)
        assert "chunk" not in end  # no scan in this model


# Where each rule table puts a new parameter's dimensions, by logical axis.
_NEW_PARAMETERS = {
    ("conv", "b_proj"): ("embed", "conv_inner"),
    ("conv", "c_proj"): ("embed", "conv_inner"),
    ("conv", "x_proj"): ("embed", "conv_inner"),
    ("conv", "out_proj"): ("conv_inner", "embed"),
    ("conv", "conv"): ("conv_width", "conv_inner"),
    ("attention", "q_norm"): ("head_dim",),
    ("attention", "k_norm"): ("head_dim",),
    ("experts", "router"): ("embed", "router"),
    ("experts", "expert_bias"): ("router",),
    ("experts", "gate_proj"): ("expert", "embed", "mlp"),
    ("experts", "up_proj"): ("expert", "embed", "mlp"),
    ("experts", "down_proj"): ("expert", "mlp", "embed"),
}


@pytest.mark.parametrize("preset, mesh_cfg", [
    ("dp", MeshConfig(dp=8)),
    ("fsdp", MeshConfig(dp=2, fsdp=4)),
    ("fsdp_tp", MeshConfig(dp=2, fsdp=2, tp=2)),
    ("3d", MeshConfig(dp=1, fsdp=2, tp=2, ep=2)),
])
def test_state_initialises_and_steps_sharded_by_rule(devices8, preset,
                                                     mesh_cfg):
    cfg = HybridConfig.tiny_lfm2(dtype=jnp.float32)
    model = HybridModel(cfg)
    mesh = build_mesh(mesh_cfg, devices8)
    rules = PRESET_RULES[preset]
    table = dict(rules)
    ids = jax.random.randint(jax.random.key(0), (8, 33), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    state, shardings = create_sharded_state(
        model, optax.adamw(1e-3), mesh, rules, jax.random.key(1), batch)
    layer = state.params["layers_1"]
    layer = dict(layer, conv=state.params["layers_2"]["conv"])
    for (module, name), axes in _NEW_PARAMETERS.items():
        leaf = layer[module][name]
        leaf = leaf["kernel"] if isinstance(leaf, dict) else leaf
        # every logical axis is in the table by rule, not by omission
        assert all(axis in table for axis in axes), name
        spec = tuple(leaf.sharding.spec) + (None,) * (
            len(axes) - len(leaf.sharding.spec))
        assert spec == tuple(table[axis] for axis in axes), (name, spec)
    step = make_train_step(model, mesh, rules, shardings)
    batch = jax.device_put(batch, data_sharding(mesh, rules))
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # the load of each routed layer rides the step's metrics
    assert sorted(metrics["moe_load"]) == [
        "layers_1/experts", "layers_2/experts"]
    for load in metrics["moe_load"].values():
        assert load.shape == (9,) and int(load.sum()) == 8 * 32 * 4
    # no gradient reaches the selection bias and the step has no other
    # rule for it: it is what it was
    assert not np.asarray(
        state.params["layers_1"]["experts"]["expert_bias"]).any()


def test_a_model_without_experts_has_no_load_in_its_metrics(devices8):
    cfg = HybridConfig.tiny(dtype=jnp.float32)
    model = HybridModel(cfg)
    mesh = build_mesh(MeshConfig(dp=1), devices8[:1])
    rules = PRESET_RULES["dp"]
    ids = jax.random.randint(jax.random.key(0), (2, 33), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    state, shardings = create_sharded_state(
        model, optax.adamw(1e-3), mesh, rules, jax.random.key(1), batch)
    _, metrics = make_train_step(model, mesh, rules, shardings)(state, batch)
    assert sorted(metrics) == ["grad_norm", "loss", "step"]
