"""AFMoE's layers (Trinity) on the product's path, on the CPU at a small
size: the sliding window in all three attention paths
(``ops/splash_attention.py``, ``ops/flash_attention.py``, the ``dot`` mask
of ``models/hybrid.py``), the gated, QK-normed attention with rotary
positions on the sliding layers only, the four-norm layer, the untied
head, and the shared expert beside the routed ones
(``models/moe.py::RoutedExperts``).  The model is held against the
benchmark's plain reference (``benchmarks/ref/afmoe.py``), which shares no
code with it: hidden 64, 4 / 2 heads of 32, window 8, 16 experts of width
32 with 4 a token and one shared, layers ``sliding sliding full sliding``
(the first dense), vocabulary 256, 32 tokens, seeded random weights."""

import hashlib
import importlib.util
import math
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from test_hybrid_model import _lower_span
from test_lfm2_moe import (
    _bf16_accumulation,
    _bf16_router,
    _program_loss,
    _rel_l2,
    _seeded,
)

from dlrover_tpu.models import moe
from dlrover_tpu.models.hybrid import HybridConfig, HybridModel, causal_mask
from dlrover_tpu.models.llama import _masked_attention, cross_entropy_loss
from dlrover_tpu.ops import splash_attention as splash
from dlrover_tpu.ops.flash_attention import flash_attention_gqa, mha_reference
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import PRESET_RULES
from dlrover_tpu.telemetry import metrics as tmetrics
from dlrover_tpu.trainer.step import (
    create_sharded_state,
    data_sharding,
    make_train_step,
)

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUTED = ("layers_1", "layers_2", "layers_3")


def _reference():
    path = os.path.join(CHECKOUT, "benchmarks", "ref", "afmoe.py")
    spec = importlib.util.spec_from_file_location("afmoe_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _published(cfg):
    """The tiny configuration under the reference's (published) key names."""
    return dict(
        hidden_size=cfg.hidden_size, rms_norm_eps=cfg.rms_norm_eps,
        layer_types=list(cfg.layer_types),
        sliding_window=cfg.sliding_window, rope_theta=cfg.rope_theta,
        num_dense_layers=cfg.num_dense_layers,
        num_experts=cfg.experts_held or cfg.num_experts,
        expert_block=cfg.expert_block,
        num_experts_per_tok=cfg.num_experts_per_token,
        num_shared_experts=cfg.num_shared_experts,
        route_norm=True, route_scale=cfg.routed_scaling_factor,
        mup_enabled=True,
    )


# -- the window alone ---------------------------------------------------------


def _explicit(q, k, v, window, segment_ids=None):
    """softmax over the keys with 0 <= t - j < window, by the formula."""
    s, d = q.shape[1], q.shape[-1]
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None]
    mask = ((back >= 0) & (back < window))[None, None]
    if segment_ids is not None:
        mask = mask & (segment_ids[:, None, :, None]
                       == segment_ids[:, None, None, :])
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _qkv(s, seed=0, h=4, h_kv=2, d=64):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (2, s, h, d)),
            jax.random.normal(keys[1], (2, s, h_kv, d)),
            jax.random.normal(keys[2], (2, s, h_kv, d)))


def _two_documents(s):
    cut = s // 2 - 17  # not on a block's edge
    return jnp.concatenate(
        [jnp.zeros((2, cut), jnp.int32), jnp.ones((2, s - cut), jnp.int32)], 1)


_PATHS = {
    "splash": lambda q, k, v, seg, w: splash.splash_attention_gqa(
        q, k, v, segment_ids=seg, window=w, interpret=True, block_q=128,
        block_kv=128),
    "in-tree": lambda q, k, v, seg, w: flash_attention_gqa(
        q, k, v, segment_ids=seg, window=w, block_q=128, block_kv=128),
    "xla": lambda q, k, v, seg, w: mha_reference(
        q, k, v, segment_ids=seg, window=w),
    # the model's ``dot`` path: the mask HybridAttention builds
    "dot": lambda q, k, v, seg, w: _masked_attention(
        q, k, v, causal_mask(q.shape[1], w, seg)),
}


class TestWindow:
    # s = 512 in blocks of 128: a window of 128 (a block), 200 (not a
    # multiple: blocks half covered), and 768 (longer than the sequence).
    @pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
    @pytest.mark.parametrize("window", [128, 200, 768])
    @pytest.mark.parametrize("path", sorted(_PATHS))
    def test_values_and_gradients_match_an_explicit_mask(
            self, path, window, packed):
        """float32 on both sides: what is left is the order of the sums in
        the blockwise softmax (1e-5 on outputs of magnitude ~1)."""
        q, k, v = _qkv(512)
        seg = _two_documents(512) if packed else None
        weights = jnp.cos(jnp.arange(64.0))

        def summed(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) * weights)

        got = _PATHS[path](q, k, v, seg, window)
        want = _explicit(q, k, v, window, seg)
        np.testing.assert_allclose(got, want, atol=1e-5)
        grads = jax.grad(summed(
            lambda q, k, v: _PATHS[path](q, k, v, seg, window)), (0, 1, 2))(
                q, k, v)
        wanted = jax.grad(summed(
            lambda q, k, v: _explicit(q, k, v, window, seg)), (0, 1, 2))(
                q, k, v)
        for a, b in zip(grads, wanted):
            np.testing.assert_allclose(a, b, atol=5e-5)

    @pytest.mark.parametrize("path", sorted(_PATHS))
    def test_a_window_of_the_sequences_length_is_causal(self, path):
        q, k, v = _qkv(256, seed=1)
        got = _PATHS[path](q, k, v, None, 256)
        np.testing.assert_allclose(
            got, mha_reference(q, k, v, causal=True), atol=1e-5)
        narrower = _PATHS[path](q, k, v, None, 255)
        # only the last query loses a key: the first
        np.testing.assert_allclose(got[:, :-1], narrower[:, :-1], atol=1e-5)
        assert float(jnp.abs(got[:, -1] - narrower[:, -1]).max()) > 1e-4

    def test_the_window_is_a_mask_and_the_segment_bound_a_hint(self):
        """``max_segment_len`` alone masks nothing between documents it
        was promised not to exist; ``window`` masks whatever the segments;
        with both the narrower band is the static mask."""
        q, k, v = _qkv(512, seed=2)
        seg = jnp.zeros((2, 512), jnp.int32)  # one document of 512
        hinted = splash.splash_attention_gqa(
            q, k, v, segment_ids=seg, max_segment_len=512, interpret=True,
            block_q=128, block_kv=128)
        np.testing.assert_allclose(
            hinted, mha_reference(q, k, v), atol=1e-5)
        both = splash.splash_attention_gqa(
            q, k, v, segment_ids=seg, max_segment_len=512, window=100,
            interpret=True, block_q=128, block_kv=128)
        np.testing.assert_allclose(
            both, _explicit(q, k, v, 100), atol=1e-5)

    def test_a_window_without_causality_is_refused(self):
        q, k, v = _qkv(128)
        for attn in (splash.splash_attention_gqa, flash_attention_gqa,
                     mha_reference):
            with pytest.raises(ValueError, match="window"):
                attn(q, k, v, causal=False, window=8)
            with pytest.raises(ValueError, match="window"):
                attn(q, k, v, window=0)

    def test_a_windowed_call_that_leaves_the_kernel_is_counted(self):
        """Off the TPU the call takes the in-tree path, window and all,
        and ``dlrover_attention_fallback_total{reason}`` says so."""
        def count():
            counter = tmetrics.REGISTRY.get("dlrover_attention_fallback_total")
            return sum(v for _n, key, v in (
                counter.samples() if counter else [])
                if dict(key).get("reason") == "backend")

        q, k, v = _qkv(256, seed=3)
        before = count()
        got = splash.splash_attention_gqa(q, k, v, window=64)
        assert count() == before + 1
        np.testing.assert_allclose(got, _explicit(q, k, v, 64), atol=1e-5)

    def test_on_a_tpu_an_untileable_windowed_shape_raises(self, monkeypatch):
        monkeypatch.setattr(splash, "pallas_interpret", lambda: False)
        q, k, v = _qkv(100)
        with pytest.raises(ValueError, match="cannot tile"):
            splash.splash_attention_gqa(q, k, v, window=8)

    def test_the_plan_counts_the_block_pairs_a_mask_keeps(self):
        causal = splash.mask_plan(8192)
        assert (causal["block_q"], causal["block_kv"]) == (1024, 1024)
        assert (causal["kept"], causal["block_pairs"]) == (36, 64)
        windowed = splash.mask_plan(8192, 2048)
        # the diagonal block and the two before it (the window's far edge
        # reaches into the third): 1 + 2 + 6 x 3
        assert windowed["kept"] == 21
        assert windowed["kept_share"] == 21 / 64
        assert splash.mask_plan(512, 2048)["kept"] == 1


# -- routing: top-8 of 128 ----------------------------------------------------


class TestRouting:
    def _scores(self, t=64, e=128, seed=0):
        return jax.nn.sigmoid(jax.random.normal(jax.random.key(seed), (t, e)))

    def test_weights_sum_to_the_route_scale(self):
        scores = self._scores()
        picks, weights = moe.route(scores, jnp.zeros(128), 8, 2.826, 1e-20)
        assert picks.shape == weights.shape == (64, 8)
        np.testing.assert_allclose(weights.sum(-1), 2.826, rtol=1e-6)
        top = np.sort(np.asarray(scores), -1)[:, -8:][:, ::-1]
        np.testing.assert_allclose(
            weights, 2.826 * top / top.sum(-1, keepdims=True), rtol=1e-6)

    def test_the_bias_moves_picks_and_not_weights(self):
        scores = self._scores(seed=1)
        bias = jnp.zeros(128).at[77].set(10.0)  # expert 77 wins every token
        picks, weights = moe.route(scores, bias, 8, 2.826, 1e-20)
        assert bool((picks == 77).any(-1).all())
        chosen = jnp.take_along_axis(scores, picks, -1)
        np.testing.assert_allclose(
            weights, 2.826 * chosen / chosen.sum(-1, keepdims=True),
            rtol=1e-6)
        grad = jax.grad(
            lambda b: moe.route(scores, b, 8, 2.826, 1e-20)[1].sum())(bias)
        assert not np.asarray(grad).any()

    def test_no_pair_is_lost_when_every_token_picks_one_expert(self):
        t, k = 64, 8
        picks = jnp.full((t, k), 21)  # in block 1 of 16
        order, position, sizes = moe.sort_pairs(picks, 16, 16)
        assert int(sizes.sum()) == t * k and int(sizes[5]) == t * k
        np.testing.assert_array_equal(np.sort(order), np.arange(t * k))
        np.testing.assert_array_equal(
            np.asarray(order)[position], np.arange(t * k))


# -- the routed layer with its shared expert ----------------------------------


def _layer_case(seed, h=256, m=128, e=128, k=8, t=512, dtype=jnp.bfloat16,
                **kw):
    kw = dict(dict(routed_scaling_factor=2.826, route_norm_eps=1e-20,
                   num_shared_experts=1), **kw)
    layer = moe.RoutedExperts(h, m, e, k, dtype=dtype, **kw)
    x = jax.random.normal(jax.random.key(seed), (1, t, h)).astype(dtype)
    params = nn.unbox(layer.init(jax.random.key(seed + 1), x))["params"]
    return layer, params, x


def _layer_cfg(k=8, shared=1, held=None, block=0):
    return dict(num_experts_per_tok=k, route_norm=True, route_scale=2.826,
                num_shared_experts=shared, num_experts=held,
                expert_block=block)


def _reference_layer(ref, params, x, **kw):
    cfg = _layer_cfg(**kw)
    cfg["num_experts"] = cfg["num_experts"] or params["gate_proj"].shape[0]
    return ref.routed_layer(cfg, params, x[0])


class TestRoutedLayer:
    # |layer - reference| over |reference|, 512 tokens, hidden 256, 128
    # experts of width 128, top-8, one shared expert, the inputs bf16 on
    # both sides.  Read on the CPU over four seeds: the layer (bf16
    # operands, float32 accumulation, float32 router, the shared expert
    # added in float32) 0.00571-0.00574; the same with the routed products'
    # partial sums in bf16, 16 terms a chunk, 0.00823-0.00830 (the shared
    # expert, computed as stated, is part of the sum and dilutes it: LFM2's
    # layer reads 0.0102 there); with a bf16 router 0.158-0.165 (a score
    # rounded to 8 bits swaps near-tied picks, and a swapped pick is a
    # whole expert's output).
    TOLERANCE = 0.007

    @pytest.mark.parametrize("seed", [0, 1])
    def test_bfloat16_compute_holds_to_the_reference(self, seed):
        layer, params, x = _layer_case(seed)
        out, sown = layer.apply({"params": params}, x,
                                mutable=["intermediates"])
        assert out.dtype == jnp.bfloat16
        want = _reference_layer(_reference(), params, x)
        assert _rel_l2(out[0], want) < self.TOLERANCE
        load = sown["intermediates"]["moe_load"][0]
        assert load.shape == (129,) and int(load.sum()) == 512 * 8

    @pytest.mark.parametrize("control", ["router", "accumulation"])
    def test_the_tolerance_fails_a_lower_precision(self, control,
                                                   monkeypatch):
        if control == "router":
            monkeypatch.setattr(moe, "router_scores", _bf16_router)
        else:
            monkeypatch.setattr(moe, "grouped_matmul", _bf16_accumulation)
        layer, params, x = _layer_case(0)
        out = layer.apply({"params": params}, x)
        want = _reference_layer(_reference(), params, x)
        assert _rel_l2(out[0], want) > self.TOLERANCE

    def test_eight_shares_and_the_shared_expert_once_add_up(self):
        """The share test of the model-configs guide: blocks 0-15, 16-31,
        ... 112-127, each computed by a layer that holds only its block
        and no shared expert, plus the shared expert's output counted
        once, add up to what the uncut reference gives for the layer."""
        ref = _reference()
        whole, params, x = _layer_case(2, h=64, m=32, t=96,
                                       dtype=jnp.float32)
        want = _reference_layer(ref, params, x)
        shared = ref.shared_expert(
            _layer_cfg(), {f"shared_{n}": jnp.asarray(
                params["shared"][f"{n}_proj"]["kernel"], jnp.float32)
                for n in ("gate", "up", "down")}, x[0])
        assert float(jnp.abs(shared).max()) > 1e-3
        total, pairs_here = shared, 0
        for block in range(8):
            share = moe.RoutedExperts(
                64, 32, 128, 8, experts_held=16, expert_block=block,
                routed_scaling_factor=2.826, route_norm_eps=1e-20,
                dtype=jnp.float32)
            held = {name: (value[16 * block:16 * (block + 1)]
                           if name.endswith("_proj") else value)
                    for name, value in params.items() if name != "shared"}
            out, sown = share.apply({"params": held}, x,
                                    mutable=["intermediates"])
            np.testing.assert_allclose(
                out[0], _reference_layer(
                    ref, held, x, shared=0, held=16, block=block),
                atol=2e-5, rtol=2e-5)
            load = sown["intermediates"]["moe_load"][0]
            assert load.shape == (17,) and int(load.sum()) == 96 * 8
            pairs_here += int(load[:16].sum())
            total = total + out[0]
        assert pairs_here == 96 * 8  # every pair is some block's
        np.testing.assert_allclose(total, want, atol=5e-5, rtol=5e-5)
        np.testing.assert_allclose(
            whole.apply({"params": params}, x)[0], want, atol=5e-5,
            rtol=5e-5)
        # a share WITH the shared expert is that share's layer output
        with_shared = moe.RoutedExperts(
            64, 32, 128, 8, experts_held=16, expert_block=3,
            routed_scaling_factor=2.826, route_norm_eps=1e-20,
            num_shared_experts=1, dtype=jnp.float32)
        held = {name: (value[48:64] if name.endswith("_proj") else value)
                for name, value in params.items()}
        np.testing.assert_allclose(
            with_shared.apply({"params": held}, x)[0],
            _reference_layer(ref, held, x, held=16, block=3),
            atol=2e-5, rtol=2e-5)

    def test_the_selection_bias_starts_at_zero_and_stays_out_of_the_gradient(
            self):
        """The bias is a leaf of zeros, and nothing but the selection sees
        it: no gradient reaches it.  What that leaves unrepaired: where
        every token's router input shares a common part three times the
        size of its own (what four norms a layer do at random weights), an
        expert's load spreads by more than half its mean, and no rule in
        the step evens it out."""
        own = jax.random.normal(jax.random.key(2), (1, 2048, 64))
        x = own + 3.0 * jax.random.normal(jax.random.key(7), (64,))
        layer = moe.RoutedExperts(
            64, 32, 32, 4, experts_held=8, dtype=jnp.float32)
        params = nn.unbox(layer.init(jax.random.key(1), x))["params"]
        assert not np.asarray(params["expert_bias"]).any()
        _, sown = layer.apply({"params": params}, x,
                              mutable=["intermediates"])
        picks = np.asarray(sown["intermediates"]["moe_picks"][0])
        load = np.bincount(picks.ravel(), minlength=32)
        assert load.sum() == 2048 * 4 and load.std() / load.mean() > 0.5
        grads = jax.grad(lambda p: layer.apply({"params": p}, x).sum())(params)
        assert not np.asarray(grads["expert_bias"]).any()
        assert np.asarray(grads["router"]).any()

    def test_every_token_on_one_block_loses_nothing(self):
        """A bias of 10 on experts 0-7 sends every pick of every token to
        them: eight groups of 96 rows, and the output is the reference's."""
        layer, params, x = _layer_case(3, h=64, m=32, e=16, t=96,
                                       dtype=jnp.float32)
        params = dict(params, expert_bias=jnp.zeros(16).at[:8].set(10.0))
        out, sown = layer.apply({"params": params}, x,
                                mutable=["intermediates"])
        np.testing.assert_array_equal(
            sown["intermediates"]["moe_load"][0], [96] * 8 + [0] * 9)
        np.testing.assert_allclose(
            out[0], _reference_layer(_reference(), params, x),
            atol=2e-5, rtol=2e-5)


# -- the model against the reference -----------------------------------------


def _reference_loss(ref, cfg, params, ids, labels):
    total = sum(ref.loss_of_row(_published(cfg), params, i, l)
                for i, l in zip(ids, labels))
    return total / labels.size


def _reference_logits(ref, cfg, params, ids):
    logits = jax.jit(
        lambda p, row: ref.logits_of_row(_published(cfg), p, row))
    return jnp.stack([logits(params, row) for row in ids])


# What each control leaves out of the program, as a change to the tiny
# configuration; the parameters stay the full model's (a module that is
# not built reads none of them).
_CONTROLS = {
    "the window ignored": dict(sliding_window=10 ** 6),
    "rotary positions on the global layer": dict(rope_layers=None),
    "rotary positions missing on a sliding layer": dict(rope_layers=()),
    "the gate left out": dict(attention_gate=False),
    "the shared expert left out": dict(num_shared_experts=0),
    "the post-norms left out": dict(sandwich_norm=False),
}


class TestModelAgainstTheReference:
    # float32 on both sides: what is left is the order of the sums (1e-4
    # on logits of magnitude ~3, as the Granite and LFM2 tests allow).
    ATOL = 1e-4

    @pytest.mark.parametrize("impl", ["dot", "splash"])
    @pytest.mark.parametrize("held, block", [(None, 0), (4, 2)],
                             ids=["uncut", "share"])
    def test_float32_logits_loss_and_gradients(self, held, block, impl):
        ref = _reference()
        cfg = HybridConfig.tiny_afmoe(
            dtype=jnp.float32, experts_held=held, expert_block=block,
            attention_impl=impl)
        model, params, ids, labels = _seeded(cfg)
        logits = model.apply({"params": params}, ids)
        want = _reference_logits(ref, cfg, params, ids)
        np.testing.assert_allclose(logits, want, atol=self.ATOL, rtol=1e-4)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: _program_loss(model, p, ids, labels)))(params)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: _reference_loss(ref, cfg, p, ids, labels)))(params)
        np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-4,
                                                    rtol=1e-4),
            grads, ref_grads)
        for name in ROUTED:
            experts = grads[name]["experts"]
            assert experts["expert_bias"].shape == (16,)
            assert not np.asarray(experts["expert_bias"]).any()
            assert float(jnp.abs(experts["router"]).max()) > 0
            assert float(jnp.abs(
                experts["shared"]["down_proj"]["kernel"]).max()) > 0

    @pytest.mark.parametrize("control", sorted(_CONTROLS))
    def test_a_program_that_leaves_a_piece_out_fails(self, control):
        """Each control is the program with one piece of the mathematics
        missing; the comparison that passes the program (``ATOL``) has to
        fail it, by a hundred times the tolerance at the least."""
        ref = _reference()
        cfg = HybridConfig.tiny_afmoe(dtype=jnp.float32)
        _, params, ids, _ = _seeded(cfg)
        want = _reference_logits(ref, cfg, params, ids)
        lacking = HybridModel(HybridConfig.tiny_afmoe(
            dtype=jnp.float32, **_CONTROLS[control]))
        got = lacking.apply({"params": params}, ids)
        assert float(jnp.abs(got - want).max()) > 100 * self.ATOL

    def test_bfloat16_compute_stays_in_its_band(self):
        """As for LFM2 (``tests/test_lfm2_moe.py``), the band is stated
        over the tokens whose picks agree with the reference's in every
        routed layer, the flips are counted and bounded, and the mean loss
        holds to 2^-7 relative.  The band is twice LFM2's (a tenth of the
        largest logit; read 0.075): a sandwich norm brings every branch's
        output to unit size before it joins the stream, so the 2^-8 a bf16
        branch is off by is 2^-8 of the stream eight times over, where a
        pre-norm model adds small branches' small errors."""
        ref, cfg = _reference(), HybridConfig.tiny_afmoe()
        model, params, ids, labels = _seeded(cfg, seed=5)
        logits, sown = model.apply({"params": params}, ids,
                                   mutable=["intermediates"])
        assert logits.dtype == jnp.bfloat16
        want = _reference_logits(ref, cfg, params, ids)
        agree = jnp.ones(ids.shape, bool)
        for n, name in enumerate(ROUTED):
            picks = sown["intermediates"][name]["experts"]["moe_picks"][0]
            ours = jnp.zeros((ids.size, 16), bool).at[
                jnp.arange(ids.size)[:, None], picks].set(True)
            theirs = jnp.stack([
                ref.picks_of_row(_published(cfg), params, row)[n]
                for row in ids]).reshape(ids.size, 16)
            agree &= (ours == theirs).all(-1).reshape(ids.shape)
        assert float(agree.mean()) > 0.8
        worst = jnp.abs(logits - want).max(-1)
        assert float(jnp.where(agree, worst, 0).max()) < 0.1 * float(
            jnp.abs(want).max())
        loss = _program_loss(model, params, ids, labels)
        ref_loss = _reference_loss(ref, cfg, params, ids, labels)
        assert abs(float(loss) - float(ref_loss)) < 2.0 ** -7 * float(ref_loss)

    def test_recomputation_changes_nothing(self):
        cfg = HybridConfig.tiny_afmoe(dtype=jnp.float32)
        model, params, ids, labels = _seeded(cfg, seed=7)
        remat = HybridModel(HybridConfig.tiny_afmoe(
            dtype=jnp.float32, remat_policy="full"))
        grads = jax.jit(jax.grad(
            lambda p: _program_loss(model, p, ids, labels)))(params)
        again = jax.jit(jax.grad(
            lambda p: _program_loss(remat, p, ids, labels)))(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6), grads,
            again)

    def test_window_and_segments_compose_in_the_model(self):
        """Attention-only layers: a packed row is two rows side by side."""
        cfg = HybridConfig.tiny_afmoe(dtype=jnp.float32)
        model, params, ids, _ = _seeded(cfg, b=1, s=32)
        seg = jnp.concatenate(
            [jnp.zeros((1, 16), jnp.int32), jnp.ones((1, 16), jnp.int32)], 1)
        positions = jnp.concatenate([jnp.arange(16), jnp.arange(16)])[None]
        packed = model.apply({"params": params}, ids, positions, seg)
        for half in (slice(0, 16), slice(16, 32)):
            alone = model.apply({"params": params}, ids[:, half])
            np.testing.assert_allclose(
                packed[:, half], alone, atol=1e-4, rtol=1e-4)


class TestModelContract:
    def test_the_parameter_tree(self):
        cfg = HybridConfig.tiny_afmoe(experts_held=4, expert_block=1)
        params = nn.unbox(jax.eval_shape(
            HybridModel(cfg).init, jax.random.key(0),
            jnp.zeros((1, 32), jnp.int32))["params"])
        assert set(params) == {
            "embed_tokens", "lm_head", "final_norm", "layers_0", "layers_1",
            "layers_2", "layers_3"}
        assert params["lm_head"]["kernel"].shape == (64, 256)
        norms = {"input_norm", "mixer_out_norm", "post_norm", "ffn_out_norm"}
        assert set(params["layers_0"]) == norms | {"attention", "mlp"}
        for name in ROUTED:
            assert set(params[name]) == norms | {"attention", "experts"}
        attention = params["layers_2"]["attention"]
        assert set(attention) == {"q_proj", "k_proj", "v_proj", "gate_proj",
                                  "o_proj", "q_norm", "k_norm"}
        # the head dim is a key of its own: 4 heads of 32 over 64
        assert attention["q_proj"]["kernel"].shape == (64, 4, 32)
        assert attention["gate_proj"]["kernel"].shape == (64, 4, 32)
        assert attention["k_proj"]["kernel"].shape == (64, 2, 32)
        assert attention["o_proj"]["kernel"].shape == (4, 32, 64)
        experts = params["layers_1"]["experts"]
        assert experts["router"].shape == (64, 16)
        assert experts["gate_proj"].shape == (4, 64, 32)  # the held block
        assert experts["shared"]["gate_proj"]["kernel"].shape == (64, 32)
        assert experts["shared"]["down_proj"]["kernel"].shape == (32, 64)

    def test_a_sliding_layer_needs_its_window(self):
        with pytest.raises(ValueError, match="sliding_window"):
            HybridConfig.tiny_afmoe(sliding_window=None)

    def test_causality_and_the_windows_reach(self):
        """Moving token 4 changes nothing before it; a model of sliding
        layers alone (window 8, four layers) cannot carry it past
        4 + 4 x 7 = 32, the global layer can."""
        cfg = HybridConfig.tiny_afmoe(dtype=jnp.float32)
        model, params, ids, _ = _seeded(cfg, s=64)
        moved_ids = ids.at[:, 4].set((ids[:, 4] + 1) % 256)
        base = model.apply({"params": params}, ids)
        moved = model.apply({"params": params}, moved_ids)
        np.testing.assert_allclose(base[:, :4], moved[:, :4], atol=1e-5)
        assert float(jnp.abs(base[:, 40:] - moved[:, 40:]).max()) > 1e-4
        local = HybridModel(HybridConfig.tiny_afmoe(
            dtype=jnp.float32, layer_types=("sliding_attention",) * 4))
        base = local.apply({"params": params}, ids)
        moved = local.apply({"params": params}, moved_ids)
        assert float(jnp.abs(base[:, 4:33] - moved[:, 4:33]).max()) > 1e-4
        np.testing.assert_allclose(base[:, 33:], moved[:, 33:], atol=1e-5)

    def test_the_named_scopes_reach_the_compiled_program(self):
        cfg = HybridConfig.tiny_afmoe(dtype=jnp.float32)
        model, params, ids, _ = _seeded(cfg)
        compiled = jax.jit(model.apply).lower(
            {"params": params}, ids).compile()
        text = compiled.as_text()
        for scope in ("attn/sliding", "attn/full", "attn/gate", "moe/shared",
                      "moe/router", "moe/sort", "moe/gate_up", "moe/down",
                      "moe/combine", "hybrid/attention", "hybrid/mlp",
                      "hybrid/head"):
            assert scope in text, scope

    def test_each_lowering_leaves_a_span_in_the_telemetry_directory(
            self, tmp_path, monkeypatch):
        end = _lower_span(
            tmp_path, monkeypatch, HybridConfig.tiny_afmoe(experts_held=4),
            (2, 32))
        assert end["layer_types"] == {
            "sliding_attention": 3, "full_attention": 1}
        assert (end["head_dim"], end["sliding_window"]) == (32, 8)
        assert end["rope_layers"] == ["sliding_attention"]
        assert end["attention_masks"] == {
            "full_attention": {
                "block_q": 32, "block_kv": 32, "block_pairs": 1, "kept": 1,
                "kept_share": 1.0},
            "sliding_attention": {
                "block_q": 32, "block_kv": 32, "block_pairs": 1, "kept": 1,
                "kept_share": 1.0}}
        assert (end["num_experts"], end["experts_held"], end["top_k"],
                end["num_shared_experts"]) == (16, 4, 4, 1)
        assert end["pairs_rows"] == 2 * 32 * 4 and end["routed_layers"] == 3
        assert end["gmm_gate_up"] == end["gmm_down"] == {
            "path": "ragged_dot", "tiling": None}

    @pytest.mark.parametrize("backend, overrides, kept", [
        ("tpu", dict(remat_policy="full", attention_impl="splash"), 4),
        ("tpu", dict(remat_policy="none", attention_impl="splash"), 0),
        ("tpu", dict(remat_policy="full", attention_impl="dot"), 0),
        # off the TPU the call takes the in-tree kernel: recomputed whole
        ("cpu", dict(remat_policy="full", attention_impl="splash"), 0),
    ])
    def test_the_span_counts_the_layers_whose_forward_kernel_runs_once(
            self, tmp_path, monkeypatch, backend, overrides, kept):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        end = _lower_span(
            tmp_path, monkeypatch,
            HybridConfig.tiny_afmoe(experts_held=4, **overrides), (2, 128))
        # out in bf16 and logsumexp in f32 over (batch 2, 4 heads, 128
        # tokens) at head dim 32, for each layer
        assert (end["attention_kept"], end["attention_kept_bytes"]) == (
            kept, kept * 2 * 4 * 128 * (32 * 2 + 4))


# Where each rule table puts a new parameter's dimensions, by logical axis.
_NEW_PARAMETERS = {
    ("attention", "gate_proj"): ("embed", "heads", "head_dim"),
    ("experts", "shared", "gate_proj"): ("embed", "mlp"),
    ("experts", "shared", "up_proj"): ("embed", "mlp"),
    ("experts", "shared", "down_proj"): ("mlp", "embed"),
    ("mixer_out_norm", "scale"): ("embed",),
    ("ffn_out_norm", "scale"): ("embed",),
    ("lm_head",): ("embed", "vocab"),
}


@pytest.mark.parametrize("preset, mesh_cfg", [
    ("dp", MeshConfig(dp=8)),
    ("fsdp", MeshConfig(dp=2, fsdp=4)),
    ("fsdp_tp", MeshConfig(dp=2, fsdp=2, tp=2)),
    ("3d", MeshConfig(dp=1, fsdp=2, tp=2, ep=2)),
])
def test_state_initialises_and_steps_sharded_by_rule(devices8, preset,
                                                     mesh_cfg):
    cfg = HybridConfig.tiny_afmoe(dtype=jnp.float32)
    model = HybridModel(cfg)
    mesh = build_mesh(mesh_cfg, devices8)
    rules = PRESET_RULES[preset]
    table = dict(rules)
    ids = jax.random.randint(jax.random.key(0), (8, 33), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    state, shardings = create_sharded_state(
        model, optax.adamw(1e-3), mesh, rules, jax.random.key(1), batch)
    tree = dict(state.params["layers_1"], lm_head=state.params["lm_head"])
    for path, axes in _NEW_PARAMETERS.items():
        leaf = tree
        for key in path:
            leaf = leaf[key]
        leaf = leaf["kernel"] if isinstance(leaf, dict) else leaf
        # every logical axis is in the table by rule, not by omission
        assert all(axis in table for axis in axes), path
        spec = tuple(leaf.sharding.spec) + (None,) * (
            len(axes) - len(leaf.sharding.spec))
        assert spec == tuple(table[axis] for axis in axes), (path, spec)
    bias_before = np.asarray(
        state.params["layers_1"]["experts"]["expert_bias"])
    step = make_train_step(model, mesh, rules, shardings)
    batch = jax.device_put(batch, data_sharding(mesh, rules))
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert sorted(metrics["moe_load"]) == [
        f"{name}/experts" for name in ROUTED]
    for load in metrics["moe_load"].values():
        assert load.shape == (17,) and int(load.sum()) == 8 * 32 * 4
    # no gradient reaches the selection bias and the step has no other
    # rule for it (load_balance_coeff is read by nothing): three steps
    # leave it at the zeros it started from
    assert not np.asarray(bias_before).any()
    assert not np.asarray(
        state.params["layers_1"]["experts"]["expert_bias"]).any()


# -- the families that were there ----------------------------------------------

# sha256 (first 16 hex digits) of the lowered loss-and-gradient program
# (StableHLO without locations) and of the parameter tree's paths and
# shapes, computed at the parent commit (42ddf91) with the installed jax
# 0.9.0: the fields this family added leave Granite's and LFM2's programs
# as they were.  A new jax may print the same program differently; then
# recompute both on the commit before the upgrade.
_BEFORE = {
    "tiny": ("d5d10b41eb248e18", "293e587a6c4c2b90"),
    "tiny_lfm2": ("2abd251cf8208dc5", "40447323c24d6b27"),
}


@pytest.mark.parametrize("family", sorted(_BEFORE))
def test_the_other_families_programs_are_what_they_were(family):
    model = HybridModel(getattr(HybridConfig, family)())
    ids = jnp.zeros((2, 32), jnp.int32)
    params = nn.unbox(jax.eval_shape(
        model.init, jax.random.key(0), ids))["params"]

    def loss(p, ids):
        return cross_entropy_loss(model.apply({"params": p}, ids), ids)

    text = jax.jit(jax.value_and_grad(loss)).lower(params, ids).as_text()
    paths = sorted(
        jax.tree_util.keystr(path) + str(leaf.shape) for path, leaf in
        jax.tree_util.tree_flatten_with_path(params)[0])
    digest = (hashlib.sha256(text.encode()).hexdigest()[:16],
              hashlib.sha256("\n".join(paths).encode()).hexdigest()[:16])
    assert digest == _BEFORE[family]
