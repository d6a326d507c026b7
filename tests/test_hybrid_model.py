"""The hybrid decoder (``models/hybrid.py``), its scan and convolution
(``ops/ssd.py``) and splash attention's scale, on the CPU at a small size:
hidden 64, 4 state-space heads x 16, state 16, chunk 8, 32 tokens, layers
``mamba, attention, mamba``, vocabulary 256, seeded random weights.  The
model is held against the benchmark's plain reference
(``benchmarks/ref/granite_hybrid.py``), which shares no code with it."""

import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models.hybrid import HybridConfig, HybridModel
from dlrover_tpu.models.llama import cross_entropy_loss
from dlrover_tpu.ops import ssd
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import PRESET_RULES
from dlrover_tpu.trainer.step import (
    create_sharded_state,
    data_sharding,
    make_train_step,
)

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(CHECKOUT, "benchmarks", "ref", "granite_hybrid.py")
    spec = importlib.util.spec_from_file_location("granite_hybrid_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _published(cfg):
    """The tiny configuration under the reference's (published) key names."""
    return dict(
        rms_norm_eps=cfg.rms_norm_eps, layer_types=list(cfg.layer_types),
        residual_multiplier=cfg.residual_multiplier,
        embedding_multiplier=cfg.embedding_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling, mamba_n_heads=cfg.ssm_heads,
        mamba_d_head=cfg.ssm_head_dim,
    )


def _scan_inputs(seed=0, b=2, s=32, h=4, p=16, n=16, dt_range=(1e-3, 1e-1)):
    keys = jax.random.split(jax.random.key(seed), 5)
    lo, hi = np.log(dt_range[0]), np.log(dt_range[1])
    return dict(
        x=jax.random.normal(keys[0], (b, s, h, p)),
        dt=jnp.exp(jax.random.uniform(keys[1], (b, s, h), minval=lo,
                                      maxval=hi)),
        A=-jax.random.uniform(keys[2], (h,), minval=1.0, maxval=16.0),
        B=jax.random.normal(keys[3], (b, s, n)),
        C=jax.random.normal(keys[4], (b, s, n)),
    )


def _per_token(x, dt, A, B, C):
    """S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x) X_t; y_t = C_t . S_t."""
    b, s, h, p = x.shape
    state = jnp.zeros((b, h, B.shape[-1], p))
    ys = []
    for t in range(s):
        decay = jnp.exp(dt[:, t] * A)[..., None, None]
        state = decay * state + jnp.einsum(
            "bh,bn,bhp->bhnp", dt[:, t], B[:, t], x[:, t])
        ys.append(jnp.einsum("bn,bhnp->bhp", C[:, t], state))
    return jnp.stack(ys, 1)


def _weighted(fn, weights):
    return lambda kw: jnp.sum(fn(**kw) * weights)


class TestScan:
    @pytest.mark.parametrize("chunk", [4, 8, 16])
    def test_values_and_gradients_match_the_recurrence(self, chunk):
        inputs = _scan_inputs()
        weights = jax.random.normal(jax.random.key(9), inputs["x"].shape)
        chunked = lambda **kw: ssd.ssd_chunked(chunk=chunk, **kw)
        np.testing.assert_allclose(
            chunked(**inputs), _per_token(**inputs), atol=1e-5, rtol=1e-5)
        got = jax.grad(_weighted(chunked, weights))(inputs)
        want = jax.grad(_weighted(_per_token, weights))(inputs)
        for name in ("x", "dt", "A", "B", "C"):
            np.testing.assert_allclose(
                got[name], want[name], atol=1e-5, rtol=1e-5, err_msg=name)

    def test_chunk_sizes_give_one_answer(self):
        inputs = _scan_inputs(seed=1)
        by_chunk = [ssd.ssd_chunked(chunk=c, **inputs) for c in (4, 8, 16)]
        for other in by_chunk[1:]:
            np.testing.assert_allclose(by_chunk[0], other, atol=1e-5)

    @pytest.mark.parametrize("dt_range", [(1e-4, 2e-4), (5.0, 10.0)],
                             ids=["smallest-steps", "largest-steps"])
    def test_gradients_are_finite_at_both_ends_of_the_step(self, dt_range):
        """A step near 0 barely decays; one of 10 at A = -16 decays by
        exp(-160) a token, exp(-1280) a chunk: both underflow or sit at 1,
        neither may give a NaN (the mask comes before the exponential)."""
        inputs = _scan_inputs(seed=2, dt_range=dt_range)
        weights = jnp.ones(inputs["x"].shape)
        chunked = lambda **kw: ssd.ssd_chunked(chunk=8, **kw)
        grads = jax.grad(_weighted(chunked, weights))(inputs)
        for name, g in grads.items():
            assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(
            chunked(**inputs), _per_token(**inputs), atol=1e-5, rtol=1e-4)

    def test_bf16_operands_keep_the_decay_in_float32(self):
        """bf16 matmul operands move the output by bf16's rounding; a bf16
        cumulative sum of the decay moves it by far more."""
        inputs = _scan_inputs(seed=3, s=64)
        want = _per_token(**inputs)
        half = {k: (v.astype(jnp.bfloat16) if k in ("x", "B", "C") else v)
                for k, v in inputs.items()}
        got = ssd.ssd_chunked(chunk=16, **half).astype(jnp.float32)
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got - want).max()) < 2e-2 * scale

    def test_a_sequence_that_is_no_multiple_of_the_chunk_raises(self):
        inputs = _scan_inputs(s=30)
        with pytest.raises(ValueError, match="not a multiple of the chunk"):
            ssd.ssd_chunked(chunk=8, **inputs)


def test_causal_conv_matches_shifted_sums():
    keys = jax.random.split(jax.random.key(4), 3)
    x = jax.random.normal(keys[0], (2, 12, 6))
    weight = jax.random.normal(keys[1], (4, 6))
    bias = jax.random.normal(keys[2], (6,))
    x_np, w_np = np.asarray(x), np.asarray(weight)
    want = np.zeros_like(x_np) + np.asarray(bias)
    for t in range(12):
        for back in range(4):  # the last tap reads the current token
            if t - back >= 0:
                want[:, t] += w_np[3 - back] * x_np[:, t - back]
    got = ssd.causal_conv1d(x, weight, bias)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # causal: a later token changes no earlier output
    moved = ssd.causal_conv1d(x.at[:, 7].add(1.0), weight, bias)
    np.testing.assert_array_equal(moved[:, :7], got[:, :7])


def _seeded(cfg, seed=0, b=2, s=32):
    """Model, ids, labels, and parameters with every leaf random (the
    initialisers leave norms at 1 and D at 1, which would hide a swap)."""
    model = HybridModel(cfg)
    ids = jax.random.randint(jax.random.key(seed), (b, s + 1), 0,
                             cfg.vocab_size)
    params = nn.unbox(model.init(jax.random.key(seed + 1), ids[:, :-1]))[
        "params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 2), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)])
    return model, params, ids[:, :-1], ids[:, 1:]



def _lower_span(tmp_path, monkeypatch, cfg, shape):
    """The one ``lower`` span that tracing ``HybridModel(cfg)`` over ids of
    ``shape`` leaves in a telemetry directory."""
    from dlrover_tpu.telemetry import events

    log = events.EventLog(directory=str(tmp_path))
    monkeypatch.setattr(events, "emit", log.emit)
    jax.eval_shape(HybridModel(cfg).init, jax.random.key(0),
                   jnp.zeros(shape, jnp.int32))
    end, = [e for e in events.read_dir(str(tmp_path))
            if e["ev"] == "span_end" and e.get("name") == "lower"]
    return end


def _program_loss(model, params, ids, labels):
    return cross_entropy_loss(model.apply({"params": params}, ids), labels)


def _reference_loss(ref, cfg, params, ids, labels):
    total = sum(ref.loss_of_row(_published(cfg), params, i, l)
                for i, l in zip(ids, labels))
    return total / labels.size


class TestModelAgainstTheReference:
    def test_float32_logits_loss_and_gradients(self):
        ref, cfg = _reference(), HybridConfig.tiny(dtype=jnp.float32)
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

    def test_bfloat16_compute_stays_in_its_band(self):
        """bf16 keeps 8 significant bits: logits of magnitude ~1 may move
        by a few 2^-8 through three layers; the mean loss averages the
        rounding out and holds to 2^-7 relative (chip_smoke.py's band for
        two programs' losses)."""
        ref, cfg = _reference(), HybridConfig.tiny()
        model, params, ids, labels = _seeded(cfg, seed=5)
        logits = model.apply({"params": params}, ids)
        assert logits.dtype == jnp.bfloat16  # the loss upcasts
        want = jnp.stack(
            [ref.logits_of_row(_published(cfg), params, row) for row in ids])
        assert float(jnp.abs(logits - want).max()) < 0.05 * float(
            jnp.abs(want).max())
        loss = _program_loss(model, params, ids, labels)
        ref_loss = _reference_loss(ref, cfg, params, ids, labels)
        assert abs(float(loss) - float(ref_loss)) < 2.0 ** -7 * float(ref_loss)

    def test_recomputation_changes_nothing(self):
        cfg = HybridConfig.tiny(dtype=jnp.float32)
        model, params, ids, labels = _seeded(cfg, seed=7)
        remat = HybridModel(HybridConfig.tiny(dtype=jnp.float32,
                                              remat_policy="full"))
        grads = jax.grad(
            lambda p: _program_loss(model, p, ids, labels))(params)
        again = jax.grad(
            lambda p: _program_loss(remat, p, ids, labels))(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6), grads,
            again)


class TestModelContract:
    def test_layer_types_from_a_json_list_hash(self):
        cfg = HybridConfig.tiny(layer_types=["mamba", "attention"])
        assert cfg.layer_types == ("mamba", "attention")
        hash(cfg)
        with pytest.raises(ValueError, match="layer_types"):
            HybridConfig.tiny(layer_types=["mamba", "moe"])

    def test_segment_ids_raise_where_a_mamba_layer_would_leak_state(self):
        cfg = HybridConfig.tiny(dtype=jnp.float32)
        model, params, ids, _ = _seeded(cfg)
        with pytest.raises(ValueError, match="segment_ids"):
            model.apply({"params": params}, ids, None, jnp.ones_like(ids))
        # ...and pass through where every layer is attention
        attn = HybridModel(HybridConfig.tiny(
            dtype=jnp.float32, layer_types=("attention",)))
        variables = attn.init(jax.random.key(0), ids)
        seg = jnp.concatenate(
            [jnp.ones_like(ids[:, :16]), 2 * jnp.ones_like(ids[:, 16:])], 1)
        packed = attn.apply(variables, ids, None, seg)
        alone = attn.apply(variables, ids[:, 16:])
        np.testing.assert_allclose(packed[:, 16:], alone, atol=1e-5)

    def test_a_row_that_is_no_multiple_of_the_chunk_raises(self):
        cfg = HybridConfig.tiny(dtype=jnp.float32)
        model = HybridModel(cfg)
        with pytest.raises(ValueError, match="not a multiple of the chunk"):
            model.init(jax.random.key(0), jnp.zeros((1, 30), jnp.int32))

    def test_unbuilt_choices_are_refused_by_name(self):
        with pytest.raises(ValueError, match="attention_impl"):
            HybridConfig.tiny(attention_impl="ring")
        with pytest.raises(ValueError, match="ssm_groups"):
            HybridConfig.tiny(ssm_groups=2)
        with pytest.raises(ValueError, match="layer_types"):
            HybridConfig.tiny(layer_types=("mamba", "linear"))

    def test_no_multiplier_means_one_over_sqrt_head_dim(self):
        ids = jax.random.randint(jax.random.key(0), (1, 32), 0, 256)
        default = HybridConfig.tiny(
            dtype=jnp.float32, attention_multiplier=None)
        variables = HybridModel(default).init(jax.random.key(1), ids)
        given = HybridConfig.tiny(dtype=jnp.float32, attention_multiplier=1 / 4)
        np.testing.assert_allclose(
            HybridModel(default).apply(variables, ids),
            HybridModel(given).apply(variables, ids), atol=1e-6)

    def test_the_named_scopes_reach_the_lowered_program(self):
        """What a device trace is cut by: every piece of the scan and of
        the layers around it is named in the program's op metadata."""
        cfg = HybridConfig.tiny(dtype=jnp.float32)
        model, params, ids, _ = _seeded(cfg)
        lowered = jax.jit(model.apply).lower({"params": params}, ids)
        text = lowered.as_text(debug_info=True)
        for scope in ("mamba/in_proj", "mamba/conv", "ssd/diag",
                      "ssd/chunk_state", "ssd/recurrence", "ssd/state_out",
                      "mamba/gated_norm", "mamba/out_proj",
                      "hybrid/attention", "hybrid/mlp", "hybrid/head"):
            assert scope in text, scope

    def test_causality(self):
        cfg = HybridConfig.tiny(dtype=jnp.float32)
        model, params, ids, _ = _seeded(cfg)
        base = model.apply({"params": params}, ids)
        moved = model.apply(
            {"params": params}, ids.at[:, 20].set((ids[:, 20] + 1) % 256))
        np.testing.assert_allclose(base[:, :20], moved[:, :20], atol=1e-5)
        assert float(jnp.abs(base[:, 20:] - moved[:, 20:]).max()) > 1e-4

    def test_each_lowering_leaves_a_span_in_the_telemetry_directory(
            self, tmp_path, monkeypatch):
        end = _lower_span(
            tmp_path, monkeypatch, HybridConfig.tiny(dtype=jnp.float32),
            (1, 32))
        assert end["what"] == "hybrid"
        assert end["layer_types"] == {"mamba": 2, "attention": 1}
        assert (end["chunk"], end["n_chunks"]) == (8, 4)
        assert (end["attention_impl"], end["head_dim"]) == ("dot", 16)
        assert (end["attention_kept"], end["attention_kept_bytes"]) == (0, 0)

    @pytest.mark.parametrize("overrides, kept", [
        (dict(remat_policy="full", attention_impl="splash"), 1),
        (dict(remat_policy="none", attention_impl="splash"), 0),
        (dict(remat_policy="full", attention_impl="dot"), 0),
    ])
    def test_the_span_counts_the_layers_whose_forward_kernel_runs_once(
            self, tmp_path, monkeypatch, overrides, kept):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        end = _lower_span(
            tmp_path, monkeypatch, HybridConfig.tiny(**overrides), (1, 128))
        # one attention layer of three: out in bf16 and logsumexp in f32
        # over (batch 1, 4 heads, 128 tokens) at head dim 16
        assert (end["attention_kept"], end["attention_kept_bytes"]) == (
            kept, kept * 4 * 128 * (16 * 2 + 4))


# Where each rule table puts a state-space parameter's sharded dimension:
# (mesh axis of dim 0, mesh axis of dim 1), None for a dimension kept whole.
_NEW_PARAMETERS = {
    "z_proj": ("embed", "ssm_inner"), "x_proj": ("embed", "ssm_inner"),
    "b_proj": ("embed", "ssm_state"), "c_proj": ("embed", "ssm_state"),
    "dt_proj": ("embed", "ssm_heads"), "out_proj": ("ssm_inner", "embed"),
    "conv_x": ("conv_width", "ssm_inner"), "conv_b": ("conv_width", "ssm_state"),
    "conv_c": ("conv_width", "ssm_state"), "conv_x_bias": ("ssm_inner",),
    "conv_b_bias": ("ssm_state",), "conv_c_bias": ("ssm_state",),
    "dt_bias": ("ssm_heads",), "A_log": ("ssm_heads",), "D": ("ssm_heads",),
    "norm": ("ssm_inner",),
}


@pytest.mark.parametrize("preset, mesh_cfg", [
    ("dp", MeshConfig(dp=4)),
    ("fsdp", MeshConfig(dp=1, fsdp=4)),
    ("fsdp_tp", MeshConfig(dp=1, fsdp=2, tp=2)),
])
def test_state_initialises_and_steps_sharded_by_rule(devices8, preset,
                                                     mesh_cfg):
    cfg = HybridConfig.tiny(dtype=jnp.float32)
    model = HybridModel(cfg)
    mesh = build_mesh(mesh_cfg, devices8[:4])
    rules = PRESET_RULES[preset]
    table = dict(rules)
    ids = jax.random.randint(jax.random.key(0), (4, 33), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    state, shardings = create_sharded_state(
        model, optax.adamw(1e-3), mesh, rules, jax.random.key(1), batch)
    mamba = state.params["layers_0"]["mamba"]
    assert set(mamba) == set(_NEW_PARAMETERS)
    for name, axes in _NEW_PARAMETERS.items():
        leaf = mamba[name]["kernel"] if name.endswith("_proj") else mamba[name]
        want = tuple(table[axis] for axis in axes)
        got = tuple(leaf.sharding.spec) + (None,) * (
            len(axes) - len(leaf.sharding.spec))
        assert got == want, (name, got, want)
        # every logical axis is in the table by rule, not by omission
        assert all(axis in table for axis in axes), name
    step = make_train_step(model, mesh, rules, shardings)
    batch = jax.device_put(batch, data_sharding(mesh, rules))
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


class TestSplashScale:
    def _qkv(self, s=128, h=4, h_kv=2, d=64):
        rng = np.random.RandomState(0)
        return tuple(
            jnp.asarray(rng.normal(size=(1, s, n, d)), jnp.float32)
            for n in (h, h_kv, h_kv))

    def _plain(self, q, k, v, scale):
        k, v = (jnp.repeat(t, q.shape[2] // t.shape[2], axis=2)
                for t in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = q.shape[1]
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    @pytest.mark.parametrize("interpret", [None, True],
                             ids=["in-tree", "library-kernel"])
    def test_default_is_unchanged_and_a_given_scale_is_honoured(
            self, interpret):
        from dlrover_tpu.ops.splash_attention import splash_attention_gqa

        q, k, v = self._qkv()
        attend = lambda **kw: splash_attention_gqa(
            q, k, v, block_q=128, block_kv=128, interpret=interpret, **kw)
        np.testing.assert_allclose(
            attend(), self._plain(q, k, v, 1 / 8), atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(
            attend(scale=1 / 64), self._plain(q, k, v, 1 / 64), atol=2e-5,
            rtol=2e-5)
        assert float(jnp.abs(attend() - attend(scale=1 / 64)).max()) > 1e-2

    def test_the_model_hands_its_multiplier_to_splash(self):
        cfg = HybridConfig.tiny(dtype=jnp.float32, layer_types=("attention",))
        ids = jax.random.randint(jax.random.key(0), (1, 128), 0, 256)
        variables = HybridModel(cfg).init(jax.random.key(1), ids)
        dot = HybridModel(cfg).apply(variables, ids)
        splash = HybridModel(HybridConfig.tiny(
            dtype=jnp.float32, layer_types=("attention",),
            attention_impl="splash")).apply(variables, ids)
        np.testing.assert_allclose(splash, dot, atol=1e-4, rtol=1e-4)
