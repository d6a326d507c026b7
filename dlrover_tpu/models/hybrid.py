"""Hybrid decoder: a layer pattern of mixers (Mamba-2 state-space layers,
gated short convolutions, attention) with a dense or a routed-expert FFN
behind each.

``HybridConfig.layer_types`` names each layer's mixer in the published
spellings: ``"mamba"`` and ``"attention"`` (Granite-4.0-H), ``"conv"`` and
``"full_attention"`` (LFM2), ``"sliding_attention"`` and
``"full_attention"`` (AFMoE); the layers are unrolled, each its own
parameters.  Three families are written down here.  The fields that tell
them apart default to Granite's, so its program is what it was.

**AFMoE** (``model_type: afmoe``, Trinity).  ``h = sqrt(hidden) * E[ids]``
(``embedding_multiplier``); a layer is ``h += N2(attn(N1(h)))`` then ``h +=
N4(ffn(N3(h)))``: four RMSNorms a layer (``sandwich_norm``; N1
``input_norm``, N2 ``mixer_out_norm``, N3 ``post_norm``, N4
``ffn_out_norm``); the head is its own matrix (``tie_word_embeddings``
False).  Attention at ``head_dim`` (a key of its own: 32 heads of 128 over
a hidden size of 2048), RMSNorm on q and k (``qk_norm``), an output gate
``out = W_o (attn * sigmoid(W_g n))`` (``attention_gate``).  A
``sliding_attention`` layer has rotary positions and sees the last
``sliding_window`` keys, the query's own counted; a ``full_attention``
layer is causal and has **no position term** (``rope_layers`` names the
kinds that carry one).  FFN: dense, then the routed layer with
``num_shared_experts`` shared experts computed for every token beside the
routed ones (``models/moe.py``).

**LFM2** (``model_type: lfm2_moe``).  Every multiplier is 1 and the head is
the embedding, tied.  ``conv`` mixer: ``B, C, x`` projected from the hidden
state (no bias), ``u = B * x``, a depthwise causal convolution of
``conv_width`` taps with neither bias nor activation, ``out = W_out (C *
conv(u))``.  ``full_attention``: grouped-query attention with an RMSNorm
over the head dim on q and on k (``qk_norm``, each its own scale) before
half-split rotary positions (``rope_theta``), scores scaled by
``1 / sqrt(head_dim)``.  FFN: the gated MLP of ``intermediate_size`` in the
first ``num_dense_layers`` layers, after them the dropless routed-expert
layer of ``models/moe.py`` (``num_experts`` router outputs, top
``num_experts_per_token``, ``moe_intermediate_size`` wide, the block
``expert_block`` of ``experts_held`` experts held here).

**Granite-4.0-H** (``model_type: granitemoehybrid``, dense):

* ``h = embedding_multiplier * E[ids]``; a layer is
  ``h += residual_multiplier * mixer(RMSNorm(h))`` then
  ``h += residual_multiplier * MLP(RMSNorm(h))`` (the gated MLP of
  ``models/llama.py``); ``logits = RMSNorm(h) E^T / logits_scaling``: the
  head is the embedding, tied, and the logits come out in ``dtype`` (the
  loss upcasts).
* attention mixer: grouped-query, no bias, **no rotary or other position
  term**, scores scaled by ``attention_multiplier`` (not ``1/sqrt(d)``).
* Mamba-2 mixer: ``z, x, B, C, dt`` projected from the hidden state, no
  bias; a depthwise causal convolution with bias and SiLU over ``x, B, C``; the scan of
  ``ops/ssd.py`` with ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``+ D * x``; ``RMSNorm(y * silu(z))`` over the whole inner width (gate
  before the norm); output projection.  One group: ``B`` and ``C`` are
  shared by all heads.

The five input projections are separate parameters (``z_proj``, ``x_proj``,
``b_proj``, ``c_proj``, ``dt_proj``: the published ``in_proj`` cut at its
own boundaries), as are the convolution's three channel ranges, so that a
rule table can shard the inner width and the heads over ``tp`` and leave
``B`` and ``C`` whole; the short convolution's ``b_proj``, ``c_proj`` and
``x_proj`` are cut the same way.  Compute is ``dtype`` (bf16), parameters float32,
the decay and the states float32 (``ops/ssd.py``).

``segment_ids`` raises in a model with mamba or conv layers: the scan's
state and the convolution's taps are not reset at packed document
boundaries, so a packed row would leak one document into the next.
"""

import dataclasses
import math
from collections import Counter
from functools import partial
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import (
    MLP,
    Dtype,
    RMSNorm,
    _masked_attention,
    _rope,
    param_with_axes,
    remat_policy,
)
from dlrover_tpu.models.moe import ROUTED_OUT, RoutedExperts, small_buffer
from dlrover_tpu.ops import grouped_matmul, ssd
from dlrover_tpu.ops.splash_attention import (
    KERNEL_RESULTS,
    kept_bytes,
    mask_plan,
    splash_attention_gqa,
)
from dlrover_tpu.parallel.sharding import constrain

ATTENTION_KINDS = ("attention", "full_attention", "sliding_attention")
LAYER_KINDS = ("mamba", "conv") + ATTENTION_KINDS


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    layer_types: Tuple[str, ...] = ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None  # None: 1 / sqrt(head_dim)
    logits_scaling: float = 1.0
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_chunk: int = 256
    conv_width: int = 4
    # Attention's position term and q/k norm: none in Granite-4.0-H.
    rope_theta: Optional[float] = None  # None: no rotary positions
    # The attention kinds that carry them; None: every one (LFM2).
    rope_layers: Optional[Tuple[str, ...]] = None
    qk_norm: bool = False
    head_dim: Optional[int] = None  # None: hidden_size // num_heads
    # What a "sliding_attention" layer sees: the last ``sliding_window``
    # keys, the query's own position counted.
    sliding_window: Optional[int] = None
    attention_gate: bool = False  # out = W_o (attn * sigmoid(W_g n))
    # Four norms a layer: the mixer's and the FFN's outputs are normed
    # before they join the residual.
    sandwich_norm: bool = False
    tie_word_embeddings: bool = True
    # FFN by layer: dense everywhere unless ``num_experts``; then the first
    # ``num_dense_layers`` are dense and the rest routed (models/moe.py).
    num_dense_layers: int = 0
    num_experts: int = 0  # the router's outputs, the whole model's experts
    num_experts_per_token: int = 0
    moe_intermediate_size: int = 0
    experts_held: Optional[int] = None  # None: all of them
    expert_block: int = 0  # which block of ``experts_held`` is held here
    routed_scaling_factor: float = 1.0
    route_norm_eps: float = 1e-6  # in the picks' normaliser
    num_shared_experts: int = 0  # beside the routed ones, for every token
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    attention_impl: str = "dot"  # dot | splash
    # models/llama.py::remat_policy.  Whatever that recomputes, a layer
    # also keeps a routed FFN's output (one row a token) and, where its
    # attention is the splash kernel, the kernel's output (a row a token
    # at the heads' width) and log-sum-exp (a float a head and token):
    # ``recompute_policy``.  So under "full" an attention layer holds two
    # rows a token where the layer's input alone was one.
    remat_policy: str = "none"

    def __post_init__(self):
        # A JSON list arrives through a configuration file; a flax module
        # attribute has to hash.
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.rope_layers is not None:
            object.__setattr__(self, "rope_layers", tuple(self.rope_layers))
        if "sliding_attention" in self.layer_types and not (
                self.sliding_window and self.sliding_window > 0):
            raise ValueError(
                "a sliding_attention layer needs sliding_window > 0; got "
                f"{self.sliding_window}")
        unknown = set(self.layer_types) - set(LAYER_KINDS)
        if unknown:
            raise ValueError(
                f"layer_types holds {sorted(unknown)}; known: {LAYER_KINDS}")
        if self.attention_impl not in ("dot", "splash"):
            raise ValueError(
                "HybridModel's attention takes its scale from the "
                "configuration, which only 'dot' and 'splash' accept; got "
                f"attention_impl={self.attention_impl!r}")
        if self.ssm_groups != 1:
            raise ValueError(
                f"ssm_groups={self.ssm_groups}: only one group of B and C "
                "shared by all heads is built")
        if self.num_experts and not (
                0 < self.num_experts_per_token <= self.num_experts
                and self.moe_intermediate_size > 0):
            raise ValueError(
                f"num_experts={self.num_experts} needs "
                "num_experts_per_token and moe_intermediate_size")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def window(self, kind: str) -> Optional[int]:
        """The sliding window of a layer of ``kind``; None: causal."""
        return self.sliding_window if kind == "sliding_attention" else None

    def rotary(self, kind: str) -> bool:
        """Whether an attention layer of ``kind`` has rotary positions."""
        return self.rope_theta is not None and (
            self.rope_layers is None or kind in self.rope_layers)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    def routed(self, layer: int) -> bool:
        """Whether layer ``layer``'s FFN is the routed-expert layer."""
        return bool(self.num_experts) and layer >= self.num_dense_layers

    @classmethod
    def tiny_lfm2(cls, **kw) -> "HybridConfig":
        """Test-scale LFM2: a dense layer, then conv and attention layers
        over 8 experts of which 4 go to a token."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            layer_types=("conv", "full_attention", "conv"), num_heads=4,
            num_kv_heads=2, conv_width=3, rope_theta=1e6, qk_norm=True,
            num_dense_layers=1, num_experts=8, num_experts_per_token=4,
            moe_intermediate_size=32,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_afmoe(cls, **kw) -> "HybridConfig":
        """Test-scale AFMoE: a dense layer, then a whole period of
        sliding and global attention over 16 experts, 4 a token, and a
        shared expert; heads of 32 over a hidden size of 64."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            layer_types=("sliding_attention", "sliding_attention",
                         "full_attention", "sliding_attention"),
            num_heads=4, num_kv_heads=2, head_dim=32, sliding_window=8,
            rope_theta=1e4, rope_layers=("sliding_attention",),
            qk_norm=True, attention_gate=True, sandwich_norm=True,
            tie_word_embeddings=False, embedding_multiplier=8.0,
            num_dense_layers=1, num_experts=16, num_experts_per_token=4,
            moe_intermediate_size=32, num_shared_experts=1,
            routed_scaling_factor=2.826, route_norm_eps=1e-20,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw) -> "HybridConfig":
        """Test-scale config with both kinds of layer."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            layer_types=("mamba", "attention", "mamba"), num_heads=4,
            num_kv_heads=2, ssm_heads=4, ssm_head_dim=16, ssm_state=16,
            ssm_chunk=8, embedding_multiplier=12.0, residual_multiplier=0.22,
            attention_multiplier=1 / 16, logits_scaling=8.0,
        )
        base.update(kw)
        return cls(**base)


def _dt_bias_init(key, shape, dtype):
    """softplus^-1 of a step drawn log-uniformly from [1e-3, 1e-1]."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _conv_init(width):
    """U(-1/sqrt(width), 1/sqrt(width)) for a depthwise convolution's taps
    and bias (fan-in = its width)."""
    bound = 1.0 / math.sqrt(width)

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


class MambaMixer(nn.Module):
    cfg: HybridConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        heads, inner = cfg.ssm_heads, cfg.ssm_inner

        def project(name, features, axis):
            return nn.DenseGeneral(
                features=features, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, use_bias=False,
                kernel_init=param_with_axes(
                    nn.initializers.lecun_normal(), ("embed", axis)),
                name=name,
            )(h)

        def conv(name, value, axis):
            width, ch = cfg.conv_width, value.shape[-1]
            weight = self.param(
                f"conv_{name}",
                param_with_axes(_conv_init(width), ("conv_width", axis)),
                (width, ch), cfg.param_dtype,
            )
            bias = self.param(
                f"conv_{name}_bias",
                param_with_axes(_conv_init(width), (axis,)),
                (ch,), cfg.param_dtype,
            )
            return nn.silu(ssd.causal_conv1d(value, weight, bias))

        def per_head(name, init):
            return self.param(
                name, param_with_axes(init, ("ssm_heads",)), (heads,),
                cfg.param_dtype,
            ).astype(jnp.float32)

        with jax.named_scope("mamba/in_proj"):
            z = project("z_proj", inner, "ssm_inner")
            x = project("x_proj", inner, "ssm_inner")
            B = project("b_proj", cfg.ssm_state, "ssm_state")
            C = project("c_proj", cfg.ssm_state, "ssm_state")
            dt = project("dt_proj", heads, "ssm_heads")
        z = constrain(z, ("batch", "seq", "act_ssm_inner"))
        x = constrain(x, ("batch", "seq", "act_ssm_inner"))
        with jax.named_scope("mamba/conv"):
            x = conv("x", x, "ssm_inner")
            B = conv("b", B, "ssm_state")
            C = conv("c", C, "ssm_state")
        dt = jax.nn.softplus(
            dt.astype(jnp.float32) + per_head("dt_bias", _dt_bias_init))
        A = -jnp.exp(per_head("A_log", _a_log_init))
        D = per_head("D", nn.initializers.ones_init())
        x = x.reshape(*x.shape[:2], heads, cfg.ssm_head_dim)
        y = ssd.ssd_chunked(x, dt, A, B, C, cfg.ssm_chunk)
        with jax.named_scope("mamba/gated_norm"):
            y = y.astype(jnp.float32) + D[:, None] * x.astype(jnp.float32)
            y = y.reshape(*y.shape[:2], inner) * nn.silu(
                z.astype(jnp.float32))
            scale = self.param(
                "norm",
                param_with_axes(nn.initializers.ones_init(), ("ssm_inner",)),
                (inner,), cfg.param_dtype,
            )
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps)
            y = (y * scale.astype(jnp.float32)).astype(cfg.dtype)
        y = constrain(y, ("batch", "seq", "act_ssm_inner"))
        with jax.named_scope("mamba/out_proj"):
            out = nn.DenseGeneral(
                features=cfg.hidden_size, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, use_bias=False,
                kernel_init=param_with_axes(
                    nn.initializers.lecun_normal(), ("ssm_inner", "embed")),
                name="out_proj",
            )(y)
        return constrain(out, ("batch", "seq", "act_embed"))


class ShortConv(nn.Module):
    """LFM2's gated short convolution: ``W_out (C * conv(B * x))``."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        inner = cfg.hidden_size

        def project(name, axes):  # the inner width is the hidden size
            return nn.DenseGeneral(
                features=inner, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, use_bias=False,
                kernel_init=param_with_axes(
                    nn.initializers.lecun_normal(), axes),
                name=name,
            )

        with jax.named_scope("conv/in_proj"):
            B, C, x = (
                constrain(
                    project(name, ("embed", "conv_inner"))(h),
                    ("batch", "seq", "act_conv_inner"))
                for name in ("b_proj", "c_proj", "x_proj"))
        with jax.named_scope("conv/conv"):
            taps = self.param(
                "conv",
                param_with_axes(
                    _conv_init(cfg.conv_width), ("conv_width", "conv_inner")),
                (cfg.conv_width, inner), cfg.param_dtype,
            )
            y = C * ssd.causal_conv1d(B * x, taps)
        y = constrain(y, ("batch", "seq", "act_conv_inner"))
        with jax.named_scope("conv/out_proj"):
            out = project("out_proj", ("conv_inner", "embed"))(y)
        return constrain(out, ("batch", "seq", "act_embed"))


def causal_mask(s: int, window: Optional[int] = None, segment_ids=None):
    """The ``dot`` path's (1 | b, 1, s, s) mask: key ``j`` is seen by query
    ``t`` iff ``0 <= t - j`` (``< window`` with one) and, with
    ``segment_ids`` (b, s), both lie in one document."""
    mask = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        mask = mask & ~jnp.tril(mask, -window)
    mask = mask[None, None]
    if segment_ids is not None:
        mask = mask & (
            segment_ids[:, None, :, None] == segment_ids[:, None, None, :])
    return mask


class HybridAttention(nn.Module):
    """Causal grouped-query attention; ``cfg.qk_norm`` and
    ``cfg.rope_theta`` add LFM2's RMSNorm on q and k and its rotary
    positions (Granite-4.0-H has neither).  ``kind`` is the layer's entry
    of ``layer_types``: it decides the window (``cfg.window``) and whether
    this layer has rotary positions (``cfg.rotary``); ``cfg.attention_gate``
    adds AFMoE's sigmoid gate on the heads' outputs."""

    cfg: HybridConfig
    kind: str = "attention"

    @nn.compact
    def __call__(self, h, positions=None, segment_ids=None):
        cfg = self.cfg
        d = cfg.resolved_head_dim
        window = cfg.window(self.kind)
        scale = cfg.attention_multiplier
        if scale is None:
            scale = 1.0 / math.sqrt(d)

        def project(name, n_heads, axis):
            return nn.DenseGeneral(
                features=(n_heads, d), axis=-1, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, use_bias=False,
                kernel_init=param_with_axes(
                    nn.initializers.lecun_normal(),
                    ("embed", axis, "head_dim")),
                name=name,
            )(h)

        q = project("q_proj", cfg.num_heads, "heads")
        k = project("k_proj", cfg.num_kv_heads, "kv_heads")
        v = project("v_proj", cfg.num_kv_heads, "kv_heads")
        if cfg.qk_norm:
            def head_norm(name, t):
                weight = self.param(
                    name,
                    param_with_axes(
                        nn.initializers.ones_init(), ("head_dim",)),
                    (d,), cfg.param_dtype,
                ).astype(jnp.float32)
                t = t.astype(jnp.float32)
                t = t * jax.lax.rsqrt(
                    jnp.mean(t * t, axis=-1, keepdims=True)
                    + cfg.rms_norm_eps)
                return (t * weight).astype(cfg.dtype)

            q, k = head_norm("q_norm", q), head_norm("k_norm", k)
        if cfg.rotary(self.kind):
            if positions is None:
                positions = jnp.arange(h.shape[1])[None]
            q, k = _rope(q, k, positions, d, cfg.rope_theta)
        q = constrain(q, ("batch", "seq", "act_heads", "act_head_dim"))
        k = constrain(k, ("batch", "seq", "act_kv_heads", "act_head_dim"))
        v = constrain(v, ("batch", "seq", "act_kv_heads", "act_head_dim"))
        with jax.named_scope(
                "attn/sliding" if window is not None else "attn/full"):
            if cfg.attention_impl == "splash":
                out = splash_attention_gqa(
                    q, k, v, segment_ids=segment_ids, scale=scale,
                    window=window)
            else:
                out = _masked_attention(
                    q, k, v, causal_mask(q.shape[1], window, segment_ids),
                    scale=scale)
        if cfg.attention_gate:
            with jax.named_scope("attn/gate"):
                gate = project("gate_proj", cfg.num_heads, "heads")
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(cfg.dtype)
        out = constrain(
            out, ("batch", "seq", "act_heads", "act_head_dim"))
        out = nn.DenseGeneral(
            features=cfg.hidden_size, axis=(-2, -1), dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, use_bias=False,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("heads", "head_dim", "embed")),
            name="o_proj",
        )(out)
        return constrain(out, ("batch", "seq", "act_embed"))


def recompute_policy(name: str):
    """What a recomputed layer keeps from its forward pass: what
    ``remat_policy(name)`` keeps, and two results of kernel-heavy pieces
    whatever that recomputes.  A routed layer's output (one row a token):
    a gradient that reads it (a norm after the layer) would otherwise run
    the layer's passes a third time (models/moe.py::_experts_in_passes).
    The splash kernel's output and log-sum-exp (one row a token and one
    float a (head, token)): the backward kernel reads both, and the
    recomputation would otherwise run the forward kernel a second time to
    make them."""
    return jax.checkpoint_policies.save_from_both_policies(
        remat_policy(name),
        jax.checkpoint_policies.save_only_these_names(
            ROUTED_OUT, KERNEL_RESULTS))


def routed_experts(cfg: HybridConfig, **kw) -> RoutedExperts:
    """The routed-expert FFN of a configuration (``scripts/logits_check.py``
    builds it alone to hold one layer to the reference)."""
    return RoutedExperts(
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.moe_intermediate_size,
        num_experts=cfg.num_experts,
        num_experts_per_token=cfg.num_experts_per_token,
        experts_held=cfg.experts_held,
        expert_block=cfg.expert_block,
        routed_scaling_factor=cfg.routed_scaling_factor,
        route_norm_eps=cfg.route_norm_eps,
        num_shared_experts=cfg.num_shared_experts,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype, **kw)


class HybridBlock(nn.Module):
    cfg: HybridConfig
    kind: str
    routed: bool = False

    @nn.compact
    def __call__(self, x, positions=None, segment_ids=None):
        cfg = self.cfg
        norm = partial(RMSNorm, cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype)
        h = norm(name="input_norm")(x)
        if self.kind == "mamba":
            mixed = MambaMixer(cfg, name="mamba")(h)
        elif self.kind == "conv":
            mixed = ShortConv(cfg, name="conv")(h)
        else:
            with jax.named_scope("hybrid/attention"):
                mixed = HybridAttention(cfg, self.kind, name="attention")(
                    h, positions, segment_ids)
        if cfg.sandwich_norm:
            mixed = norm(name="mixer_out_norm")(mixed)
        x = x + cfg.residual_multiplier * mixed
        h = norm(name="post_norm")(x)
        if self.routed:
            ffn = routed_experts(cfg, name="experts")(h)
        else:
            with jax.named_scope("hybrid/mlp"):
                ffn = MLP(cfg, name="mlp")(h)
        if cfg.sandwich_norm:
            ffn = norm(name="ffn_out_norm")(ffn)
        x = x + cfg.residual_multiplier * ffn
        return constrain(x, ("batch", "seq", "act_embed"))


class HybridModel(nn.Module):
    """Decoder-only LM over a layer pattern.  ``__call__`` returns logits
    (b, s, vocab), with ``LlamaModel``'s signature (``positions`` reaches
    the attention layers of a configuration with ``rope_theta``; ``None``
    counts each row from 0)."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None):
        from dlrover_tpu.telemetry.spans import span

        cfg = self.cfg
        kinds = Counter(cfg.layer_types)
        if segment_ids is not None and (kinds["mamba"] or kinds["conv"]):
            raise ValueError(
                "HybridModel: segment_ids (packed rows) are not supported "
                "with mamba or conv layers: neither the scan's state nor "
                "the convolution's taps are reset at document boundaries "
                "(ops/ssd.py)")
        # What each tracing of the model lowered, by layer kind, for the
        # telemetry directory (one span a trace, not a step).
        with span("lower", what="hybrid") as lowered:
            lowered.update(
                layer_types=dict(kinds),
                attention_impl=cfg.attention_impl,
                head_dim=cfg.resolved_head_dim,
            )
            # The attention layers whose forward kernel the backward pass
            # will not run again (``recompute_policy``), and what keeping
            # the kernel's two results holds.
            each = 0
            if cfg.remat_policy != "none" and cfg.attention_impl == "splash":
                each = kept_bytes(
                    *input_ids.shape, cfg.num_heads, cfg.resolved_head_dim,
                    cfg.dtype)
            kept = sum(kinds[k] for k in ATTENTION_KINDS) if each else 0
            lowered.update(
                attention_kept=kept, attention_kept_bytes=kept * each)
            if kinds["sliding_attention"]:
                seq = input_ids.shape[1]
                lowered.update(
                    sliding_window=cfg.sliding_window,
                    rope_layers=sorted(
                        k for k in ATTENTION_KINDS
                        if kinds[k] and cfg.rotary(k)),
                    attention_masks={
                        k: mask_plan(seq, cfg.window(k))
                        for k in ATTENTION_KINDS if kinds[k]},
                )
            if kinds["mamba"]:
                lowered.update(
                    chunk=cfg.ssm_chunk,
                    n_chunks=input_ids.shape[1] // cfg.ssm_chunk)
            if cfg.num_experts:
                pairs = input_ids.size * cfg.num_experts_per_token
                held = cfg.experts_held or cfg.num_experts
                cap, small_rows = small_buffer(pairs, held, cfg.num_experts)
                h, m = cfg.hidden_size, cfg.moe_intermediate_size
                lowered.update(
                    num_experts=cfg.num_experts, experts_held=held,
                    top_k=cfg.num_experts_per_token, pairs_rows=pairs,
                    pairs_cap=cap,
                    num_shared_experts=cfg.num_shared_experts,
                    routed_layers=sum(
                        cfg.routed(i) for i in range(len(cfg.layer_types))),
                    gmm_gate_up=grouped_matmul.plan(pairs, h, 2 * m),
                    gmm_down=grouped_matmul.plan(pairs, m, h),
                )
                if cap < pairs:  # the small buffer's products (models/moe.py)
                    lowered.update(
                        gmm_gate_up_at_cap=grouped_matmul.plan(
                            small_rows, h, 2 * m),
                        gmm_down_at_cap=grouped_matmul.plan(small_rows, m, h))
            embed = self.param(
                "embed_tokens",
                param_with_axes(
                    nn.initializers.normal(stddev=0.02), ("vocab", "embed")),
                (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype,
            )
            x = embed.astype(cfg.dtype)[input_ids] * jnp.asarray(
                cfg.embedding_multiplier, cfg.dtype)
            x = constrain(x, ("batch", "seq", "act_embed"))
            block_cls = HybridBlock
            if cfg.remat_policy != "none":
                block_cls = nn.remat(
                    HybridBlock, policy=recompute_policy(cfg.remat_policy),
                    prevent_cse=True)
            for i, kind in enumerate(cfg.layer_types):
                x = block_cls(cfg, kind, cfg.routed(i), name=f"layers_{i}")(
                    x, positions, segment_ids)
            with jax.named_scope("hybrid/head"):
                x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                            name="final_norm")(x)
                if cfg.tie_word_embeddings:
                    logits = jnp.einsum(
                        "bse,ve->bsv", x, embed.astype(cfg.dtype))
                else:
                    logits = nn.DenseGeneral(
                        features=cfg.vocab_size, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, use_bias=False,
                        kernel_init=param_with_axes(
                            nn.initializers.lecun_normal(),
                            ("embed", "vocab")),
                        name="lm_head",
                    )(x)
                logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
        return constrain(logits, ("batch", "seq", "act_vocab"))
