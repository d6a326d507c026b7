"""GLM-family prefix-LM: bidirectional attention over the prompt prefix,
causal over the generated suffix.

Completes the reference registry's family list (atorch maps GLM blocks to
TP layers in ``modules_registry.py``; GLM-130B is also the flagship of
the reference's goodput story, ``README.md:55``).  The family trait that
matters architecturally is the *prefix-LM attention mask*: tokens in the
prefix (prompt / corrupted-span context) see each other bidirectionally,
suffix tokens see the whole prefix plus their causal past.  Blocks are
RMSNorm + gated-SiLU (the GLM-2/3 lineage), on the zoo's shared logical
axes so every sharding rule table applies unchanged.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from dlrover_tpu.models.llama import (
    MLP,
    RMSNorm,
    _masked_attention,
    _rope,
    cross_entropy_loss,
    param_with_axes,
    remat_policy,
)
from dlrover_tpu.parallel.sharding import constrain

Dtype = Any


@dataclasses.dataclass(frozen=True)
class GLMConfig:
    vocab_size: int = 65024
    hidden_size: int = 4096
    intermediate_size: int = 13696
    num_layers: int = 28
    num_heads: int = 32
    num_kv_heads: int = 2
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    scan_layers: bool = True
    logits_f32_output: bool = True
    # Same policies as llama (models/llama.py remat_policy): at 65B-class
    # depth the materialized prefix-LM attention scores (layers x b x h x
    # s x s) dominate HBM without rematerialization — compiler-measured
    # 120GB of saved scores at 80 layers, s=2048.
    remat_policy: str = "none"  # none | full | dots_saveable | offload

    # llama's MLP is reused directly: it reads only hidden_size,
    # intermediate_size, dtype/param_dtype (all present here).
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "GLMConfig":
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
        )
        base.update(kw)
        return cls(**base)


def prefix_lm_mask(seq_len: int, prefix_len):
    """Bool attention mask: bidirectional among the first ``prefix_len``
    positions, causal afterwards.

    ``prefix_len`` is a scalar (one prefix for the whole batch) or a
    ``(batch,)`` vector (per-example prefixes); either may be a traced
    array.  Returns (1, 1, s, s) or (b, 1, s, s).  prefix_len=0 degrades
    to plain causal.
    """
    pl = jnp.asarray(prefix_len)
    if pl.ndim > 1:
        raise ValueError(
            "prefix_len must be a scalar or (batch,) vector, got shape "
            f"{pl.shape} — a (batch, seq) segment_ids array (packed rows) "
            "is handled by GLMAttention's segmented path, which never "
            "builds this dense mask"
        )
    i = jnp.arange(seq_len)[:, None]
    j = jnp.arange(seq_len)[None, :]
    causal = j <= i  # (s, s)
    if pl.ndim == 0:
        return (causal | (j < pl))[None, None]
    in_prefix = jnp.arange(seq_len)[None, :] < pl[:, None]  # (b, s) keys
    return causal[None, None] | in_prefix[:, None, None, :]


class GLMAttention(nn.Module):
    cfg: GLMConfig

    @nn.compact
    def __call__(self, x, positions, prefix_len):
        cfg = self.cfg
        d = cfg.head_dim

        def proj(name, heads, logical):
            return nn.DenseGeneral(
                features=(heads, d),
                axis=-1,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                use_bias=False,
                kernel_init=param_with_axes(
                    nn.initializers.lecun_normal(), logical
                ),
                name=name,
            )(x)

        q = proj("q_proj", cfg.num_heads, ("embed", "heads", "head_dim"))
        k = proj("k_proj", cfg.num_kv_heads, ("embed", "kv_heads", "head_dim"))
        v = proj("v_proj", cfg.num_kv_heads, ("embed", "kv_heads", "head_dim"))
        q = constrain(q, ("batch", "seq", "act_heads", "act_head_dim"))
        k = constrain(k, ("batch", "seq", "act_kv_heads", "act_head_dim"))
        v = constrain(v, ("batch", "seq", "act_kv_heads", "act_head_dim"))
        q, k = _rope(q, k, positions, d, cfg.rope_theta)
        pl_arr = jnp.asarray(prefix_len)
        if pl_arr.ndim == 2:
            # Packed rows: the generic third model input carries (b, s)
            # segment ids.  Causal ∧ same-segment via the chunked
            # segmented reference — no (b, s, s) mask in HBM.  (Prefix-LM
            # bidirectionality and packing are mutually exclusive: a
            # packed row has no single prefix.)
            from dlrover_tpu.ops.flash_attention import mha_reference

            out = mha_reference(q, k, v, causal=True, segment_ids=pl_arr)
        else:
            mask = prefix_lm_mask(x.shape[1], prefix_len)
            out = _masked_attention(q, k, v, mask)
        out = constrain(
            out, ("batch", "seq", "act_heads", "act_head_dim")
        )
        out = nn.DenseGeneral(
            features=cfg.hidden_size,
            axis=(-2, -1),
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("heads", "head_dim", "embed")
            ),
            name="o_proj",
        )(out)
        return constrain(out, ("batch", "seq", "act_embed"))


class GLMBlock(nn.Module):
    """Pre-RMSNorm block; ``(carry, None)`` so it can be scanned."""

    cfg: GLMConfig

    @nn.compact
    def __call__(self, x, positions, prefix_len):
        cfg = self.cfg
        h = RMSNorm(
            cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="input_norm"
        )(x)
        x = x + GLMAttention(cfg, name="attention")(h, positions, prefix_len)
        h = RMSNorm(
            cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="post_norm"
        )(x)
        x = x + MLP(cfg, name="mlp")(h)
        return constrain(x, ("batch", "seq", "act_embed")), None


class GLMModel(nn.Module):
    """Prefix-LM; __call__(input_ids, positions, prefix_len) -> logits.

    ``prefix_len``: scalar (or 0-d array) — number of leading positions
    attending bidirectionally; ``(batch,)`` for per-example prefixes.
    0 = plain causal LM.  A ``(batch, seq)`` array in this slot is
    treated as packed-row segment ids (the generic train step passes
    ``batch["segment_ids"]`` here) and runs causal same-segment
    attention instead of the prefix mask.
    """

    cfg: GLMConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, prefix_len=0):
        cfg = self.cfg
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])[None, :]
            positions = jnp.broadcast_to(positions, input_ids.shape)
        # The generic train step's third positional slot carries
        # prefix_len here (None = causal); a (b, s) segment_ids array
        # from the packed pipeline flows through unchanged and selects
        # GLMAttention's segmented path.
        prefix_len = jnp.asarray(0 if prefix_len is None else prefix_len)
        embed = self.param(
            "embed_tokens",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.hidden_size),
            cfg.param_dtype,
        )
        x = embed.astype(cfg.dtype)[input_ids]
        x = constrain(x, ("batch", "seq", "act_embed"))

        block_cls = GLMBlock
        if cfg.remat_policy != "none":
            block_cls = nn.remat(
                GLMBlock,
                policy=remat_policy(cfg.remat_policy),
                prevent_cse=not cfg.scan_layers,
            )
        if cfg.scan_layers:
            x, _ = nn.scan(
                block_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="layers")(x, positions, prefix_len)
        else:
            for i in range(cfg.num_layers):
                x, _ = block_cls(cfg, name=f"layers_{i}")(
                    x, positions, prefix_len
                )

        x = RMSNorm(
            cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="final_norm"
        )(x)
        logits = nn.DenseGeneral(
            features=cfg.vocab_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
            name="lm_head",
        )(x)
        if cfg.logits_f32_output:
            logits = logits.astype(jnp.float32)
        return constrain(logits, ("batch", "seq", "act_vocab"))


glm_lm_loss = cross_entropy_loss
