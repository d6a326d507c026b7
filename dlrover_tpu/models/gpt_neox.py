"""GPT-NeoX-family decoder: parallel-residual blocks, partial rotary.

Widens the model zoo beyond llama (reference parity: atorch's module
registry maps GPTNeoX blocks to TP layers,
``atorch/modules/distributed_modules/modules_registry.py``; here the same
family is expressed with the framework's logical-axis names so every
sharding rule table — dp/fsdp/tp/sp — applies with no model changes).

Family traits vs llama:
- LayerNorm with bias (not RMSNorm), biased dense layers;
- *parallel* residual: ``x + attn(ln1(x)) + mlp(ln2(x))`` — one residual
  add per block, attention and MLP computed from the same input (XLA can
  schedule them concurrently);
- rotary embedding on the first ``rotary_pct`` of head dims only;
- GELU MLP at 4x width.
"""

import dataclasses
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import (
    _rope,
    cross_entropy_loss,
    dot_product_attention,
    param_with_axes,
)
from dlrover_tpu.parallel.sharding import constrain

Dtype = Any


@dataclasses.dataclass(frozen=True)
class GPTNeoXConfig:
    vocab_size: int = 50432
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 2048
    rotary_pct: float = 0.25
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    scan_layers: bool = True
    logits_f32_output: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size

    @classmethod
    def tiny(cls, **kw) -> "GPTNeoXConfig":
        base = dict(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=128,
        )
        base.update(kw)
        return cls(**base)


class LayerNorm(nn.Module):
    eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            param_with_axes(nn.initializers.ones_init(), ("embed",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        bias = self.param(
            "bias",
            param_with_axes(nn.initializers.zeros_init(), ("embed",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
        norm = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        out = norm * scale.astype(jnp.float32) + bias.astype(jnp.float32)
        return out.astype(self.dtype)


def _partial_rope(q, k, positions, head_dim: int, pct: float, theta: float):
    """Rotary on the first ``pct`` of head dims, pass-through on the rest."""
    rot = int(head_dim * pct)
    rot -= rot % 2  # rope pairs dims
    if rot <= 0:
        return q, k
    q_rot, k_rot = _rope(
        q[..., :rot], k[..., :rot], positions, rot, theta
    )
    return (
        jnp.concatenate([q_rot, q[..., rot:]], -1),
        jnp.concatenate([k_rot, k[..., rot:]], -1),
    )


class NeoXAttention(nn.Module):
    cfg: GPTNeoXConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        d = cfg.head_dim
        dense = partial(
            nn.DenseGeneral,
            axis=-1,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=True,
        )
        qkv = dense(
            features=(3, cfg.num_heads, d),
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "qkv", "heads",
                                                 "head_dim")
            ),
            bias_init=param_with_axes(
                nn.initializers.zeros_init(), ("qkv", "heads", "head_dim")
            ),
            name="qkv_proj",
        )(x)
        q, k, v = (
            qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :],
        )
        q = constrain(q, ("batch", "seq", "act_heads", "act_head_dim"))
        k = constrain(k, ("batch", "seq", "act_heads", "act_head_dim"))
        v = constrain(v, ("batch", "seq", "act_heads", "act_head_dim"))
        q, k = _partial_rope(
            q, k, positions, d, cfg.rotary_pct, cfg.rope_theta
        )
        out = dot_product_attention(q, k, v, cfg, segment_ids)
        out = nn.DenseGeneral(
            features=cfg.hidden_size,
            axis=(-2, -1),
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=True,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("heads", "head_dim", "embed")
            ),
            bias_init=param_with_axes(
                nn.initializers.zeros_init(), ("embed",)
            ),
            name="o_proj",
        )(out)
        return constrain(out, ("batch", "seq", "act_embed"))


class NeoXMLP(nn.Module):
    cfg: GPTNeoXConfig

    @nn.compact
    def __call__(self, x):
        from dlrover_tpu.models.layers import BiasedGeluMLP

        cfg = self.cfg
        return BiasedGeluMLP(
            cfg.hidden_size, cfg.intermediate_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="ffn",
        )(x)


class NeoXBlock(nn.Module):
    """Parallel-residual block; ``(carry, None)`` so it can be scanned."""

    cfg: GPTNeoXConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        attn_in = LayerNorm(
            cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype, name="input_norm"
        )(x)
        mlp_in = LayerNorm(
            cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype,
            name="post_attention_norm",
        )(x)
        x = (
            x
            + NeoXAttention(cfg, name="attention")(
                attn_in, positions, segment_ids
            )
            + NeoXMLP(cfg, name="mlp")(mlp_in)
        )
        return constrain(x, ("batch", "seq", "act_embed")), None


class GPTNeoXModel(nn.Module):
    """Decoder-only LM; __call__ returns logits (b, s, vocab)."""

    cfg: GPTNeoXConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None):
        cfg = self.cfg
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])[None, :]
            positions = jnp.broadcast_to(positions, input_ids.shape)
        embed = self.param(
            "embed_in",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.hidden_size),
            cfg.param_dtype,
        )
        x = embed.astype(cfg.dtype)[input_ids]
        x = constrain(x, ("batch", "seq", "act_embed"))

        if cfg.scan_layers:
            x, _ = nn.scan(
                NeoXBlock,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="layers")(x, positions, segment_ids)
        else:
            for i in range(cfg.num_layers):
                x, _ = NeoXBlock(cfg, name=f"layers_{i}")(
                    x, positions, segment_ids
                )

        x = LayerNorm(
            cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype, name="final_norm"
        )(x)
        logits = nn.DenseGeneral(
            features=cfg.vocab_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
            name="embed_out",
        )(x)
        if cfg.logits_f32_output:
            logits = logits.astype(jnp.float32)
        return constrain(logits, ("batch", "seq", "act_vocab"))


neox_lm_loss = cross_entropy_loss
