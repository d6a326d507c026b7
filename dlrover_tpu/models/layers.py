"""Shared building blocks for the encoder-lineage model families
(BERT blocks, CLIP towers, GPT-NeoX MLP): biased self-attention and the
biased GELU FFN, both on the zoo's logical axes.  llama/GLM keep their
own attention (GQA + RoPE) and gated-SiLU MLP.
"""

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from dlrover_tpu.models.llama import _masked_attention, param_with_axes
from dlrover_tpu.parallel.sharding import constrain

Dtype = Any


class BiasedSelfAttention(nn.Module):
    """Biased q/k/v/o self-attention: bidirectional by default, optionally
    causal, optional segment masking."""

    hidden_size: int
    num_heads: int
    causal: bool = False
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, segment_ids=None):
        d = self.hidden_size // self.num_heads

        def proj(name, logical):
            return nn.DenseGeneral(
                features=(self.num_heads, d),
                axis=-1,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                use_bias=True,
                kernel_init=param_with_axes(
                    nn.initializers.lecun_normal(), logical
                ),
                bias_init=param_with_axes(
                    nn.initializers.zeros_init(), ("heads", "head_dim")
                ),
                name=name,
            )(x)

        q = proj("q_proj", ("embed", "heads", "head_dim"))
        k = proj("k_proj", ("embed", "heads", "head_dim"))
        v = proj("v_proj", ("embed", "heads", "head_dim"))
        q = constrain(q, ("batch", "seq", "act_heads", "act_head_dim"))
        k = constrain(k, ("batch", "seq", "act_heads", "act_head_dim"))
        v = constrain(v, ("batch", "seq", "act_heads", "act_head_dim"))
        s = x.shape[1]
        if self.causal:
            mask = jnp.tril(jnp.ones((s, s), dtype=bool))[None, None]
        else:
            mask = jnp.ones((1, 1, s, s), dtype=bool)
        if segment_ids is not None:
            # Attend within a segment only: covers packed documents AND
            # padding (give pad tokens their own segment id; they then
            # attend nothing live, and the loss mask excludes them).
            seg = (
                segment_ids[:, None, :, None]
                == segment_ids[:, None, None, :]
            )
            mask = jnp.logical_and(mask, seg)
        out = _masked_attention(q, k, v, mask)
        out = nn.DenseGeneral(
            features=self.hidden_size,
            axis=(-2, -1),
            dtype=self.dtype,
            param_dtype=self.param_dtype,
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


class BiasedGeluMLP(nn.Module):
    """Biased Dense → GELU → Dense FFN on the ("embed","mlp") axes."""

    hidden_size: int
    intermediate_size: int
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.DenseGeneral(
            features=self.intermediate_size,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            use_bias=True,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "mlp")
            ),
            bias_init=param_with_axes(nn.initializers.zeros_init(), ("mlp",)),
            name="up_proj",
        )(x)
        h = nn.gelu(h)
        h = constrain(h, ("batch", "seq", "act_mlp"))
        out = nn.DenseGeneral(
            features=self.hidden_size,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            use_bias=True,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("mlp", "embed")
            ),
            bias_init=param_with_axes(
                nn.initializers.zeros_init(), ("embed",)
            ),
            name="down_proj",
        )(h)
        return constrain(out, ("batch", "seq", "act_embed"))
