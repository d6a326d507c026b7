"""BERT-family bidirectional encoder with an MLM head.

Widens the model zoo to the encoder modality (reference parity: atorch's
module registry ships TP mappings for Bert,
``atorch/modules/distributed_modules/modules_registry.py``).  Same
logical-axis names as the decoder zoo, so every sharding rule table
applies unchanged; attention is bidirectional with an optional padding
mask instead of the causal mask.

Structure (post-LN, original BERT): token+position+type embeddings →
LayerNorm → N blocks of [self-attn → add&norm → GELU FFN → add&norm] →
MLM transform (dense+GELU+norm) → vocab decoder.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.models.gpt_neox import LayerNorm
from dlrover_tpu.models.layers import BiasedGeluMLP, BiasedSelfAttention
from dlrover_tpu.models.llama import param_with_axes
from dlrover_tpu.parallel.sharding import constrain

Dtype = Any


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_seq_len: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    scan_layers: bool = True
    logits_f32_output: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "BertConfig":
        base = dict(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            intermediate_size=128, max_seq_len=128,
        )
        base.update(kw)
        return cls(**base)


class BertBlock(nn.Module):
    """Post-LN encoder block; ``(carry, None)`` so it can be scanned."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, x, segment_ids=None):
        cfg = self.cfg
        attn = BiasedSelfAttention(
            cfg.hidden_size, cfg.num_heads, causal=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="attention",
        )(x, segment_ids)
        x = LayerNorm(
            cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype,
            name="attention_norm",
        )(x + attn)
        h = BiasedGeluMLP(
            cfg.hidden_size, cfg.intermediate_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="mlp",
        )(x)
        x = LayerNorm(
            cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype,
            name="output_norm",
        )(x + h)
        return constrain(x, ("batch", "seq", "act_embed")), None


class BertModel(nn.Module):
    """Encoder with MLM head; __call__ returns logits (b, s, vocab).

    The positional signature matches ``make_train_step``'s calling
    convention — ``(input_ids, positions, segment_ids)`` — so the sharded
    step drives BERT exactly like the decoder zoo.  ``segment_ids`` is
    both the packing AND padding mechanism (attention is bidirectional
    within a segment only); ``token_type_ids`` is BERT's sentence-A/B
    embedding input, independent of masking.
    """

    cfg: BertConfig

    @nn.compact
    def __call__(
        self,
        input_ids,
        positions=None,
        segment_ids=None,
        token_type_ids=None,
    ):
        cfg = self.cfg
        word = self.param(
            "word_embeddings",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.hidden_size),
            cfg.param_dtype,
        )
        pos = self.param(
            "position_embeddings",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("pos", "embed")
            ),
            (cfg.max_seq_len, cfg.hidden_size),
            cfg.param_dtype,
        )
        typ = self.param(
            "token_type_embeddings",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("type", "embed")
            ),
            (cfg.type_vocab_size, cfg.hidden_size),
            cfg.param_dtype,
        )
        s = input_ids.shape[1]
        if s > cfg.max_seq_len:
            # JAX gathers clamp out-of-range indices silently — surface
            # the misconfiguration instead of repeating the last position.
            raise ValueError(
                f"sequence length {s} exceeds max_seq_len "
                f"{cfg.max_seq_len}"
            )
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(s)[None], input_ids.shape
            )
        x = (
            word.astype(cfg.dtype)[input_ids]
            + pos.astype(cfg.dtype)[positions]
            + typ.astype(cfg.dtype)[token_type_ids]
        )
        x = LayerNorm(
            cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype,
            name="embeddings_norm",
        )(x)
        x = constrain(x, ("batch", "seq", "act_embed"))

        if cfg.scan_layers:
            x, _ = nn.scan(
                BertBlock,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast,),
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="layers")(x, segment_ids)
        else:
            for i in range(cfg.num_layers):
                x, _ = BertBlock(cfg, name=f"layers_{i}")(x, segment_ids)

        # MLM transform + decoder (untied head, logical vocab axis).
        h = nn.DenseGeneral(
            features=cfg.hidden_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=True,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "embed_out")
            ),
            bias_init=param_with_axes(
                nn.initializers.zeros_init(), ("embed_out",)
            ),
            name="mlm_transform",
        )(x)
        h = nn.gelu(h)
        h = LayerNorm(
            cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype, name="mlm_norm"
        )(h)
        logits = nn.DenseGeneral(
            features=cfg.vocab_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
            name="mlm_decoder",
        )(h)
        if cfg.logits_f32_output:
            logits = logits.astype(jnp.float32)
        return constrain(logits, ("batch", "seq", "act_vocab"))


def mlm_loss(logits, labels, mlm_mask):
    """Masked-LM cross entropy over positions where ``mlm_mask`` is 1."""
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    tgt = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    ll = tgt - lse
    mask = mlm_mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
