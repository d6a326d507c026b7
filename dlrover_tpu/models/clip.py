"""CLIP-family dual-tower model: ViT image encoder + causal text encoder
with a symmetric contrastive loss.

Completes the model-family coverage of the reference's TP module registry
(``atorch/modules/distributed_modules/modules_registry.py`` maps CLIP
attention/MLP blocks alongside Bert/GPTNeoX/llama).  TPU redesign notes:

- patch embedding is a Dense over flattened patches (identical math to
  the conv, but it stays on the zoo's existing logical axes);
- both towers use pre-LN blocks (LayerNorm/GELU — the CLIP lineage),
  the text tower causal, the vision tower bidirectional;
- the contrastive loss is written on the full logical batch: under GSPMD
  the batch dim is sharded on the mesh, and XLA inserts the all-gather
  for the (B, B) similarity matrix itself — no hand-rolled cross-replica
  negative mining like GPU implementations need.
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
class CLIPConfig:
    # vision tower
    image_size: int = 224
    patch_size: int = 16
    vision_hidden: int = 768
    vision_layers: int = 12
    vision_heads: int = 12
    # text tower
    vocab_size: int = 49408
    text_hidden: int = 512
    text_layers: int = 12
    text_heads: int = 8
    max_text_len: int = 77
    # joint space
    projection_dim: int = 512
    layer_norm_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @classmethod
    def tiny(cls, **kw) -> "CLIPConfig":
        base = dict(
            image_size=32, patch_size=8, vision_hidden=64, vision_layers=2,
            vision_heads=4, vocab_size=256, text_hidden=64, text_layers=2,
            text_heads=4, max_text_len=16, projection_dim=32,
        )
        base.update(kw)
        return cls(**base)


class _TowerBlock(nn.Module):
    """Pre-LN transformer block shared by both towers (attention body
    shared with BERT via :class:`BiasedSelfAttention`)."""

    hidden: int
    heads: int
    causal: bool
    eps: float
    dtype: Dtype
    param_dtype: Dtype

    @nn.compact
    def __call__(self, x):
        h = LayerNorm(self.eps, self.dtype, self.param_dtype, name="ln1")(x)
        attn = BiasedSelfAttention(
            self.hidden, self.heads, causal=self.causal,
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="attention",
        )(h)
        x = x + attn
        h = LayerNorm(self.eps, self.dtype, self.param_dtype, name="ln2")(x)
        h = BiasedGeluMLP(
            self.hidden, 4 * self.hidden,
            dtype=self.dtype, param_dtype=self.param_dtype, name="mlp",
        )(h)
        x = x + h
        return constrain(x, ("batch", "seq", "act_embed"))


class VisionTower(nn.Module):
    cfg: CLIPConfig

    @nn.compact
    def __call__(self, pixels):
        """pixels: (b, H, W, C) -> pooled (b, vision_hidden)."""
        cfg = self.cfg
        b, H, W, C = pixels.shape
        p = cfg.patch_size
        if H != cfg.image_size or W != cfg.image_size:
            raise ValueError(
                f"expected {cfg.image_size}x{cfg.image_size} images, got "
                f"{H}x{W}"
            )
        n = (H // p) * (W // p)
        patches = pixels.reshape(b, H // p, p, W // p, p, C)
        patches = patches.transpose(0, 1, 3, 2, 4, 5).reshape(b, n, p * p * C)
        x = nn.DenseGeneral(
            features=cfg.vision_hidden,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("patch_dim", "embed")
            ),
            name="patch_embed",
        )(patches.astype(cfg.dtype))
        cls = self.param(
            "cls_token",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("embed",)
            ),
            (cfg.vision_hidden,),
            cfg.param_dtype,
        )
        x = jnp.concatenate(
            [jnp.broadcast_to(cls.astype(cfg.dtype), (b, 1, cfg.vision_hidden)), x],
            axis=1,
        )
        pos = self.param(
            "pos_embed",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("pos", "embed")
            ),
            (n + 1, cfg.vision_hidden),
            cfg.param_dtype,
        )
        x = x + pos.astype(cfg.dtype)[None]
        x = constrain(x, ("batch", "seq", "act_embed"))
        for i in range(cfg.vision_layers):
            x = _TowerBlock(
                cfg.vision_hidden, cfg.vision_heads, False,
                cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype,
                name=f"block_{i}",
            )(x)
        x = LayerNorm(
            cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype, name="final_norm"
        )(x)
        return x[:, 0]  # CLS pooling


class TextTower(nn.Module):
    cfg: CLIPConfig

    @nn.compact
    def __call__(self, input_ids, text_lengths=None):
        """input_ids: (b, s) -> pooled (b, text_hidden).

        Pools at position ``text_lengths - 1`` per example (the EOT slot
        for right-padded captions — original CLIP's argmax-EOT pooling
        made explicit); without lengths, at the final position."""
        cfg = self.cfg
        s = input_ids.shape[1]
        if s > cfg.max_text_len:
            raise ValueError(
                f"text length {s} exceeds max_text_len {cfg.max_text_len}"
            )
        embed = self.param(
            "token_embed",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.text_hidden),
            cfg.param_dtype,
        )
        pos = self.param(
            "pos_embed",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("pos", "embed")
            ),
            (cfg.max_text_len, cfg.text_hidden),
            cfg.param_dtype,
        )
        x = embed.astype(cfg.dtype)[input_ids] + pos.astype(cfg.dtype)[:s][None]
        x = constrain(x, ("batch", "seq", "act_embed"))
        for i in range(cfg.text_layers):
            x = _TowerBlock(
                cfg.text_hidden, cfg.text_heads, True,
                cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype,
                name=f"block_{i}",
            )(x)
        x = LayerNorm(
            cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype, name="final_norm"
        )(x)
        if text_lengths is None:
            return x[:, -1]
        idx = jnp.clip(text_lengths - 1, 0, s - 1)
        return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]


class CLIPModel(nn.Module):
    """Returns (image_embeds, text_embeds, logit_scale) — all f32,
    embeddings L2-normalized into the joint space."""

    cfg: CLIPConfig

    @nn.compact
    def __call__(self, pixels, input_ids, text_lengths=None):
        cfg = self.cfg
        img = VisionTower(cfg, name="vision")(pixels)
        txt = TextTower(cfg, name="text")(input_ids, text_lengths)

        def project(x, name):
            return nn.DenseGeneral(
                features=cfg.projection_dim,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                use_bias=False,
                kernel_init=param_with_axes(
                    nn.initializers.lecun_normal(), ("embed", "embed_out")
                ),
                name=name,
            )(x)

        img = project(img, "visual_projection").astype(jnp.float32)
        txt = project(txt, "text_projection").astype(jnp.float32)
        img = img / jnp.linalg.norm(img, axis=-1, keepdims=True).clip(1e-8)
        txt = txt / jnp.linalg.norm(txt, axis=-1, keepdims=True).clip(1e-8)
        logit_scale = self.param(
            "logit_scale",
            param_with_axes(
                nn.initializers.constant(jnp.log(1 / 0.07)), ()
            ),
            (),
            jnp.float32,
        )
        # Clamp at ln(100) (the reference CLIP bound): an unbounded learned
        # temperature saturates the f32 logsumexp and NaNs long runs.
        return img, txt, jnp.exp(jnp.clip(logit_scale, None, jnp.log(100.0)))


def clip_contrastive_loss(image_embeds, text_embeds, logit_scale):
    """Symmetric InfoNCE over the (global) batch.

    Written on the full logical batch: if the batch dim is sharded on the
    mesh, GSPMD gathers the negatives itself.
    """
    logits = logit_scale * image_embeds @ text_embeds.T  # (B, B)
    lse_i = jax.nn.logsumexp(logits, axis=1)
    lse_t = jax.nn.logsumexp(logits, axis=0)
    diag = jnp.diagonal(logits)
    loss_i = jnp.mean(lse_i - diag)
    loss_t = jnp.mean(lse_t - diag)
    return 0.5 * (loss_i + loss_t)
