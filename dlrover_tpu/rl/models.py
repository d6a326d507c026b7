"""Actor/critic/reference/reward model wrappers.

Reference parity: ``atorch/rl/model_engine.py`` (multi-model RLHF engine)
— the four roles: actor (policy LM), critic (value model), reference
(frozen initial policy), reward model.  The critic reuses the llama
backbone modules with a scalar value head instead of the LM head.
"""

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from dlrover_tpu.models.llama import (
    DecoderBlock,
    LlamaConfig,
    RMSNorm,
)

param_with_axes = nn.with_logical_partitioning


def tiny_actor_factory():
    """Generation-server model factory for tests/examples:
    ``--model-factory dlrover_tpu.rl.models:tiny_actor_factory``."""
    from dlrover_tpu.models.llama import LlamaModel

    return LlamaModel(LlamaConfig.tiny(dtype=jnp.float32, num_layers=1))


class CriticModel(nn.Module):
    """Value model: llama backbone + per-token scalar value head."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None):
        cfg = self.cfg
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])[None, :]
            positions = jnp.broadcast_to(positions, input_ids.shape)
        embed = self.param(
            "embed_tokens",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.hidden_size),
            cfg.param_dtype,
        )
        x = embed.astype(cfg.dtype)[input_ids]
        x, _ = nn.scan(
            DecoderBlock,
            variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True},
            in_axes=(nn.broadcast, nn.broadcast),
            length=cfg.num_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(cfg, name="layers")(x, positions, segment_ids)
        x = RMSNorm(
            cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="final_norm"
        )(x)
        values = nn.DenseGeneral(
            features=1,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            kernel_init=param_with_axes(
                nn.initializers.zeros_init(), ("embed", None)
            ),
            name="value_head",
        )(x)
        return values[..., 0]  # (b, t)
