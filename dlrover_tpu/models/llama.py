"""LLaMA-family decoder-only transformer, TPU-first.

Flagship model of the framework (reference parity: atorch's LLaMA examples +
HF module registry, ``atorch/examples/llama2``, ``modules_registry.py``).
Design choices for TPU:

- every parameter carries *logical axis names* via
  ``nn.with_logical_partitioning`` — parallelism (dp/fsdp/tp/sp) is applied
  by rule tables in ``dlrover_tpu.parallel.sharding``, never module rewrites;
- layers are stacked with ``nn.scan`` (one compiled block body, XLA-friendly)
  and rematerialized with ``nn.remat`` policies;
- attention is a pluggable ``attention_impl``: "dot" (XLA fused),
  "flash" (Pallas blockwise kernel), "ring" (sequence-parallel ring
  attention over the `sp` mesh axis);
- compute in bfloat16, params in float32 (MXU-native mixed precision).
"""

import dataclasses
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning

from dlrover_tpu.parallel.sharding import constrain

Dtype = Any

param_with_axes = nn.with_logical_partitioning


def _fp8_kwargs(cfg):
    """DenseGeneral kwargs for the fp8 path: a plain ``dot_general`` for
    per-call dynamic scaling, a stateful ``dot_general_cls`` for delayed
    scaling (amax history in the 'fp8' collection of the train state)."""
    if not getattr(cfg, "use_fp8", False):
        return {}
    scaling = getattr(cfg, "fp8_scaling", "dynamic")
    if scaling not in ("dynamic", "delayed"):
        raise ValueError(
            f"fp8_scaling must be 'dynamic' or 'delayed', got {scaling!r}"
        )
    if scaling == "delayed":
        import functools

        from dlrover_tpu.ops.fp8 import DelayedFp8DotGeneral

        return {
            "dot_general_cls": functools.partial(
                DelayedFp8DotGeneral,
                amax_history_len=cfg.fp8_amax_history,
            )
        }
    from dlrover_tpu.ops.fp8 import fp8_dot_general

    return {"dot_general": fp8_dot_general}


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 0  # 0 → hidden_size // num_heads
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    attention_impl: str = "dot"  # dot | flash | splash | ring | ulysses
    # f32 lm_head matmul (8x slower MXU rate on v5e).  Default False: the
    # matmul runs bf16 and only the softmax/loss math is f32 — maxtext's
    # default, worth ~30% step time at GPT-2-small scale.
    logits_dot_in_fp32: bool = False
    # Emit logits in f32 (True) or leave them in ``dtype`` (False).  At
    # 32k vocab the f32 cast materializes a b*s*v*4B tensor in HBM purely
    # as a loss input; the loss upcasts per-block inside its reductions
    # anyway, so False saves that round trip (~6% step time at GPT-2-small
    # scale) at the cost of bf16-rounded logit values.
    logits_f32_output: bool = True
    # Scaled-e4m3 matmuls in the attention-projection and MLP denses
    # (native fp8 MXU throughput on v5p+/Trillium; transparent upcast
    # elsewhere).  The lm_head is never fp8: logits feed the softmax
    # cross-entropy, where e4m3 error directly biases the loss — its
    # precision is governed by logits_dot_in_fp32 above (bf16 default,
    # f32 loss math either way).
    use_fp8: bool = False
    # "dynamic": per-call absmax scaling (stateless).  "delayed": TE-style
    # amax-history scaling carried in the train state's 'fp8' collection
    # (ops/fp8.py DelayedFp8DotGeneral) — no absmax reduction on the
    # forward critical path.
    fp8_scaling: str = "dynamic"
    fp8_amax_history: int = 16
    remat_policy: str = "none"  # none | full | dots_saveable | offload
    scan_layers: bool = True
    tie_embeddings: bool = False
    # Splash/flash tile sizes, clamped to seq_len inside the kernel wrapper.
    # Measured on v5e (round 4): 1024 ties 512 at s=1024 (69.5 vs 69.9 ms)
    # and wins 6-7% at 4k/8k; 2048 exceeds the 16 MB scoped-vmem limit.
    flash_block_q: int = 1024
    flash_block_kv: int = 1024
    # MoE (1 expert = dense MLP); see models/moe.py.
    num_experts: int = 1
    num_experts_per_token: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 0.01
    moe_z_loss_weight: float = 1e-3
    # Scales the sown MoE losses; the Pipeline sets it to 1/num_microbatches
    # so per-microbatch sows sum back to the non-pipelined value.
    moe_loss_scale: float = 1.0
    # Pipeline parallelism (1 stage = no pipelining); see parallel/pipeline.py.
    pipeline_stages: int = 1
    pipeline_microbatches: int = 1
    pipeline_schedule: str = "gpipe"  # gpipe | 1f1b (remat-per-tick)
    # muP (Tensor Programs V): logits are divided by this width multiplier
    # (target_hidden / base_hidden).  1.0 = standard parametrization.  Set
    # automatically by ``mup.api.scale_config`` — never hand-written; pair
    # with ``mup.mu_adamw`` whose per-param lr comes from the same base
    # config.  Reference capability: ``atorch/mup/shape.py`` set_base_shapes.
    mup_readout_mult: float = 1.0
    # KV-cache decode mode: Attention maintains a "cache" collection of
    # size max_seq_len; each call appends its k/v at the cache index and
    # attends over everything written so far (prefill = one multi-token
    # call, then single-token steps).  See rl/generation.py.
    decode: bool = False
    # > 0: __call__ returns final hidden states and the trainer computes
    # head + CE chunked over the vocab (ops/chunked_ce.py) — the
    # (b, s, vocab) logits tensor never materializes (0.5 GB at 32k
    # vocab, 2 GB at 128k).  0 = normal logits output.
    fused_ce_chunks: int = 0

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test-scale config that still exercises GQA + scan."""
        base = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            max_seq_len=128,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama2_13b(cls, **kw) -> "LlamaConfig":
        base = dict(
            hidden_size=5120,
            intermediate_size=13824,
            num_layers=40,
            num_heads=40,
            num_kv_heads=40,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        base = dict(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            rope_theta=500000.0,
            max_seq_len=8192,
        )
        base.update(kw)
        return cls(**base)


def _rope(q, k, positions, head_dim: int, theta: float):
    """Rotary position embeddings applied to q/k: (..., seq, heads, head_dim)."""
    fraction = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta**fraction)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (b, s, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.cos(angles)[..., None, :]

    def rotate(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
        return out.astype(x.dtype)

    return rotate(q), rotate(k)


class RMSNorm(nn.Module):
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
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (norm * scale.astype(jnp.float32)).astype(self.dtype)


def _masked_attention(q, k, v, mask, scale=None):
    """Shared attention core (GQA head-repeat, 1/sqrt(d) scale unless the
    caller gives one, f32 masked softmax): ONE numerically sensitive
    implementation for both the causal training path and the KV-cache
    decode path."""
    d = q.shape[-1]
    n_q, n_kv = q.shape[2], k.shape[2]
    if n_q != n_kv:
        k = jnp.repeat(k, n_q // n_kv, axis=2)
        v = jnp.repeat(v, n_q // n_kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if scale is None:
        scores = scores / jnp.sqrt(d).astype(q.dtype)
    else:
        scores = scores * jnp.asarray(scale, q.dtype)
    scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def dot_product_attention(q, k, v, cfg: LlamaConfig, segment_ids=None):
    """Reference attention: causal, GQA via head repeat (XLA fuses this).

    Packed rows route through the chunked segmented reference — the causal
    ∧ same-segment predicate is computed per q-chunk, never materializing
    the (b, s, s) boolean mask in HBM (64M entries per head-broadcast at
    s=8192)."""
    if segment_ids is not None:
        from dlrover_tpu.ops.flash_attention import mha_reference

        return mha_reference(q, k, v, causal=True, segment_ids=segment_ids)
    s = q.shape[1]
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    return _masked_attention(q, k, v, causal[None, None])


def cached_attention(q, k_all, v_all, start_index, cfg: LlamaConfig):
    """Decode attention: q (b, s_in, h, d) over the cache (b, max, kv, d);
    position i of this call attends cache slots <= start_index + i.

    ``start_index`` may be per-row ``(b,)`` — rows at DIFFERENT sequence
    positions, the continuous-batching slot pool — or a scalar (every
    row in lockstep, the single-sequence sampler)."""
    s_in, max_len = q.shape[1], k_all.shape[1]
    start = jnp.broadcast_to(jnp.asarray(start_index), (q.shape[0],))
    qpos = start[:, None] + jnp.arange(s_in)[None, :]  # (b, s_in)
    kpos = jnp.arange(max_len)
    mask = (kpos[None, None, :] <= qpos[:, :, None])[:, None]  # (b,1,s,max)
    return _masked_attention(q, k_all, v_all, mask)


def _select_attention(cfg: LlamaConfig):
    if cfg.attention_impl == "flash":
        from dlrover_tpu.ops.flash_attention import flash_attention_gqa

        # The in-tree kernel was tuned and measured at 512 blocks; its
        # unfused bwd carries larger per-step vmem footprints than splash,
        # so the 1024 default (measured on splash only) is capped here.
        return partial(
            flash_attention_gqa,
            block_q=min(cfg.flash_block_q, 512),
            block_kv=min(cfg.flash_block_kv, 512),
        )
    if cfg.attention_impl == "splash":
        from dlrover_tpu.ops.splash_attention import splash_attention_gqa

        return partial(
            splash_attention_gqa,
            block_q=cfg.flash_block_q,
            block_kv=cfg.flash_block_kv,
        )
    if cfg.attention_impl == "ring":
        from dlrover_tpu.parallel.ring_attention import ring_attention

        return partial(ring_attention, axis_name="sp")
    if cfg.attention_impl == "ulysses":
        from dlrover_tpu.parallel.ulysses import ulysses_attention

        return partial(ulysses_attention, axis_name="sp")
    return None


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        d = cfg.resolved_head_dim
        dense = partial(
            nn.DenseGeneral,
            axis=-1,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            **_fp8_kwargs(cfg),
        )
        q = dense(
            features=(cfg.num_heads, d),
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "heads", "head_dim")
            ),
            name="q_proj",
        )(x)
        k = dense(
            features=(cfg.num_kv_heads, d),
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "kv_heads", "head_dim")
            ),
            name="k_proj",
        )(x)
        v = dense(
            features=(cfg.num_kv_heads, d),
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "kv_heads", "head_dim")
            ),
            name="v_proj",
        )(x)
        q = constrain(q, ("batch", "seq", "act_heads", "act_head_dim"))
        k = constrain(k, ("batch", "seq", "act_kv_heads", "act_head_dim"))
        v = constrain(v, ("batch", "seq", "act_kv_heads", "act_head_dim"))
        q, k = _rope(q, k, positions, d, cfg.rope_theta)

        if cfg.decode:
            if segment_ids is not None:
                raise ValueError(
                    "KV-cache decode does not support packed sequences "
                    "(segment_ids); generate per-sequence instead"
                )
            if cfg.attention_impl != "dot":
                raise ValueError(
                    "KV-cache decode uses its own cached attention; set "
                    f"attention_impl='dot' (got {cfg.attention_impl!r})"
                )
            # Append this call's (post-RoPE) k/v at the cache index, then
            # attend over every slot written so far — O(max_len) per step
            # instead of recomputing the O(T^2) prefix.
            b = x.shape[0]
            ck = self.variable(
                "cache", "cached_key",
                lambda: jnp.zeros(
                    (b, cfg.max_seq_len, cfg.num_kv_heads, d), k.dtype
                ),
            )
            cv = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros(
                    (b, cfg.max_seq_len, cfg.num_kv_heads, d), v.dtype
                ),
            )
            # Per-ROW index (b,): rows may sit at different positions —
            # that is what lets a continuous-batching slot pool decode
            # requests of different lengths in one jitted step (the
            # lockstep single-sequence sampler is the degenerate case of
            # all rows equal).
            ci = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((b,), jnp.int32),
            )
            idx = jnp.broadcast_to(ci.value, (b,))  # scalar-legacy safe
            rows = jnp.arange(b)[:, None]
            cols = idx[:, None] + jnp.arange(x.shape[1])[None, :]
            k_all = ck.value.at[rows, cols].set(k)
            v_all = cv.value.at[rows, cols].set(v)
            ck.value, cv.value = k_all, v_all
            ci.value = idx + x.shape[1]
            out = cached_attention(q, k_all, v_all, idx, cfg)
        else:
            attn_fn = _select_attention(cfg)
            if attn_fn is None:
                out = dot_product_attention(q, k, v, cfg, segment_ids)
            else:
                out = attn_fn(q, k, v, segment_ids=segment_ids)
        out = constrain(out, ("batch", "seq", "act_heads", "act_head_dim"))
        out = nn.DenseGeneral(
            features=cfg.hidden_size,
            axis=(-2, -1),
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            **_fp8_kwargs(cfg),
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("heads", "head_dim", "embed")
            ),
            name="o_proj",
        )(out)
        return constrain(out, ("batch", "seq", "act_embed"))


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = partial(
            nn.DenseGeneral,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            **_fp8_kwargs(cfg),
        )
        gate = dense(
            features=cfg.intermediate_size,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "mlp")
            ),
            name="gate_proj",
        )(x)
        up = dense(
            features=cfg.intermediate_size,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("embed", "mlp")
            ),
            name="up_proj",
        )(x)
        h = nn.silu(gate) * up
        h = constrain(h, ("batch", "seq", "act_mlp"))
        out = dense(
            features=cfg.hidden_size,
            kernel_init=param_with_axes(
                nn.initializers.lecun_normal(), ("mlp", "embed")
            ),
            name="down_proj",
        )(h)
        return constrain(out, ("batch", "seq", "act_embed"))


class DecoderBlock(nn.Module):
    """One transformer block; returns ``(carry, None)`` so it can be the
    body of an ``nn.scan`` over the `layers` logical axis."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="input_norm")(x)
        x = x + Attention(cfg, name="attention")(h, positions, segment_ids)
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="post_norm")(x)
        if cfg.num_experts > 1:
            from dlrover_tpu.models.moe import MoEMLP

            x = x + MoEMLP(
                hidden_size=cfg.hidden_size,
                intermediate_size=cfg.intermediate_size,
                num_experts=cfg.num_experts,
                num_experts_per_token=cfg.num_experts_per_token,
                capacity_factor=cfg.moe_capacity_factor,
                aux_loss_weight=cfg.moe_aux_loss_weight
                * cfg.moe_loss_scale,
                z_loss_weight=cfg.moe_z_loss_weight * cfg.moe_loss_scale,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="moe_mlp",
            )(h)
        else:
            x = x + MLP(cfg, name="mlp")(h)
        return constrain(x, ("batch", "seq", "act_embed")), None


_REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots_saveable": jax.checkpoint_policies.dots_saveable,
    "dots_with_no_batch_dims": (
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    ),
}


def remat_policy(name: str):
    if name == "offload":
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=[],
            offload_src="device",
            offload_dst="pinned_host",
        )
    return _REMAT_POLICIES.get(name)


class LlamaModel(nn.Module):
    """Decoder-only LM.  __call__ returns logits (b, s, vocab)."""

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
        x = constrain(x, ("batch", "seq", "act_embed"))

        block_cls = DecoderBlock
        if cfg.remat_policy != "none":
            block_cls = nn.remat(
                DecoderBlock,
                policy=remat_policy(cfg.remat_policy),
                prevent_cse=not cfg.scan_layers,
            )
        if cfg.decode and cfg.pipeline_stages > 1:
            raise ValueError("KV-cache decode does not support pipelining")
        if (
            cfg.use_fp8
            and cfg.fp8_scaling == "delayed"
            and cfg.pipeline_stages > 1
        ):
            raise ValueError(
                "delayed fp8 scaling is not plumbed through the pipeline "
                "schedule; use fp8_scaling='dynamic' with pipelining"
            )
        if cfg.pipeline_stages > 1:
            from dlrover_tpu.parallel.pipeline import Pipeline

            x = Pipeline(
                block_cls=block_cls,
                cfg=cfg,
                num_layers=cfg.num_layers,
                num_stages=cfg.pipeline_stages,
                num_microbatches=max(cfg.pipeline_microbatches, 1),
                schedule=cfg.pipeline_schedule,
                name="pipeline",
            )(x, positions, segment_ids)
        elif cfg.scan_layers:
            x, _ = nn.scan(
                block_cls,
                # intermediates must be declared or sown MoE losses are
                # silently dropped at the scan boundary.
                variable_axes={
                    "params": 0, "intermediates": 0, "cache": 0,
                    # delayed-fp8 amax histories: one per layer
                    "fp8": 0,
                },
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="layers")(x, positions, segment_ids)
        else:
            for i in range(cfg.num_layers):
                x, _ = block_cls(cfg, name=f"layers_{i}")(x, positions, segment_ids)

        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="final_norm")(x)
        # Decode always needs logits (the sampler consumes them); fused-CE
        # is a training-loss optimization only.
        if cfg.fused_ce_chunks > 0 and not cfg.decode:
            # Fused-loss mode: return final hidden states; the trainer
            # computes head-matmul + CE chunked (ops/chunked_ce.py) so the
            # (b, s, vocab) logits never materialize.  The lm_head param
            # is still registered (dummy 1-token call, DCE'd by XLA) so
            # the param tree, shardings, and checkpoints are identical to
            # the unfused configuration.
            if not cfg.tie_embeddings:
                nn.DenseGeneral(
                    features=cfg.vocab_size,
                    dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    use_bias=False,
                    kernel_init=param_with_axes(
                        nn.initializers.lecun_normal(), ("embed", "vocab")
                    ),
                    name="lm_head",
                )(jnp.zeros((1, 1, cfg.hidden_size), cfg.dtype))
            if cfg.mup_readout_mult != 1.0:
                x = x / cfg.mup_readout_mult
            return constrain(x, ("batch", "seq", "act_embed"))
        if cfg.tie_embeddings:
            logits = jnp.einsum("bse,ve->bsv", x, embed.astype(cfg.dtype))
        else:
            logits = nn.DenseGeneral(
                features=cfg.vocab_size,
                dtype=(
                    jnp.float32 if cfg.logits_dot_in_fp32 else cfg.dtype
                ),
                param_dtype=cfg.param_dtype,
                use_bias=False,
                kernel_init=param_with_axes(
                    nn.initializers.lecun_normal(), ("embed", "vocab")
                ),
                name="lm_head",
            )(x)
        if cfg.mup_readout_mult != 1.0:
            # muP readout: logit scale stays width-invariant (the transfer
            # condition); the division lives in the forward pass so tied
            # and untied heads behave identically.
            logits = logits / cfg.mup_readout_mult
        if cfg.logits_f32_output:
            logits = logits.astype(jnp.float32)
        return constrain(logits, ("batch", "seq", "act_vocab"))


def fused_ce_loss(cfg: LlamaConfig, params, hidden, batch):
    """Loss for ``fused_ce_chunks`` mode: head matmul + CE streamed over
    vocab chunks (:mod:`dlrover_tpu.ops.chunked_ce`), logits never
    materialized.  ``hidden`` is the model output (b, s, e); the head
    weight comes out of ``params`` (tied: the embedding, transposed).
    The chunk GEMM honors ``logits_dot_in_fp32`` (f32 operands when set,
    else ``cfg.dtype``); softmax math is always f32.
    """
    from dlrover_tpu.ops.chunked_ce import chunked_linear_cross_entropy

    b, s, e = hidden.shape
    # Honor logits_dot_in_fp32 exactly like the unfused head (the chunked
    # GEMM runs in the operands' dtype).
    gemm_dtype = jnp.float32 if cfg.logits_dot_in_fp32 else cfg.dtype
    hidden = hidden.astype(gemm_dtype)
    if cfg.tie_embeddings:
        w = params["embed_tokens"].astype(gemm_dtype).T
    else:
        w = params["lm_head"]["kernel"].astype(gemm_dtype)
    mask = batch.get("mask")
    return chunked_linear_cross_entropy(
        hidden.reshape(b * s, e),
        w,
        batch["labels"].reshape(-1),
        cfg.fused_ce_chunks,
        None if mask is None else mask.reshape(-1),
    )


def cross_entropy_loss(logits, targets, mask=None):
    """Token-level CE with optional padding mask; stays in f32.

    Formulated as ``logits[target] - logsumexp(logits)`` instead of a full
    ``log_softmax``: the (b, s, vocab) log-prob tensor never materializes
    in HBM (logsumexp reduces it), worth ~3% step time at 32k vocab.
    """
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    tgt = jnp.take_along_axis(logits32, targets[..., None], axis=-1)[..., 0]
    ll = tgt - lse
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
