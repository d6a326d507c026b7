"""The optimization zoo: each class edits the ModelContext.

Reference parity: ``atorch/auto/opt_lib/`` — zero_optimization.py (zero1/2,
fsdp), tensor_parallel_optimization.py, sequence_parallel_optimization.py,
pipeline_parallel_optimization.py, mixed_parallel_optimization.py,
amp_optimization.py, half_optimization.py, checkpoint_optimization.py,
module_replace_optimization.py.  The torch versions rewrite modules and wrap
optimizers; the TPU versions steer GSPMD: mesh axis sizes, logical-axis rule
tables, model-config overrides, and optax wrappers.  The collectives the
reference codes by hand (column/row TP, Ulysses all-to-all, ZeRO
reduce-scatter) are *derived* by XLA from these edits.
"""

from typing import Any, Dict, Optional

import jax.numpy as jnp

from dlrover_tpu.auto.model_context import ModelContext
from dlrover_tpu.parallel.sharding import DP_RULES, FSDP_RULES, FSDP_TP_RULES


class Optimization:
    """tune() refines a config against the context; transform() applies it."""

    name = "base"
    # Groups that conflict: only one per group may be applied.
    group: Optional[str] = None

    def tune(self, ctx: ModelContext, config: Dict[str, Any]) -> Dict[str, Any]:
        return config

    def transform(self, ctx: ModelContext, config: Dict[str, Any]) -> None:
        raise NotImplementedError


# -- data parallel family ---------------------------------------------------


class ParallelModeOptimization(Optimization):
    """Pure DP (reference ``parallel_mode``): batch over dp, params replicated."""

    name = "parallel_mode"
    group = "zero"

    def transform(self, ctx, config):
        ctx.install_base_rules(DP_RULES)


def _set_fsdp_axis(ctx, config):
    """Give the fsdp mesh axis its size (explicit, or all remaining dp ways)."""
    size = int(config.get("fsdp_size", 0))
    if size:
        ctx.mesh_config.fsdp = size
    elif ctx.mesh_config.fsdp == 1:
        ctx.mesh_config.fsdp = -1
        ctx.mesh_config.dp = 1


class Zero1Optimization(Optimization):
    """ZeRO-1: optimizer state sharded over fsdp, params/grads replicated.

    Reference ``zero_optimization.py:115`` wraps fairscale OSS; here it's an
    *overlay* applied to the optimizer-state subtree's rule table at
    finalize time (see ``create_sharded_state(opt_state_rules=...)``) — an
    overlay rather than a snapshot so later tp/sp rule edits reach the
    optimizer state too.
    """

    name = "zero1"
    group = "zero"

    def transform(self, ctx, config):
        ctx.install_base_rules(DP_RULES)
        _set_fsdp_axis(ctx, config)
        ctx.opt_state_overlay = {"embed": "fsdp"}


class Zero2Optimization(Zero1Optimization):
    """ZeRO-2 = ZeRO-1 + gradient sharding.  Under one jitted SPMD program
    gradients are transient values XLA already materializes sharded wherever
    their consumers (the fsdp-sharded optimizer update) want them — so the
    rule-table effect equals zero1; the distinction the reference maintains
    (persistent grad buckets) has no analog when there is no per-rank grad
    storage."""

    name = "zero2"
    group = "zero"


class FSDPOptimization(Optimization):
    """ZeRO-3 / FSDP: params themselves sharded over fsdp; GSPMD inserts the
    per-layer just-in-time all-gathers (reference ``zero_optimization.py:240``
    + auto-wrap policies, which scan-over-layers makes unnecessary)."""

    name = "fsdp"
    group = "zero"

    def tune(self, ctx, config):
        config.setdefault("fsdp_size", 0)  # 0 = all remaining ways
        return config

    def transform(self, ctx, config):
        ctx.install_base_rules(FSDP_RULES)
        _set_fsdp_axis(ctx, config)
        ctx.opt_state_overlay = None  # params already sharded -> states follow


# -- model parallel family --------------------------------------------------


class TensorParallelOptimization(Optimization):
    """Megatron-style TP: head/mlp/vocab dims over tp.  Reference builds
    column/row-parallel layer classes (``modules/distributed_modules/
    layers.py``); here the same math falls out of the rule table."""

    name = "tensor_parallel"

    def tune(self, ctx, config):
        if "tp_size" not in config:
            n = ctx.n_devices()
            # Largest divisor of the device count that is <= 4.
            config["tp_size"] = max(
                d for d in (1, 2, 3, 4) if n % d == 0
            )
        return config

    def transform(self, ctx, config):
        tp = int(config.get("tp_size", 1))
        ctx.mesh_config.tp = tp
        for axis in ("heads", "kv_heads", "mlp", "vocab",
                     "act_heads", "act_kv_heads", "act_mlp", "act_vocab"):
            ctx.set_rule(axis, "tp")


class SequenceParallelOptimization(Optimization):
    """Ulysses/ring SP (reference ``sequence_parallel_optimization.py:10``
    and ``distributed_attention.py``): shard the sequence dim over sp and
    pick the attention implementation that keeps it exact."""

    name = "sequence_parallel"

    def tune(self, ctx, config):
        config.setdefault("sp_size", 2)
        config.setdefault("impl", "ulysses")  # ulysses | ring
        return config

    def transform(self, ctx, config):
        ctx.mesh_config.sp = int(config.get("sp_size", 2))
        ctx.set_rule("seq", "sp")
        impl = config.get("impl", "ulysses")
        ctx.override_model(attention_impl=impl)


class ExpertParallelOptimization(Optimization):
    """MoE expert parallelism: expert dim over ep, tokens all-to-all."""

    name = "expert_parallel"

    def transform(self, ctx, config):
        ctx.mesh_config.ep = int(config.get("ep_size", ctx.mesh_config.ep))
        ctx.set_rule("expert", "ep")


class PipelineParallelOptimization(Optimization):
    """Pipeline stages over the pp mesh axis (DCN-tolerant).  Reference
    compiles torch graphs with PiPPy; here the model runs as pipelined
    shard_map stages (``dlrover_tpu/parallel/pipeline.py``)."""

    name = "pipeline_parallel"

    def tune(self, ctx, config):
        config.setdefault("pp_size", 2)
        config.setdefault("num_microbatches", 8)
        # 1f1b (remat-per-tick) bounds live activations by the stage chain;
        # the right default once microbatches outnumber stages.
        config.setdefault("schedule", "1f1b")
        return config

    def transform(self, ctx, config):
        pp = int(config.get("pp_size", 2))
        ctx.mesh_config.pp = pp
        ctx.override_model(
            pipeline_stages=pp,
            pipeline_microbatches=int(config.get("num_microbatches", 8)),
            pipeline_schedule=config.get("schedule", "gpipe"),
        )


class MixedParallelOptimization(Optimization):
    """Compose tp/pp/sp/ep/fsdp in one config (reference
    ``mixed_parallel_optimization.py:32``).  config example:
    {"tp_size": 4, "pp_size": 2, "fsdp_size": 0, "sp_size": 1}."""

    name = "mixed_parallel"

    def transform(self, ctx, config):
        zero = config.get("zero", "fsdp")  # fsdp | zero1 | zero2 | none
        if zero == "fsdp":
            FSDPOptimization().transform(
                ctx, {"fsdp_size": config.get("fsdp_size", 0)}
            )
        elif zero in ("zero1", "zero2"):
            Zero1Optimization().transform(
                ctx, {"fsdp_size": config.get("fsdp_size", 0)}
            )
        if int(config.get("tp_size", 1)) > 1:
            TensorParallelOptimization().transform(
                ctx, {"tp_size": config["tp_size"]}
            )
        if int(config.get("sp_size", 1)) > 1:
            SequenceParallelOptimization().transform(
                ctx,
                {"sp_size": config["sp_size"],
                 "impl": config.get("sp_impl", "ulysses")},
            )
        if int(config.get("ep_size", 1)) > 1:
            ExpertParallelOptimization().transform(
                ctx, {"ep_size": config["ep_size"]}
            )
        if int(config.get("pp_size", 1)) > 1:
            PipelineParallelOptimization().transform(
                ctx,
                {"pp_size": config["pp_size"],
                 "num_microbatches": config.get("num_microbatches", 8),
                 "schedule": config.get("schedule", "gpipe")},
            )


# -- precision family -------------------------------------------------------


class AmpNativeOptimization(Optimization):
    """bf16 compute / f32 params+optimizer — the TPU-native AMP (no loss
    scaling needed: bf16 shares float32's exponent range, unlike fp16)."""

    name = "amp_native"
    group = "precision"

    def transform(self, ctx, config):
        ctx.override_model(dtype=jnp.bfloat16, param_dtype=jnp.float32)


class Fp8Optimization(Optimization):
    """Scaled-e4m3 matmuls in the dense projections (reference
    ``amp_optimization.py:112`` Fp8 via TransformerEngine; here a
    drop-in ``dot_general`` — ``ops/fp8.py``).  Composes with amp_native:
    activations stay bf16, only the dots run fp8."""

    name = "fp8"
    group = "matmul_precision"

    def transform(self, ctx, config):
        overrides = {"use_fp8": True}
        scaling = config.get("scaling", "dynamic")
        if scaling not in ("dynamic", "delayed"):
            raise ValueError(f"fp8 scaling must be dynamic|delayed: {scaling}")
        overrides["fp8_scaling"] = scaling
        if "amax_history" in config:
            overrides["fp8_amax_history"] = int(config["amax_history"])
        ctx.override_model(**overrides)


class HalfOptimization(Optimization):
    """Pure bf16 (params too): halves param HBM; pair with f32 master
    weights in the optimizer if loss curves degrade."""

    name = "half"
    group = "precision"

    def transform(self, ctx, config):
        dtype = jnp.bfloat16 if config.get("dtype", "bf16") == "bf16" else (
            jnp.float16
        )
        ctx.override_model(dtype=dtype, param_dtype=dtype)


# -- memory family ----------------------------------------------------------


class CheckpointOptimization(Optimization):
    """Activation rematerialization (reference ``checkpoint_optimization``):
    policy names map to jax.checkpoint policies inside the scanned block."""

    name = "checkpoint"

    def tune(self, ctx, config):
        config.setdefault("policy", "dots_saveable")
        return config

    def transform(self, ctx, config):
        ctx.override_model(remat_policy=config.get("policy", "full"))


# The chunked head+CE becomes the default once the materialized logits
# tensor would exceed this many bytes (bf16).  256MB ≈ a 32k-vocab
# batch-8 seq-1024 step — below it the plain head is fine, above it the
# logits buffer starts crowding HBM (2 GB at 128k vocab).  This is the
# memory-bound crossover; not measured on the chip (ROADMAP S4: a cell
# whose head and loss are a large share settles it).
FUSED_CE_AUTO_LOGITS_BYTES = 256 * 2**20


class ModuleReplaceOptimization(Optimization):
    """Swap hot modules for optimized kernels (reference swaps HF modules
    for flash-attn CUDA modules and its fused cross-entropy,
    ``module_replace_optimization.py``): the attention implementation
    and, with ``fused_ce_chunks > 0``, the chunked fused linear+CE head
    (``ops/chunked_ce.py``) that never materializes the logits.

    ``fused_ce_chunks="auto"`` sizes the decision from the model itself:
    chunk whenever the would-be logits tensor exceeds
    ``FUSED_CE_AUTO_LOGITS_BYTES``, with enough chunks to keep each
    chunk's logits slab near 32MB.  When the knob is UNSET, the default
    depends on the caller: the framework trainer path (whose train/eval
    steps handle the hidden-states ``__call__`` contract) opts in via
    ``ctx.fused_ce_auto=True``; a direct ``transform`` caller defaults to
    ``0`` — silently changing what ``apply_fn`` returns under their feet
    is exactly the surprise this guards against."""

    name = "module_replace"

    def transform(self, ctx, config):
        from dlrover_tpu.common.log import logger

        overrides = {
            "attention_impl": config.get("attention_impl", "flash")
        }
        default_chunks = (
            "auto" if getattr(ctx, "fused_ce_auto", False) else 0
        )
        chunks = config.get("fused_ce_chunks", default_chunks)
        if chunks == "auto":
            chunks = self._auto_chunks(ctx)
            if chunks:
                # Loud, because this changes the optimized model's
                # __call__ contract: it returns final hidden states (the
                # trainer computes head+CE chunked) instead of logits.
                # auto_accelerate's own train/eval steps handle it; a
                # consumer reading logits off apply_fn directly should
                # pass fused_ce_chunks=0 explicitly.
                logger.info(
                    "module_replace: auto-selected chunked fused CE "
                    "(%d chunks) — the logits tensor would exceed the "
                    "%.0fMB crossover; model __call__ now returns hidden "
                    "states and the trainer fuses head+CE",
                    chunks, FUSED_CE_AUTO_LOGITS_BYTES / 2**20,
                )
        chunks = int(chunks)
        if chunks > 0:
            overrides["fused_ce_chunks"] = chunks
        ctx.override_model(**overrides)

    @staticmethod
    def _auto_chunks(ctx) -> int:
        cfg = getattr(ctx.model, "cfg", None) or getattr(
            ctx.model, "config", None
        )
        if not hasattr(cfg, "fused_ce_chunks"):
            return 0  # model family without a fused head: nothing to swap
        vocab = getattr(cfg, "vocab_size", 0)
        if not vocab or ctx.sample_batch is None:
            return 0
        ids = ctx.sample_batch.get("input_ids")
        if ids is None:
            return 0
        tokens = int(ids.shape[0]) * int(ids.shape[1])
        logits_bytes = tokens * vocab * 2  # bf16
        if logits_bytes <= FUSED_CE_AUTO_LOGITS_BYTES:
            return 0
        # enough chunks for ~32MB logits slabs, at least 4 — but the
        # chunked head requires chunks | vocab, so snap to the nearest
        # divisor (upward first: finer chunks only cost a little scan
        # overhead, a non-divisor costs a trace-time ValueError).
        want = max(4, -(-logits_bytes // (32 * 2**20)))
        for d in range(want, min(vocab, want * 8) + 1):
            if vocab % d == 0:
                return d
        for d in range(min(want, vocab), 3, -1):
            if vocab % d == 0:
                return d
        return 0  # pathological vocab (prime): stay unfused


class GradAccumulationOptimization(Optimization):
    """Keep the global batch fixed by accumulating micro-batches (the
    elastic trainer drives the factor as the world resizes)."""

    name = "grad_accumulation"

    def transform(self, ctx, config):
        ctx.grad_accum = max(1, int(config.get("steps", 1)))


class WeightUpdateShardingOptimization(Optimization):
    """Cross-replica weight-update sharding (ZeRO-on-TPU, arXiv
    2004.13336; ``parallel/wus.py``): gradients reduce-scatter over the
    replica axes, each replica updates 1/N of the optimizer state, and
    params all-gather back — optimizer HBM and update FLOPs ÷ N.

    ``mode="scatter"`` (default) keeps params stored in their base
    layout; ``mode="gather"`` also stores params scattered and places
    the re-gather at the top of the step so it overlaps early forward
    compute (the 1F1B warm-up window — see ``parallel/pipeline.py``).
    """

    name = "weight_update_sharding"

    def tune(self, ctx, config):
        config.setdefault("mode", "scatter")
        return config

    def transform(self, ctx, config):
        mode = config.get("mode", "scatter")
        from dlrover_tpu.parallel.wus import MODES

        if mode not in MODES:
            raise ValueError(
                f"weight_update_sharding mode {mode!r} not in {MODES}"
            )
        ctx.weight_update_sharding = mode


class QuantizedOptimizerOptimization(Optimization):
    """8-bit Adam states (reference: CUDA quantization_optimizer.cu via the
    atorch opt registry) — ~4x less optimizer HBM."""

    name = "quantized_optimizer"

    def transform(self, ctx, config):
        import optax

        from dlrover_tpu.common.log import logger
        from dlrover_tpu.optimizers.quantized import scale_by_quantized_adam

        if ctx.optimizer is not None:
            logger.warning(
                "quantized_optimizer replaces the configured optimizer; "
                "pass lr/schedule via its config to control it"
            )
        # Mirror default_optimizer()'s schedule/hyperparams so adding this
        # opt changes only the state storage, not the training dynamics.
        lr = config.get("lr", 3e-4)
        schedule = optax.warmup_cosine_decay_schedule(
            0.0,
            lr,
            config.get("warmup_steps", 100),
            max(config.get("total_steps", 10000),
                config.get("warmup_steps", 100) + 1),
        )
        ctx.optimizer = optax.chain(
            optax.clip_by_global_norm(config.get("grad_clip", 1.0)),
            scale_by_quantized_adam(
                b1=config.get("b1", 0.9),
                b2=config.get("b2", 0.95),
                block_size=config.get("block_size", 256),
                # Under weight-update sharding set this to the replica
                # count: per-shard code padding keeps block boundaries
                # on the partition boundaries (optimizers/quantized.py).
                shards=config.get("shards", 1),
            ),
            optax.add_decayed_weights(config.get("weight_decay", 0.1)),
            optax.scale_by_learning_rate(schedule),
        )


class Bf16OptimizerOptimization(Optimization):
    """fp32 master weights for bf16 params (pairs with the `half` opt)."""

    name = "bf16_optimizer"

    def transform(self, ctx, config):
        from dlrover_tpu.optimizers.bf16_optimizer import bf16_mixed_precision

        if bf16_mixed_precision not in ctx.optimizer_wrappers:
            ctx.optimizer_wrappers.append(bf16_mixed_precision)
