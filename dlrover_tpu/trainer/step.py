"""Sharded train-state initialization and jitted train/eval steps.

This is the heart of the compute path: *one* jitted SPMD program over a
``jax.sharding.Mesh`` replaces the reference's per-process DDP/FSDP/TP module
stack (atorch ``auto/accelerate.py`` model_transform).  The parallelism
strategy enters only through (a) the mesh shape and (b) the logical-axis rule
table; GSPMD derives all collectives.

Key mechanics (maxtext/t5x pattern):
- ``jax.eval_shape`` over the full TrainState builder gives an abstract boxed
  (``nn.Partitioned``) tree; optimizer states built by ``tree_map`` inherit
  the boxes, so optimizer sharding comes for free;
- ``nn.logical_to_mesh_sharding`` turns logical specs into NamedShardings;
- init runs *inside jit with out_shardings* so a 70B model never materializes
  unsharded (reference analog: atorch meta-model init,
  ``utils/meta_model_utils.py``);
- train_step donates the state: in-place buffer reuse, no HBM double-booking.
"""

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax.linen import partitioning as nn_partitioning
from flax.training import train_state
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dlrover_tpu.models.llama import cross_entropy_loss
from dlrover_tpu.parallel import wus
from dlrover_tpu.parallel.mesh import use_mesh
from dlrover_tpu.parallel.sharding import (
    Rules,
    count_constraints,
    logical_to_spec,
    replica_axes_from_rules,
)


class TrainState(train_state.TrainState):
    """flax TrainState + non-param variable collections.

    ``variables`` holds mutable collections that must persist across steps
    (today: the ``fp8`` amax-history state for delayed scaling); empty for
    ordinary models.  It is a normal pytree field: checkpointing, sharding
    and donation treat it like any other state."""

    variables: Any = None


def create_sharded_state(
    model: nn.Module,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: Rules,
    rng: jax.Array,
    sample_batch: Dict[str, Any],
    opt_state_rules: Optional[Rules] = None,
    weight_update_sharding: Optional[str] = None,
):
    """Build a TrainState fully sharded from birth.

    Returns ``(state, state_shardings)``; the shardings tree matches the
    unboxed state and is reused for the train step's in/out shardings and by
    the checkpoint engine for reshard-on-restore.

    ``opt_state_rules`` shards the *optimizer state* with a different rule
    table than the params — that's ZeRO-1 under GSPMD: params replicated
    (dp rules) while Adam moments shard over ``fsdp``; XLA inserts the
    reduce-scatter/all-gather around the update automatically.

    ``weight_update_sharding`` (``"scatter"`` / ``"gather"``) turns on
    cross-replica weight-update sharding (``parallel/wus.py``): the
    optimizer state is born scattered over the free ``dp``/``fsdp``
    replica axes (and params too, in ``gather`` mode).  The return
    becomes a triple ``(state, state_shardings, plan)`` — hand the plan
    to ``make_train_step(weight_update_sharding=plan)`` so the step and
    the storage layout agree.
    """

    def _build(rng):
        variables = model.init(rng, sample_batch["input_ids"])
        params = variables["params"]
        extra = {k: v for k, v in variables.items() if k != "params"}
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=optimizer,
            variables=extra,
        )

    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
        abs_state = jax.eval_shape(_build, rng)
        specs = nn.get_partition_spec(abs_state)
        shardings = nn.logical_to_mesh_sharding(specs, mesh, list(rules))
        if opt_state_rules is not None:
            shardings = shardings.replace(
                opt_state=nn.logical_to_mesh_sharding(
                    specs.opt_state, mesh, list(opt_state_rules)
                )
            )
        plan = None
        if weight_update_sharding is not None:
            plan = wus.make_plan(
                mesh, shardings, nn.unbox(abs_state),
                mode=weight_update_sharding,
                axes=replica_axes_from_rules(rules) or None,
            )
        # Init always runs in the base layout: with non-partitionable
        # threefry (the default here) random bits inside jit depend on the
        # output sharding, so initializing straight into the scattered
        # layout would give different initial weights than a non-WUS run.
        # Relayout after the fact instead — bit-identical across modes.
        init_fn = jax.jit(_build, out_shardings=shardings)
        from dlrover_tpu.telemetry.spans import span

        with span("compile", what="init"):
            state = init_fn(rng)
    state = nn.unbox(state)
    if weight_update_sharding is not None:
        shardings = wus.apply_plan_to_shardings(shardings, plan)
        state = jax.device_put(state, shardings)
        return state, shardings, plan
    return state, shardings


def data_sharding(mesh: Mesh, rules: Rules) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(("batch", "seq"), rules))


def make_train_step(
    model: nn.Module,
    mesh: Mesh,
    rules: Rules,
    state_shardings,
    loss_fn: Optional[Callable] = None,
    donate_state: bool = True,
    gradient_fn_factory: Optional[Callable] = None,
    weight_update_sharding=None,
    abstract_state=None,
) -> Callable:
    """Build the jitted SPMD train step: (state, batch) -> (state, metrics).

    batch = {"input_ids": (b, s) int32, "labels": (b, s) int32,
             optional "mask": (b, s), optional "positions"/"segment_ids"}.

    ``weight_update_sharding`` turns on cross-replica weight-update
    sharding (``parallel/wus.py``): pass the :class:`wus.WusPlan` that
    ``create_sharded_state(weight_update_sharding=...)`` returned, or
    the string ``"scatter"`` together with ``abstract_state``
    (``jax.eval_shape(lambda s: s, state)``) to build the plan here.
    ``"gather"`` mode stores params scattered, so its plan must come
    from ``create_sharded_state`` — the storage layout and the step
    must agree from birth.
    """
    fused_cfg = _fused_ce_cfg(model, loss_fn)
    loss_fn = loss_fn or _default_lm_loss
    wus_plan = _resolve_wus(
        weight_update_sharding, mesh, rules, state_shardings, abstract_state
    )
    if wus_plan is not None:
        state_shardings = wus.apply_plan_to_shardings(
            state_shardings, wus_plan
        )
    if donate_state and jax.default_backend() == "cpu":
        # XLA's CPU client has a donation race under async dispatch on
        # forced multi-device hosts: donating state buffers that came
        # through device_put (restore path) aborts the process with
        # ``cpu_client.cc Check failed: buffer_info.buffer.IsAvailable()``
        # or glibc heap corruption within a few steps of a checkpoint
        # restore.  Donation only exists to avoid HBM double-booking —
        # worthless on host RAM — so keep it for real accelerators only.
        donate_state = False
    batch_shard = data_sharding(mesh, rules)
    replicated = NamedSharding(mesh, PartitionSpec())
    # Collections the state carries across steps (e.g. 'fp8' amax
    # histories).  Known at build time from the shardings tree structure.
    extra_keys = sorted(getattr(state_shardings, "variables", None) or {})
    if extra_keys and gradient_fn_factory is not None:
        raise ValueError(
            "gradient_fn_factory assumes a scalar loss; models carrying "
            f"mutable collections {extra_keys} need the aux-returning "
            "default gradient path"
        )

    def _step(state: TrainState, batch: Dict[str, Any]):
        # Under WUS "gather" mode the stored params are 1/N-scattered;
        # this constraint is the explicit all-gather, placed before any
        # compute so the latency-hiding scheduler overlaps it with the
        # first microbatches' forward (1F1B: stage k's gather runs
        # under stages <k's ticks).  "scatter" mode: identity.
        full_params = (
            wus_plan.gather_params(state.params)
            if wus_plan is not None else state.params
        )

        def compute_loss(params):
            # getattr: LoRA and other callers bring their own TrainState
            # subclasses without the variables field.
            logits, aux_vars = state.apply_fn(
                {"params": params,
                 **(getattr(state, "variables", None) or {})},
                batch["input_ids"],
                batch.get("positions"),
                batch.get("segment_ids"),
                mutable=["intermediates"] + extra_keys,
            )
            if fused_cfg is not None:
                from dlrover_tpu.models.llama import fused_ce_loss

                # fused-CE mode: the model returned hidden states, the
                # head matmul lives inside the chunked loss.
                loss = fused_ce_loss(fused_cfg, params, logits, batch)
            else:
                loss = loss_fn(logits, batch)
            # MoE load-balancing/z losses arrive sown in intermediates, as
            # do the dropless layer's step metrics (pairs routed to each
            # held expert and to elsewhere, by layer; the layers whose load
            # fit one pass over the small pairs buffer).
            from dlrover_tpu.models.moe import (
                collect_moe_losses,
                collect_moe_metrics,
            )

            sown = aux_vars.get("intermediates", {})
            loss = loss + collect_moe_losses(sown)
            return loss, (
                {k: aux_vars[k] for k in extra_keys},
                collect_moe_metrics(sown))

        if gradient_fn_factory is not None:
            (loss, ), grads = gradient_fn_factory(
                lambda p: compute_loss(p)[0])(full_params)
            new_vars, moe_metrics = {}, {}
        else:
            (loss, (new_vars, moe_metrics)), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(full_params)
        if wus_plan is not None:
            # The reduce-scatter point: grads leave their base layout for
            # the 1/N-scattered one, so the optimizer below runs on each
            # replica's shard of grads + state.
            grads = wus_plan.scatter_grads(grads)
        if extra_keys:
            new_state = state.apply_gradients(
                grads=grads,
                variables=jax.lax.stop_gradient(new_vars),
            )
        else:
            new_state = state.apply_gradients(grads=grads)
        gnorm = optax.global_norm(grads)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "step": new_state.step,
            **moe_metrics,
        }
        return new_state, metrics

    jitted = jax.jit(
        _step,
        in_shardings=(state_shardings, batch_shard),
        out_shardings=(state_shardings, replicated),
        donate_argnums=(0,) if donate_state else (),
    )

    compiled = [False]

    def step_with_rules(state, batch):
        # The models' activation constraints (`sharding.constrain`) and
        # the ring/ulysses shard_map regions read the rule table and the
        # mesh at trace time: `constrain` takes the mesh from
        # `current_mesh()`, which `use_mesh` sets.  Afterwards the jit
        # cache makes this context free.
        with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
            if not compiled[0]:
                # First call pays trace+XLA compile: a telemetry span so
                # the trace and goodput attribution both see it.  (A
                # reshape after reform re-jits; that shows as a fresh
                # process's first-call span, which is exactly right.)
                compiled[0] = True
                from dlrover_tpu.telemetry.spans import span

                with span("compile", what="train_step") as end, \
                        count_constraints() as applied:
                    out = jitted(state, batch)
                    end["activation_constraints"] = applied[0]
                    return out
            return jitted(state, batch)

    step_with_rules.jitted = jitted
    step_with_rules.batch_sharding = batch_shard
    return step_with_rules


def _resolve_wus(weight_update_sharding, mesh, rules, state_shardings,
                 abstract_state):
    """Normalize the ``weight_update_sharding`` argument to a WusPlan."""
    if weight_update_sharding is None:
        return None
    if isinstance(weight_update_sharding, wus.WusPlan):
        return weight_update_sharding
    mode = str(weight_update_sharding)
    if mode == "gather" and abstract_state is None:
        raise ValueError(
            "weight_update_sharding='gather' stores params scattered; "
            "build the plan where the state is born — "
            "create_sharded_state(weight_update_sharding='gather') — "
            "and pass the returned plan here"
        )
    if abstract_state is None:
        raise ValueError(
            "weight_update_sharding as a string needs abstract_state="
            "jax.eval_shape(lambda s: s, state) to decide per-leaf "
            "divisibility; or pass the WusPlan from create_sharded_state"
        )
    return wus.make_plan(
        mesh, state_shardings, abstract_state, mode=mode,
        axes=replica_axes_from_rules(rules) or None,
    )


def make_eval_step(model, mesh, rules, state_shardings, loss_fn=None,
                   weight_update_sharding=None):
    fused_cfg = _fused_ce_cfg(model, loss_fn)
    loss_fn = loss_fn or _default_lm_loss
    batch_shard = data_sharding(mesh, rules)
    replicated = NamedSharding(mesh, PartitionSpec())
    wus_plan = (
        weight_update_sharding
        if isinstance(weight_update_sharding, wus.WusPlan) else None
    )
    if wus_plan is not None:
        state_shardings = wus.apply_plan_to_shardings(
            state_shardings, wus_plan
        )

    def _eval(state: TrainState, batch):
        # Extra collections (fp8 scales) enter read-only: the module
        # skips its history update when the collection is immutable.
        params = (
            wus_plan.gather_params(state.params)
            if wus_plan is not None else state.params
        )
        logits = state.apply_fn(
            {"params": params, **(getattr(state, "variables", None) or {})},
            batch["input_ids"],
            batch.get("positions"),
            batch.get("segment_ids"),
        )
        if fused_cfg is not None:
            from dlrover_tpu.models.llama import fused_ce_loss

            return {"loss": fused_ce_loss(
                fused_cfg, params, logits, batch
            )}
        return {"loss": loss_fn(logits, batch)}

    jitted = jax.jit(
        _eval,
        in_shardings=(state_shardings, batch_shard),
        out_shardings=replicated,
    )

    def eval_with_rules(state, batch):
        with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
            return jitted(state, batch)

    return eval_with_rules


def _default_lm_loss(logits, batch):
    return cross_entropy_loss(logits, batch["labels"], batch.get("mask"))


def _fused_ce_cfg(model, loss_fn):
    """Return the model config when fused_ce_chunks mode is active.

    The flag changes what the model RETURNS (hidden states, not logits),
    so a user-supplied loss_fn expecting logits cannot compose with it —
    fail loudly at build time instead of silently feeding it hidden.
    """
    cfg = getattr(model, "cfg", None)
    if not cfg or getattr(cfg, "fused_ce_chunks", 0) <= 0:
        return None
    if loss_fn is not None:
        raise ValueError(
            "fused_ce_chunks > 0 computes the loss inside the step "
            "(chunked head+CE over hidden states); it cannot compose "
            "with a custom loss_fn expecting logits"
        )
    return cfg


def default_optimizer(
    lr: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 100,
    total_steps: int = 10000,
) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )
