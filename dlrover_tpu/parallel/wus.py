"""Cross-replica weight-update sharding (ZeRO-on-TPU, arXiv 2004.13336).

Data-parallel training replicates the optimizer update: every replica
all-reduces the full gradient, then runs the identical Adam math on the
identical full state.  Weight-update sharding (WUS) splits that work
across the replica axes instead — each replica owns 1/N of the
gradient, updates 1/N of the optimizer state, and the updated params
are all-gathered back.  The per-chip prize is optimizer state ÷ N in
HBM plus update FLOPs ÷ N; the collective cost is unchanged in the
ideal lowering (reduce-scatter + all-gather moves the same bytes as
one all-reduce).

Implementation: a *sharding plan*, not a rewrite.  The step stays one
GSPMD program; WUS enters purely as partition specs — gradients are
constrained to a "scattered" layout that appends the free replica axes
(``dp``/``fsdp`` dims the leaf doesn't already use) to its first
evenly-divisible dim, optimizer state is born and kept in that layout,
and updated params are constrained back to their base layout (the
all-gather).  XLA derives the collectives.

Lowering honesty (this matters for reading the AOT census): jaxlib
0.4.36's TPU pipeline materializes "partial gradient → scattered
layout" as ``all-reduce + dynamic-slice`` rather than a literal
``reduce-scatter`` HLO op; the fused reduce-scatter only appears for
explicit ``lax.psum_scatter`` in manual (shard_map) regions — the
ring-attention program of ``scripts/aot_slice_compile.py`` does emit
it.  The HBM reduction and the ÷N update math are compiler-verified
either way (``memory_analysis``); ``telemetry/costmodel.py`` predicts
both lowerings' collective bytes and the census says which one XLA
picked, so a toolchain upgrade that starts fusing AR+DS is seen.

Two modes (``make_train_step(weight_update_sharding=...)``):

* ``"scatter"`` — params stored in their base layout; grads + optimizer
  state scattered; updated params re-gathered at the end of the step.
* ``"gather"`` — additionally stores *params* scattered between steps
  (ZeRO-3 flavored).  The step's first op is the param all-gather, so
  XLA's latency-hiding scheduler can overlap it with early compute —
  in the 1F1B pipeline schedule the gather of later stages' weights
  runs under the first microbatches' forward ticks
  (``parallel/pipeline.py``).
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dlrover_tpu.parallel.mesh import DATA_AXES

MODES = ("scatter", "gather")


def replica_axes(mesh: Mesh, axes: Optional[Tuple[str, ...]] = None
                 ) -> Tuple[str, ...]:
    """The mesh axes a weight update is replicated over: the data axes
    (``dp``/``fsdp``) that exist in the mesh with size > 1."""
    axes = axes or DATA_AXES
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return tuple(a for a in axes if sizes.get(a, 1) > 1)


def _spec_axes(spec: PartitionSpec) -> Tuple[str, ...]:
    """Flat tuple of every mesh axis a PartitionSpec uses."""
    used = []
    for entry in spec:
        if entry is None:
            continue
        for ax in ((entry,) if isinstance(entry, str) else tuple(entry)):
            used.append(ax)
    return tuple(used)


def scatter_spec(
    spec: PartitionSpec,
    shape: Tuple[int, ...],
    mesh: Mesh,
    axes: Tuple[str, ...],
) -> Optional[PartitionSpec]:
    """The scattered layout for one leaf: append the leaf's *free*
    replica axes to its first evenly-divisible dim.

    Free = replica axes the base spec doesn't already use (a leaf
    sharded over ``fsdp`` by the rule table only gains ``dp``).  The
    chosen dim must divide by (existing shard factor x free factor) so
    every device holds an equal contiguous block.  Returns ``None``
    when no dim fits (scalars, tiny leaves) — the leaf stays in its
    base layout, which is exactly correct: an undivisible leaf's update
    is cheaper than the collective that would shard it.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used = set(_spec_axes(spec))
    free = tuple(a for a in axes if a not in used and sizes.get(a, 1) > 1)
    if not free or not shape:
        return None
    factor = int(np.prod([sizes[a] for a in free]))
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for d, dim in enumerate(shape):
        entry = entries[d]
        existing = ((entry,) if isinstance(entry, str) else tuple(entry or ()))
        existing_factor = int(np.prod([sizes[a] for a in existing])) or 1
        if dim % (existing_factor * factor) != 0 or dim == 0:
            continue
        entries[d] = tuple(existing) + free
        return PartitionSpec(*entries)
    return None


def scatter_sharding(
    sharding: NamedSharding,
    shape: Tuple[int, ...],
    mesh: Mesh,
    axes: Tuple[str, ...],
) -> NamedSharding:
    """Scattered NamedSharding for one leaf (base sharding if no dim fits)."""
    spec = scatter_spec(sharding.spec, shape, mesh, axes)
    if spec is None:
        return sharding
    return NamedSharding(mesh, spec)


def scatter_tree(shardings, abstract, mesh: Mesh, axes: Tuple[str, ...]):
    """Map a shardings tree + matching abstract (shape) tree to the
    scattered layout, leaf by leaf.

    Unconstrained leaves (``None`` shardings — e.g. the int8 codec's
    codes/scales, which strip their flax boxes) are treated as
    replicated base layout: those are exactly the leaves WUS exists to
    scatter."""

    def one(sh, ab):
        shape = tuple(getattr(ab, "shape", None) or ())
        if not shape or not hasattr(ab, "shape"):
            return sh
        if sh is None:
            sh = NamedSharding(mesh, PartitionSpec())
        if not isinstance(sh, NamedSharding):
            return sh
        return scatter_sharding(sh, shape, mesh, axes)

    return jax.tree.map(
        one, shardings, abstract,
        is_leaf=lambda x: x is None or isinstance(x, NamedSharding),
    )


class WusPlan(NamedTuple):
    """Everything the train step needs to run a sharded weight update.

    Built once from the abstract state (shapes decide divisibility);
    deterministic, so ``create_sharded_state`` and ``make_train_step``
    independently derive identical layouts.
    """

    mode: str
    axes: Tuple[str, ...]          # replica axes actually scattered over
    n_replica: int                 # product of their sizes
    base_params: Any               # rule-table param shardings (gather target)
    stored_params: Any             # layout params live in between steps
    grad_shardings: Any            # scattered layout for gradients
    base_opt: Any                  # rule-table optimizer-state shardings
    opt_shardings: Any             # scattered layout for optimizer state

    def gather_params(self, params):
        """Constrain stored params to the base layout — in ``gather``
        mode this is the explicit all-gather, placed at the top of the
        step so the scheduler can overlap it with early forward compute
        (1F1B: later stages' gathers run under earlier microbatches)."""
        if self.mode != "gather":
            return params
        return jax.tree.map(
            lambda p, s: jax.lax.with_sharding_constraint(p, s)
            if isinstance(s, NamedSharding) else p,
            params, self.base_params,
        )

    def scatter_grads(self, grads):
        """Constrain gradients to the scattered layout: the
        reduce-scatter point (lowered by this XLA as
        all-reduce + dynamic-slice; see module docstring)."""
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s)
            if isinstance(s, NamedSharding) else g,
            grads, self.grad_shardings,
        )


def make_plan(
    mesh: Mesh,
    state_shardings,
    abstract_state,
    mode: str = "scatter",
    axes: Optional[Tuple[str, ...]] = None,
) -> Optional[WusPlan]:
    """Build the WUS plan from a state's shardings + abstract shapes.

    ``state_shardings``/``abstract_state`` are the trees returned /
    described by ``create_sharded_state`` (``.params`` in the *base*
    rule-table layout).  Returns ``None`` when the mesh has no replica
    axis with size > 1 — a pure tp mesh has nothing to scatter over and
    the step builder silently runs unsharded updates.
    """
    if mode not in MODES:
        raise ValueError(
            f"weight_update_sharding mode {mode!r} not in {MODES}"
        )
    axes = replica_axes(mesh, axes)
    if not axes:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_replica = int(np.prod([sizes[a] for a in axes]))
    base_params = state_shardings.params
    abs_params = abstract_state.params
    grad_shardings = scatter_tree(base_params, abs_params, mesh, axes)
    opt_shardings = scatter_tree(
        state_shardings.opt_state, abstract_state.opt_state, mesh, axes
    )
    # Normalized base (None -> replicated) so tree zips stay aligned.
    base_opt = scatter_tree(
        state_shardings.opt_state, abstract_state.opt_state, mesh, ()
    )
    stored_params = grad_shardings if mode == "gather" else base_params
    return WusPlan(
        mode=mode,
        axes=axes,
        n_replica=n_replica,
        base_params=base_params,
        stored_params=stored_params,
        grad_shardings=grad_shardings,
        base_opt=base_opt,
        opt_shardings=opt_shardings,
    )


def apply_plan_to_shardings(state_shardings, plan: Optional[WusPlan]):
    """The storage layout for a whole TrainState under a plan: optimizer
    state always scattered, params scattered in ``gather`` mode."""
    if plan is None:
        return state_shardings
    return state_shardings.replace(
        params=plan.stored_params, opt_state=plan.opt_shardings
    )


def _shard_factor(sh, sizes) -> int:
    if not isinstance(sh, NamedSharding):
        return 1
    return int(np.prod([sizes[a] for a in _spec_axes(sh.spec)])) or 1


def scattered_bytes(abstract_state, plan: Optional[WusPlan]) -> int:
    """Per-chip optimizer-state bytes the plan removes: for each leaf,
    (bytes / base shard factor) - (bytes / scattered shard factor).
    The cost model uses this as the predicted per-chip HBM delta; the
    AOT compile verifies it against ``memory_analysis``."""
    if plan is None:
        return 0
    mesh = None
    for sh in jax.tree.leaves(plan.opt_shardings):
        if isinstance(sh, NamedSharding):
            mesh = sh.mesh
            break
    if mesh is None:
        return 0
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    saved = 0
    for ab, base_sh, wus_sh in zip(
        jax.tree.leaves(abstract_state.opt_state),
        jax.tree.leaves(plan.base_opt),
        jax.tree.leaves(plan.opt_shardings),
    ):
        if not hasattr(ab, "shape"):
            continue
        nbytes = int(np.prod(ab.shape or (1,))) * ab.dtype.itemsize
        saved += (nbytes // _shard_factor(base_sh, sizes)
                  - nbytes // _shard_factor(wus_sh, sizes))
    return max(0, saved)
