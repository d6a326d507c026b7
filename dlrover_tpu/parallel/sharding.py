"""Logical-axis sharding rules: the TPU-native "parallelism strategy" layer.

Reference parity: atorch's optimization library turns FSDP/TP/SP choices into
module rewrites (``auto/opt_lib/``).  Here a *strategy is just a rule table*
mapping logical tensor axes to mesh axes; GSPMD derives every collective.
Switching dp→fsdp→tp+sp touches no model code — only these rules.

Logical axes used by the model zoo:

    batch   — per-example dim
    seq     — sequence/context dim (activations)
    embed   — residual stream
    heads   — attention heads
    kv_heads— KV heads (GQA)
    head_dim— per-head feature dim
    mlp     — FFN hidden dim
    vocab   — vocabulary dim
    expert  — MoE expert dim
    layers  — stacked (scanned) layer dim
    ssm_inner — state-space mixer's inner width (heads x head dim)
    ssm_heads — state-space heads (dt, A, D)
    ssm_state — state width (B, C: one group, shared by all heads)
    conv_width— taps of the depthwise causal convolution
    conv_inner— gated short convolution's width (B, C, x and the taps)
    router    — the router's outputs, one an expert of the whole model
                (never sharded: every chip routes over all of them)
"""

import contextlib
import contextvars
import math
from typing import Dict, List, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dlrover_tpu.parallel.mesh import current_mesh

Rules = Tuple[Tuple[str, Union[str, Tuple[str, ...], None]], ...]

# -- canonical rule tables -------------------------------------------------
#
# Parameter axes (embed/heads/mlp/vocab/...) and activation axes
# (batch/seq/act_*) are deliberately distinct logical names: an activation
# constraint like (batch, seq, act_embed) must never reuse a mesh axis the
# batch dim already consumed (the maxtext/t5x convention).

_ACT_REPLICATED = (
    ("act_embed", None),
    ("act_head_dim", None),
)

# Axes shared by every table: pipeline stages always map to pp (size-1 mesh
# axis = no-op), MoE capacity/expert activations follow the expert rule.
_COMMON = (
    ("stage", "pp"),
    ("act_expert", "ep"),
    ("act_capacity", None),
    # GPT-NeoX fused q/k/v projection's 3-way split dim (never sharded).
    ("qkv", None),
    # BERT position/type embedding tables' leading dims (never sharded)
    # and the MLM transform's square-dense output dim.
    ("pos", None),
    ("type", None),
    ("embed_out", None),
    # CLIP vision tower: flattened-patch input dim of the patch embedding.
    ("patch_dim", None),
    # Taps of the state-space mixer's causal convolution (never sharded).
    ("conv_width", None),
    # The routed-expert layer's router and selection bias (never sharded).
    ("router", None),
)

# Pure data parallel: params replicated, batch split on dp(+fsdp).
DP_RULES: Rules = (
    ("batch", ("dp", "fsdp")),
    ("seq", None),
    ("act_heads", None),
    ("act_kv_heads", None),
    ("act_mlp", None),
    ("act_vocab", None),
    ("embed", None),
    ("heads", None),
    ("kv_heads", None),
    ("head_dim", None),
    ("mlp", None),
    ("vocab", None),
    ("expert", None),
    ("layers", None),
    ("act_ssm_inner", None),
    ("ssm_inner", None),
    ("ssm_heads", None),
    ("ssm_state", None),
    ("act_conv_inner", None),
    ("conv_inner", None),
) + _ACT_REPLICATED + _COMMON

# FSDP/ZeRO-3 analog: shard every weight's embed dim over fsdp; params are
# all-gathered just-in-time per layer by GSPMD (+ the zero-1/2/3 distinction
# collapses to which state the rule table shards — see auto/opt_lib).
FSDP_RULES: Rules = (
    ("batch", ("dp", "fsdp")),
    ("seq", None),
    ("act_heads", None),
    ("act_kv_heads", None),
    ("act_mlp", None),
    ("act_vocab", None),
    ("embed", "fsdp"),
    ("heads", None),
    ("kv_heads", None),
    ("head_dim", None),
    ("mlp", None),
    ("vocab", None),
    ("expert", None),
    ("layers", None),
    ("act_ssm_inner", None),
    ("ssm_inner", None),
    ("ssm_heads", None),
    ("ssm_state", None),
    ("act_conv_inner", None),
    ("conv_inner", None),
) + _ACT_REPLICATED + _COMMON

# Megatron-style TP composed with FSDP (+ optional sequence parallel):
# contraction dims on fsdp, output-feature dims on tp; activations shard
# heads/mlp over tp and seq over sp.  Column/row parallel + its collectives
# fall out of GSPMD propagation.
FSDP_TP_RULES: Rules = (
    ("batch", ("dp", "fsdp")),
    ("seq", "sp"),
    ("act_heads", "tp"),
    ("act_kv_heads", "tp"),
    ("act_mlp", "tp"),
    ("act_vocab", "tp"),
    ("embed", "fsdp"),
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("head_dim", None),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("expert", "ep"),
    ("layers", None),
    # State-space mixer: heads (and the inner width, heads-major) over tp
    # like attention's; B and C are one group all heads read, kept whole.
    ("act_ssm_inner", "tp"),
    ("ssm_inner", "tp"),
    ("ssm_heads", "tp"),
    ("ssm_state", None),
    # Gated short convolution: depthwise, so its width shards like an
    # MLP's (column-parallel in, row-parallel out).
    ("act_conv_inner", "tp"),
    ("conv_inner", "tp"),
) + _ACT_REPLICATED + _COMMON

PRESET_RULES: Dict[str, Rules] = {
    "dp": DP_RULES,
    "fsdp": FSDP_RULES,
    "fsdp_tp": FSDP_TP_RULES,
    "3d": FSDP_TP_RULES,
}


def rules_to_dict(rules: Rules) -> Dict[str, Union[str, Tuple[str, ...], None]]:
    return dict(rules)


def logical_to_spec(
    logical_axes: Sequence[Optional[str]], rules: Rules
) -> PartitionSpec:
    """Map a tensor's logical axis names to a PartitionSpec."""
    table = rules_to_dict(rules)
    spec = []
    used: set = set()
    for ax in logical_axes:
        mesh_ax = table.get(ax) if ax is not None else None
        # A mesh axis may shard at most one tensor dim.
        if mesh_ax is not None:
            axes = (mesh_ax,) if isinstance(mesh_ax, str) else tuple(mesh_ax)
            axes = tuple(a for a in axes if a not in used)
            used.update(axes)
            mesh_ax = axes if len(axes) != 1 else axes[0]
            if axes == ():
                mesh_ax = None
        spec.append(mesh_ax)
    return PartitionSpec(*spec)


def tree_to_shardings(logical_tree, rules: Rules, mesh: Mesh):
    """Convert a pytree of logical-axis tuples into NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(a, (str, type(None))) for a in x),
    )


# Constraints `constrain` put into the program while a trace ran under
# `count_constraints`; the train step's `compile` span reports the count.
_CONSTRAINT_COUNT: contextvars.ContextVar[Optional[List[int]]] = (
    contextvars.ContextVar("dlrover_tpu_constraint_count", default=None)
)


@contextlib.contextmanager
def count_constraints():
    """Yields a one-element list that holds how many constraints
    ``constrain`` applied inside the block."""
    count = [0]
    token = _CONSTRAINT_COUNT.set(count)
    try:
        yield count
    finally:
        _CONSTRAINT_COUNT.reset(token)


def constrain(x, logical_axes: Sequence[Optional[str]]):
    """Hold an activation to the sharding its logical axes name: the one
    call every model makes on q/k/v, the MLP's hidden, the residual
    stream and the logits, so that GSPMD moves the weights to the
    activations and not the activations to the weights.

    The rule table is the one in scope (``nn_partitioning.axis_rules``)
    and the mesh is ``current_mesh()``; the steps set both while they
    trace.  With no rules, no mesh or a mesh of one device there is
    nothing to hold and ``x`` itself is returned: nothing is added to
    the program.  A dimension its mesh axes do not divide raises; it is
    not skipped.
    """
    rules = nn.get_logical_axis_rules()
    mesh = current_mesh()
    if not rules or mesh is None or mesh.size == 1:
        return x
    spec = logical_to_spec(logical_axes, rules)
    if len(spec) != x.ndim:
        raise ValueError(
            f"constrain: logical axes {tuple(logical_axes)} name "
            f"{len(spec)} dimensions, the array has shape {x.shape}"
        )
    for dim, entry in zip(x.shape, spec):
        axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
        ways = math.prod(mesh.shape[a] for a in axes)
        if dim % ways:
            raise ValueError(
                f"constrain: logical axes {tuple(logical_axes)} of shape "
                f"{x.shape} put mesh axes {axes} ({ways} ways) on a "
                f"dimension of {dim}, which they do not divide "
                f"(mesh {dict(mesh.shape)})"
            )
    count = _CONSTRAINT_COUNT.get()
    if count is not None:
        count[0] += 1
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_sharding(mesh: Mesh, rules: Rules) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(("batch", "seq"), rules))


def replica_axes_from_rules(rules: Rules) -> Tuple[str, ...]:
    """The mesh axes a rule table replicates weight updates over — the
    axes its ``batch`` rule consumes.  Every gradient is psum'd over
    exactly these, so they are what weight-update sharding
    (``parallel/wus.py``) scatters the optimizer across; deriving them
    from the table (rather than assuming the mesh's DATA_AXES) keeps a
    custom rule table that batches over different axes consistent."""
    entry = rules_to_dict(rules).get("batch")
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)
