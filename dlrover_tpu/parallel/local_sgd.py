"""Local SGD / HSDP: inner steps per slice, periodic outer sync over DCN.

Reference parity: ``atorch/local_sgd/HSDP/__init__.py:17``
(``patch_local_sgd_to_fsdp``: FSDP shard groups run N local steps, then
outer optimizers synchronize replicas) and ``local_sgd/reduce_methods/``
(linear mean, generalized task arithmetic).  TPU redesign — this is the
natural multi-slice training shape:

- the mesh carries a ``dcn`` axis (one entry per pod slice);
- every model/optimizer leaf gains a leading slice axis sharded on
  ``dcn``; the inner train step is ``jax.vmap`` over that axis, so XLA
  compiles per-slice programs with NO cross-slice collectives — inner
  traffic stays on ICI by construction;
- every ``sync_every`` steps a separate jitted outer step reduces the
  per-slice deltas over ``dcn`` (linear mean or sign-election task
  arithmetic), feeds them to a DiLoCo-style outer optimizer (SGD with
  Nesterov momentum on the anchor), and re-broadcasts the anchor.

The whole LocalSGDState is one pytree, so Flash Checkpoint persists and
restores it like any train state (resumability tested).
"""

from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dlrover_tpu.common.log import logger


def build_slice_mesh(
    n_slices: int,
    devices: Optional[Sequence] = None,
    inner_axis: str = "fsdp",
) -> Mesh:
    """(dcn, inner) mesh: the slice axis rides DCN, everything else ICI.

    On real multi-slice TPU hardware the device array comes from
    ``mesh_utils.create_hybrid_device_mesh`` so each mesh row IS a physical
    slice (plain reshape would not guarantee that and intra-row traffic
    could silently ride DCN); the reshape path is the CPU-test fallback,
    mirroring ``parallel/mesh.py``'s hybrid-mesh construction."""
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) % n_slices != 0:
        raise ValueError(f"{len(devices)} devices not divisible by "
                         f"{n_slices} slices")
    per_slice = len(devices) // n_slices
    try:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_hybrid_device_mesh(
            (1, per_slice), (n_slices, 1), devices=devices
        )
    except Exception:  # CPU/virtual devices carry no slice topology
        arr = np.array(devices).reshape(n_slices, per_slice)
    return Mesh(arr, ("dcn", inner_axis))


class LocalSGDConfig(NamedTuple):
    sync_every: int = 16
    outer_lr: float = 0.7
    outer_momentum: float = 0.9
    nesterov: bool = True
    # "linear" = mean of slice deltas; "task_arithmetic" = sign election:
    # keep only coordinates agreeing with the majority sign, mean those.
    reduce_method: str = "linear"
    # "int8": blockwise-quantize each slice's delta BEFORE it crosses the
    # dcn axis — the outer sync's cross-slice traffic becomes int8 codes +
    # one f32 absmax per block (~4x fewer DCN bytes), exactly where bytes
    # are most expensive.  Reference capability:
    # ``atorch/ops/csrc/quantization/quant_reduce.cu:1-248`` (quantized
    # allreduce helpers); here the codec is the shared blockwise int8 from
    # ``optimizers/quantized.py`` and GSPMD moves the codes.
    sync_quantization: str = "none"  # none | int8
    quant_block_size: int = 256


class LocalSGDState(NamedTuple):
    slice_state: Any  # TrainState with a leading (n_slices,) axis
    anchor_params: Any  # the synchronized global model
    outer_momentum: Any  # outer optimizer state (same tree as params)
    step: jnp.ndarray  # global step counter


def _int8_mean_over_dcn(
    deltas, mesh: Mesh, block_size: int, dcn_axis: str = "dcn",
    param_specs: Optional[Any] = None,
):
    """Cross-slice mean where every byte that rides DCN is int8.

    The reference's quantized allreduce pipeline
    (``atorch/ops/csrc/quantization/quant_reduce.cu``: quantize →
    reduce-scatter → dequant/reduce/requant → all-gather), expressed as a
    ``shard_map`` over the ``dcn`` axis:

    1. each slice splits its local delta into S chunks and quantizes them
       (int8 codes + f32 absmax per ``block_size`` block);
    2. ``all_to_all`` routes chunk j's codes to slice j — the
       reduce-scatter leg, (S-1)/S · N int8 wire per slice;
    3. the owner dequantizes S versions, means them, REquantizes;
    4. ``all_gather`` of the reduced codes — the broadcast leg, another
       (S-1)/S · N int8.

    Total DCN wire ≈ 2(S-1)/S·N bytes of int8 + absmax, vs the f32
    all-reduce's 2(S-1)/S·4N — the ~4x the quantization promises at ANY
    slice count (a plain "quantize then all-gather everything" only wins
    4/S·... at small S).  Leaves smaller than S·block stay f32.  Returns
    the REDUCED (mean) tree, replicated across slices (and keeping each
    leaf's intra-slice ``param_specs`` sharding: HSDP shards are codec'd
    locally — the sync never materializes a full-model f32 copy).
    """

    from dlrover_tpu.optimizers.quantized import (
        dequantize_blockwise,
        quantize_blockwise,
    )

    S = mesh.shape[dcn_axis]

    def per_leaf(d, spec):
        rest = d.shape[1:]
        spec = tuple(spec) if spec is not None else ()
        spec = spec + (None,) * (len(rest) - len(spec))
        # local (per-device) element count: the codec runs on the shard
        shard_factor = int(np.prod([
            mesh.shape[a] for s in spec if s is not None
            for a in ((s,) if isinstance(s, str) else s)
        ])) or 1
        n = int(np.prod(rest)) // shard_factor
        if n < S * block_size:
            return jnp.mean(d, axis=0)

        chunk = -(-n // (S * block_size)) * block_size
        n_pad = chunk * S

        def local(dl):
            # dl: this slice's LOCAL delta shard, view (1, *rest_local)
            rest_local = dl.shape[1:]
            flat = jnp.pad(dl.reshape(-1), (0, n_pad - n))
            rows = flat.reshape(S, chunk)
            q, am = jax.vmap(
                lambda x: quantize_blockwise(x, block_size, "linear")
            )(rows)
            # reduce-scatter leg: chunk j's codes travel to slice j
            q = jax.lax.all_to_all(
                q, dcn_axis, split_axis=0, concat_axis=0, tiled=True
            )
            am = jax.lax.all_to_all(
                am, dcn_axis, split_axis=0, concat_axis=0, tiled=True
            )
            # owner-side dequant -> mean -> requant
            vals = jax.vmap(
                lambda c, a: dequantize_blockwise(
                    c, a, (chunk,), block_size, "linear"
                )
            )(q, am)
            red = jnp.mean(vals, axis=0)
            q2, am2 = quantize_blockwise(red, block_size, "linear")
            # broadcast leg: reduced codes come back int8 too
            q_full = jax.lax.all_gather(q2, dcn_axis, tiled=True)
            am_full = jax.lax.all_gather(am2, dcn_axis, tiled=True)
            out = dequantize_blockwise(
                q_full, am_full, (n_pad,), block_size, "linear"
            )
            return out[:n].reshape((1,) + rest_local)

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=PartitionSpec(dcn_axis, *spec),
            out_specs=PartitionSpec(None, *spec),
            check_vma=False,
        )(d)[0]

    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    if param_specs is None:
        specs = [None] * len(leaves)
    else:
        specs = jax.tree.leaves(
            param_specs,
            is_leaf=lambda x: x is None or isinstance(x, PartitionSpec),
        )
    return jax.tree_util.tree_unflatten(
        treedef, [per_leaf(d, s) for d, s in zip(leaves, specs)]
    )


def _reduce_deltas(deltas, method: str):
    """Combine per-slice deltas (leading slice axis) into one update."""
    if method == "linear":
        return jax.tree.map(lambda d: jnp.mean(d, axis=0), deltas)
    if method == "task_arithmetic":
        def ta(d):
            sign = jnp.sign(jnp.sum(jnp.sign(d), axis=0))  # elected sign
            agree = (jnp.sign(d) == sign[None]) & (sign[None] != 0)
            total = jnp.sum(jnp.where(agree, d, 0.0), axis=0)
            count = jnp.maximum(jnp.sum(agree, axis=0), 1)
            return total / count
        return jax.tree.map(ta, deltas)
    raise ValueError(f"unknown reduce method {method}")


def build_local_sgd(
    base_state,
    n_slices: int,
    mesh: Mesh,
    config: LocalSGDConfig = LocalSGDConfig(),
    dcn_axis: str = "dcn",
    param_specs: Optional[Any] = None,
):
    """Lift a single-slice TrainState into Local-SGD training.

    Returns ``(state, inner_step, maybe_sync)``:

    - ``inner_step(state, batch) -> (state, metrics)``: vmapped per-slice
      update; ``batch`` leaves carry a leading ``(n_slices, ...)`` axis.
    - ``maybe_sync(state) -> state``: runs the outer sync iff
      ``state.step % sync_every == 0`` (jit-friendly ``lax.cond``).

    ``param_specs``: optional pytree of ``PartitionSpec`` matching
    ``base_state.params`` — the HSDP intra-slice (fsdp) sharding; each
    param leaf is placed at ``P(dcn, *spec)`` and the anchor/momentum at
    ``P(*spec)``, so within-slice ZeRO-3 collectives stay on ICI.  Default
    (None) replicates within the slice — pure multi-replica Local SGD.
    """
    if dcn_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no '{dcn_axis}' axis: {mesh.axis_names}")
    if mesh.shape[dcn_axis] != n_slices:
        raise ValueError(
            f"mesh {dcn_axis}={mesh.shape[dcn_axis]} != n_slices={n_slices}"
        )

    sliced = NamedSharding(mesh, PartitionSpec(dcn_axis))
    replicated = NamedSharding(mesh, PartitionSpec())

    def _param_sharding(with_dcn: bool):
        if param_specs is None:
            return None
        prefix = (dcn_axis,) if with_dcn else ()
        return jax.tree.map(
            lambda spec: NamedSharding(
                mesh, PartitionSpec(*prefix, *(spec or ()))
            ),
            param_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec) or x is None,
        )

    def broadcast(tree, shardings=None):
        def lift(x, sh=None):
            x = jnp.asarray(x)  # TrainState.step arrives as a python int
            return jax.device_put(
                jnp.broadcast_to(x[None], (n_slices,) + x.shape),
                sh or sliced,
            )

        if shardings is None:
            return jax.tree.map(lift, tree)
        return jax.tree.map(lift, tree, shardings)

    slice_state = broadcast(base_state)
    if param_specs is not None:
        slice_state = slice_state.replace(
            params=broadcast(base_state.params, _param_sharding(True))
        )
    anchor_sharding = _param_sharding(False)
    anchor = (
        jax.device_put(base_state.params, replicated)
        if anchor_sharding is None
        else jax.tree.map(
            jax.device_put, base_state.params, anchor_sharding
        )
    )
    momentum = jax.tree.map(jnp.zeros_like, anchor)
    state = LocalSGDState(
        slice_state=slice_state,
        anchor_params=anchor,
        outer_momentum=momentum,
        step=jnp.zeros([], jnp.int32),
    )

    # -- inner step: vmap over the slice axis ⇒ no cross-dcn collectives --
    def make_inner_step(per_slice_step: Callable):
        vstep = jax.vmap(per_slice_step)

        @jax.jit
        def inner(state: LocalSGDState, batch):
            new_slice_state, metrics = vstep(state.slice_state, batch)
            # Metrics keep their leading slice axis: averaging here would
            # put a cross-dcn all-reduce in the hot step; callers mean on
            # host at their logging cadence instead.
            return (
                state._replace(
                    slice_state=new_slice_state, step=state.step + 1
                ),
                metrics,
            )

        return inner

    # -- outer sync -------------------------------------------------------
    def _sync(state: LocalSGDState) -> LocalSGDState:
        # delta = anchor - slice_params: "how far each slice moved", so the
        # outer step  anchor -= lr * (-movement)  walks TOWARD the slices.
        deltas = jax.tree.map(
            lambda anchor_leaf, slice_leaf: anchor_leaf[None] - slice_leaf,
            state.anchor_params,
            state.slice_state.params,
        )
        if config.sync_quantization == "int8":
            if config.reduce_method != "linear":
                raise ValueError(
                    "int8 sync quantization implements the linear mean "
                    "(the quantized-allreduce pipeline); task_arithmetic "
                    "needs every slice's full delta"
                )
            reduced = _int8_mean_over_dcn(
                deltas, mesh, config.quant_block_size, dcn_axis,
                param_specs=param_specs,
            )
        elif config.sync_quantization != "none":
            raise ValueError(
                f"unknown sync_quantization {config.sync_quantization!r}"
            )
        else:
            reduced = _reduce_deltas(deltas, config.reduce_method)
        mu, lr = config.outer_momentum, config.outer_lr
        new_momentum = jax.tree.map(
            lambda m, d: mu * m + d, state.outer_momentum, reduced
        )
        if config.nesterov:
            dirs = jax.tree.map(
                lambda m_new, d: d + mu * m_new, new_momentum, reduced
            )
        else:
            dirs = new_momentum
        new_anchor = jax.tree.map(
            lambda a, s: a - lr * s, state.anchor_params, dirs
        )
        new_slice_params = jax.tree.map(
            lambda a, s: jnp.broadcast_to(a[None], s.shape),
            new_anchor,
            state.slice_state.params,
        )
        return state._replace(
            slice_state=state.slice_state.replace(params=new_slice_params),
            anchor_params=new_anchor,
            outer_momentum=new_momentum,
        )

    @jax.jit
    def maybe_sync(state: LocalSGDState) -> LocalSGDState:
        return jax.lax.cond(
            state.step % config.sync_every == 0,
            _sync,
            lambda s: s,
            state,
        )

    logger.info(
        "Local SGD: %d slices, sync every %d steps, reduce=%s",
        n_slices, config.sync_every, config.reduce_method,
    )
    return state, make_inner_step, maybe_sync
