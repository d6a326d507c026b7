"""Ulysses-style sequence parallelism: all_to_all swaps the sharded dim from
sequence to heads for the attention window, so each `sp` rank computes full-
sequence attention for a head subset.

Reference parity: atorch ``auto/opt_lib/sequence_parallel_optimization.py``
(DeepSpeed-Ulysses pattern — SP groups orthogonal to DP, attention is
head-parallel, everything else sequence-split).  TPU-native: the two
``lax.all_to_all``s live in a ``shard_map`` region and ride ICI; the inner
attention reuses the fused Pallas kernel.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dlrover_tpu.common.log import logger
from dlrover_tpu.parallel.mesh import axis_size, current_mesh
from dlrover_tpu.ops.flash_attention import flash_attention_gqa, mha_reference


def _ulysses_shard(
    q, k, v, seg=None, *, axis_name: str, sp: int, use_flash: bool
):
    h_loc, h_kv_loc = q.shape[2], k.shape[2]
    if h_loc % sp != 0:
        raise ValueError(
            f"ulysses needs per-shard query heads ({h_loc}) divisible by the "
            f"{axis_name} axis size ({sp}); use ring attention instead"
        )
    if h_kv_loc % sp != 0:
        # GQA with fewer kv heads than sp ranks: replicate kv heads up to the
        # query-head count before the swap (the standard Ulysses-GQA fix).
        k = jnp.repeat(k, h_loc // h_kv_loc, axis=2)
        v = jnp.repeat(v, h_loc // h_kv_loc, axis=2)
    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=axis_name, tiled=True
    )
    # (b, s/P, h, d) -> (b, s, h/P, d): heads scatter, sequence gathers.
    qg = a2a(q, split_axis=2, concat_axis=1)
    kg = a2a(k, split_axis=2, concat_axis=1)
    vg = a2a(v, split_axis=2, concat_axis=1)
    attn = flash_attention_gqa if use_flash else mha_reference
    if seg is not None:
        # After the swap each rank holds the FULL sequence (for a head
        # subset), so it needs the full segment-id row: gather the
        # seq-sharded (b, s/P) chunks — integer metadata, tiny next to
        # the kv all_to_alls — and mask inside the inner kernel.
        seg_full = jax.lax.all_gather(seg, axis_name, axis=1, tiled=True)
        out = attn(qg, kg, vg, segment_ids=seg_full)
    else:
        out = attn(qg, kg, vg)
    return a2a(out, split_axis=1, concat_axis=2)


def ulysses_attention(
    q,
    k,
    v,
    segment_ids=None,
    axis_name: str = "sp",
    mesh=None,
    data_axes=("dp", "fsdp"),
    head_axis: str = "tp",
    use_flash: bool = True,
):
    """Head-parallel exact attention; global-view shapes as in ring_attention.

    Requires per-shard head count divisible by the `sp` size (after the GQA
    kv replication step).  ``segment_ids`` (b, s) packed rows shard over
    ``sp`` like the sequence; after the head/sequence swap each rank
    all_gathers the full segment row and masks inside the inner kernel —
    no silent cross-document attention.
    """
    mesh = mesh or current_mesh()
    sp = axis_size(mesh, axis_name)
    if sp <= 1:
        if mesh is None:
            logger.warning(
                "ulysses_attention: no ambient mesh (wrap the call in "
                "parallel.mesh.use_mesh) — falling back to unsharded "
                "reference attention"
            )
        return mha_reference(q, k, v, causal=True, segment_ids=segment_ids)
    spec = P(tuple(data_axes), axis_name, head_axis, None)
    shard_fn = functools.partial(
        _ulysses_shard, axis_name=axis_name, sp=sp, use_flash=use_flash
    )
    if segment_ids is not None:
        seg_spec = P(tuple(data_axes), axis_name)
        fn = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, spec, seg_spec),
            out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v, segment_ids)
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
