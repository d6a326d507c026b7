"""Fused causal (GQA) attention: Pallas TPU kernel + memory-efficient VJP.

Reference parity: the reference binds flash-attention CUDA kernels
(``tfplus/flash_attn/ops/flash_attention_ops.cc``, atorch
``modules/transformer/layers.py`` flash-attn module swaps).  On TPU the same
op is a Pallas kernel: blockwise online-softmax forward that keeps the
(seq × seq) score matrix out of HBM, and two Pallas backward kernels
(recompute-from-LSE — FlashAttention-2's dq and dk/dv formulations) so the
VJP is O(seq · block) memory too.  Matmuls run in the input dtype (bf16 on
the MXU) with f32 accumulation; softmax math is f32.

Layout convention matches the model zoo: q (b, s, h, d), k/v (b, s, h_kv, d)
with h a multiple of h_kv (GQA).  All softmax math in float32.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common.platform import pallas_interpret

_NEG_INF = -1e30  # finite "masked" value: keeps exp() well-defined
_LSE_LANES = 8  # trailing lane dim on the lse output (TPU tiling rule)
_SEG_LANES = 8  # lane/sublane padding on segment-id kernel inputs


def _pick_chunk(s: int, cap: int) -> int:
    """Largest divisor of ``s`` not exceeding ``cap`` (>= 1)."""
    if s <= cap:
        return s
    for c in range(cap, 0, -1):
        if s % c == 0:
            return c
    return s


def _segmented_reference(q, k, v, causal, segment_ids, q_chunk, window=None):
    """Packed-row reference attention, chunked over q.

    The (b, s, s) boolean segment mask is never materialized in HBM (64M
    entries per head-broadcast at s=8192): the causal ∧ same-segment
    predicate is computed per q-chunk — peak mask footprint b·chunk·s —
    and the chunk body is rematerialized so the VJP recomputes scores
    instead of saving every chunk's probabilities.
    """
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    c = _pick_chunk(s_q, q_chunk)
    n = s_q // c
    scale = 1.0 / math.sqrt(d)
    kpos = jnp.arange(s_kv)

    def chunk(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * c, c, axis=1)
        seg_q = jax.lax.dynamic_slice_in_dim(segment_ids, i * c, c, axis=1)
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", qc, k).astype(jnp.float32) * scale
        )
        pred = seg_q[:, None, :, None] == segment_ids[:, None, None, :]
        if causal:
            qpos = i * c + jnp.arange(c)
            seen = qpos[:, None] >= kpos[None, :]
            if window is not None:
                seen = seen & (qpos[:, None] - kpos[None, :] < window)
            pred = jnp.logical_and(pred, seen[None, None])
        scores = jnp.where(pred, scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    if n == 1:
        return chunk(jnp.int32(0))
    out = jax.lax.map(jax.checkpoint(chunk), jnp.arange(n))  # (n, b, c, h, d)
    return jnp.moveaxis(out, 0, 1).reshape(b, s_q, h, d)


def mha_reference(
    q, k, v, causal: bool = True, segment_ids=None, q_chunk: int = 512,
    window: Optional[int] = None,
):
    """Plain-XLA reference (and fallback) attention; exact.

    Dense path is O(s²) memory; with ``segment_ids`` the predicate is
    fused per q-chunk (:func:`_segmented_reference`) so packed rows never
    materialize the (b, s, s) segment mask.  ``window`` (causal only): key
    ``j`` is seen by query ``t`` iff ``0 <= t - j < window``.
    """
    check_window(window, causal)
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    if segment_ids is not None:
        return _segmented_reference(
            q, k, v, causal, segment_ids, q_chunk, window)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    mask = jnp.ones((s, k.shape[1]), dtype=bool)
    if causal:
        mask = jnp.tril(mask)
    if window is not None:
        mask = mask & ~jnp.tril(mask, -window)
    mask = mask[None, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def check_window(window, causal):
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window}: a sliding window is a causal mask over the "
            f"last `window` >= 1 positions (causal={causal})")


def block_live(iq, ik, block_q, block_kv, causal, window):
    """Whether any (query, key) pair of q block ``iq`` and kv block ``ik``
    is seen: not wholly above the diagonal, not wholly behind the window
    (program ids in a kernel, plain ints in ``splash_attention.mask_plan``)."""
    if not causal:
        return True
    live = ik * block_kv <= iq * block_q + block_q - 1
    if window is not None:
        live = live & (ik * block_kv + block_kv - 1 > iq * block_q - window)
    return live


def _position_mask(iq, ik, block_q, block_kv, causal, window):
    """The causal (and windowed) predicate of one block, or None."""
    if not causal:
        return None
    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0
    )
    kpos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1
    )
    mask = qpos >= kpos
    if window is not None:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    return mask


def _seg_lane_blocks(segment_ids):
    """(b, s) segment ids → lane-padded kernel inputs: q-side (b, s, 8)
    and kv-side (b, 8, s) so each Pallas block keeps a TPU-tileable
    trailing layout (same trick as the lse lanes)."""
    seg = segment_ids.astype(jnp.int32)
    b, s = seg.shape
    seg_q = jnp.broadcast_to(seg[:, :, None], (b, s, _SEG_LANES))
    seg_kv = jnp.broadcast_to(seg[:, None, :], (b, _SEG_LANES, s))
    return seg_q, seg_kv


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(
    *refs, sm_scale: float, causal: bool, segmented: bool, block_q: int,
    block_kv: int, num_kv_blocks: int, window: Optional[int] = None,
):
    """Grid = (batch, q_heads, q_blocks, kv_blocks); kv dim is sequential
    ("arbitrary") so the (m, l, acc) scratch carries across kv steps.

    With ``segmented`` the input list grows two lane-padded segment-id
    blocks and the causal mask is AND-ed with the same-segment predicate
    *inside the block* — packed rows never see a materialized mask."""
    if segmented:
        (q_ref, k_ref, v_ref, seg_q_ref, seg_kv_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        seg_q_ref = seg_kv_ref = None
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: blocks strictly above the diagonal are fully masked — skip
    # their FLOPs entirely (the ~2x saving flash attention exists for);
    # with a window, so are the blocks wholly behind it.
    live = block_live(iq, ik, block_q, block_kv, causal, window)

    @pl.when(live)
    def _compute():
        # Matmuls stay in the input dtype (bf16 on TPU: full MXU rate, 8x
        # the f32 rate on v5e) with f32 ACCUMULATION via
        # preferred_element_type; only the softmax math runs f32.
        q = q_ref[0, 0]  # (block_q, d)
        k = k_ref[0, 0]  # (block_kv, d)
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (block_q, block_kv) f32

        mask = _position_mask(iq, ik, block_q, block_kv, causal, window)
        if segmented:
            seg_mask = seg_q_ref[0][:, :1] == seg_kv_ref[0][:1, :]
            mask = seg_mask if mask is None else jnp.logical_and(mask, seg_mask)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...][:, :1]  # (block_q, 1)
        l_prev = l_scr[...][:, :1]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            # p cast to the value dtype for the MXU; accumulator stays f32.
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_scr[...][:, :1]
        m = m_scr[...][:, :1]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        # lse carries a trailing lane dim (size _LSE_LANES) purely to satisfy
        # the TPU (8,128)-tiling rule on the output block; value is broadcast.
        lse_ref[0, 0] = jnp.broadcast_to(
            m + jnp.log(safe_l), lse_ref[0, 0].shape
        )


def _flash_fwd(
    q_t, k_t, v_t, segment_ids, *, causal, block_q, block_kv, interpret,
    window=None,
):
    """q_t (b, h, s, d); k_t/v_t (b, h_kv, s_kv, d) → (out, lse) in t-layout.
    ``segment_ids`` (b, s) or None selects the segmented kernel variant."""
    b, h, s_q, d = q_t.shape
    h_kv, s_kv = k_t.shape[1], k_t.shape[2]
    group = h // h_kv
    num_kv_blocks = s_kv // block_kv
    sm_scale = 1.0 / math.sqrt(d)
    segmented = segment_ids is not None

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        segmented=segmented,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=num_kv_blocks,
        window=window,
    )
    grid = (b, h, s_q // block_q, num_kv_blocks)
    in_specs = [
        pl.BlockSpec(
            (1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
        ),
        pl.BlockSpec(
            (1, 1, block_kv, d),
            lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0),
        ),
        pl.BlockSpec(
            (1, 1, block_kv, d),
            lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0),
        ),
    ]
    inputs = [q_t, k_t, v_t]
    if segmented:
        seg_q, seg_kv = _seg_lane_blocks(segment_ids)
        in_specs += [
            pl.BlockSpec(
                (1, block_q, _SEG_LANES), lambda ib, ih, iq, ik: (ib, iq, 0)
            ),
            pl.BlockSpec(
                (1, _SEG_LANES, block_kv), lambda ib, ih, iq, ik: (ib, 0, ik)
            ),
        ]
        inputs += [seg_q, seg_kv]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, _LSE_LANES),
                lambda ib, ih, iq, ik: (ib, ih, iq, 0),
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_q, d), q_t.dtype),
            jax.ShapeDtypeStruct((b, h, s_q, _LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*inputs)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 dq / dk+dv formulation)
# ---------------------------------------------------------------------------


def _bwd_dkdv_kernel(
    *refs, sm_scale, causal, segmented, block_q, block_kv, num_q_blocks,
    window=None,
):
    """Grid (b, h, kv_blocks, q_blocks); q dim sequential so (dk, dv)
    accumulate in scratch for one kv block."""
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         seg_q_ref, seg_kv_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        seg_q_ref = seg_kv_ref = None
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # Causal: q blocks strictly below the diagonal contribute nothing.
    live = block_live(i, j, block_q, block_kv, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]  # (bq, d)
        k = k_ref[0, 0]  # (bkv, d)
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]  # (bq, 1) f32
        delta = delta_ref[0, 0][:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (bq, bkv)
        p = jnp.exp(s - lse)
        mask = _position_mask(i, j, block_q, block_kv, causal, window)
        if segmented:
            seg_mask = seg_q_ref[0][:, :1] == seg_kv_ref[0][:1, :]
            mask = seg_mask if mask is None else jnp.logical_and(mask, seg_mask)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        pb = p.astype(do.dtype)
        # dv += p^T @ do
        dv_scr[...] += jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dp = do @ v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        # dk += ds^T @ q
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == num_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    *refs, sm_scale, causal, segmented, block_q, block_kv, num_kv_blocks,
    window=None,
):
    """Grid (b, h, q_blocks, kv_blocks); kv dim sequential, dq in scratch."""
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         seg_q_ref, seg_kv_ref, dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        seg_q_ref = seg_kv_ref = None
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = block_live(i, j, block_q, block_kv, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        p = jnp.exp(s - lse)
        mask = _position_mask(i, j, block_q, block_kv, causal, window)
        if segmented:
            seg_mask = seg_q_ref[0][:, :1] == seg_kv_ref[0][:1, :]
            mask = seg_mask if mask is None else jnp.logical_and(mask, seg_mask)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == num_kv_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_pallas(
    q_t, k_t, v_t, out_t, lse, do_t, segment_ids,
    *, causal, block_q, block_kv, interpret, window=None,
):
    """FA-2 backward as two Pallas kernels; all tensors in t-layout
    (b, h, s, d) with k/v carrying h_kv heads (GQA folded outside)."""
    b, h, s_q, d = q_t.shape
    h_kv, s_kv = k_t.shape[1], k_t.shape[2]
    group = h // h_kv
    nq, nk = s_q // block_q, s_kv // block_kv
    sm_scale = 1.0 / math.sqrt(d)
    segmented = segment_ids is not None

    # D_i = Σ_d dO·O (FlashAttention-2 eq. 4), lane-padded for TPU tiling.
    delta = jnp.sum(
        do_t.astype(jnp.float32) * out_t.astype(jnp.float32), axis=-1
    )
    lse8 = jnp.broadcast_to(lse[..., None], lse.shape + (_LSE_LANES,))
    delta8 = jnp.broadcast_to(delta[..., None], delta.shape + (_LSE_LANES,))

    qkv_spec = pl.BlockSpec(
        (1, 1, block_q, d), lambda ib, ih, j, i: (ib, ih, i, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, 1, block_kv, d), lambda ib, ih, j, i, g=group: (ib, ih // g, j, 0)
    )
    lane_spec = pl.BlockSpec(
        (1, 1, block_q, _LSE_LANES), lambda ib, ih, j, i: (ib, ih, i, 0)
    )
    dkdv_in_specs = [qkv_spec, kv_spec, kv_spec, qkv_spec, lane_spec,
                     lane_spec]
    dkdv_inputs = [q_t, k_t, v_t, do_t, lse8, delta8]
    if segmented:
        seg_q, seg_kv = _seg_lane_blocks(segment_ids)
        # dkdv grid is (b, h, kv_blocks=j, q_blocks=i).
        dkdv_in_specs += [
            pl.BlockSpec(
                (1, block_q, _SEG_LANES), lambda ib, ih, j, i: (ib, i, 0)
            ),
            pl.BlockSpec(
                (1, _SEG_LANES, block_kv), lambda ib, ih, j, i: (ib, 0, j)
            ),
        ]
        dkdv_inputs += [seg_q, seg_kv]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal,
            segmented=segmented,
            block_q=block_q, block_kv=block_kv, num_q_blocks=nq,
            window=window,
        ),
        grid=(b, h, nk, nq),
        in_specs=dkdv_in_specs,
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda ib, ih, j, i: (ib, ih, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda ib, ih, j, i: (ib, ih, j, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_kv, d), k_t.dtype),
            jax.ShapeDtypeStruct((b, h, s_kv, d), v_t.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary",
            )
        ),
        interpret=interpret,
    )(*dkdv_inputs)
    # GQA: per-q-head dk/dv fold back onto the kv heads.
    dk = dk.reshape(b, h_kv, group, s_kv, d).sum(2)
    dv = dv.reshape(b, h_kv, group, s_kv, d).sum(2)

    dq_in_specs = [
        pl.BlockSpec(
            (1, 1, block_q, d), lambda ib, ih, i, j: (ib, ih, i, 0)
        ),
        pl.BlockSpec(
            (1, 1, block_kv, d),
            lambda ib, ih, i, j, g=group: (ib, ih // g, j, 0),
        ),
        pl.BlockSpec(
            (1, 1, block_kv, d),
            lambda ib, ih, i, j, g=group: (ib, ih // g, j, 0),
        ),
        pl.BlockSpec(
            (1, 1, block_q, d), lambda ib, ih, i, j: (ib, ih, i, 0)
        ),
        pl.BlockSpec(
            (1, 1, block_q, _LSE_LANES),
            lambda ib, ih, i, j: (ib, ih, i, 0),
        ),
        pl.BlockSpec(
            (1, 1, block_q, _LSE_LANES),
            lambda ib, ih, i, j: (ib, ih, i, 0),
        ),
    ]
    dq_inputs = [q_t, k_t, v_t, do_t, lse8, delta8]
    if segmented:
        # dq grid is (b, h, q_blocks=i, kv_blocks=j).
        dq_in_specs += [
            pl.BlockSpec(
                (1, block_q, _SEG_LANES), lambda ib, ih, i, j: (ib, i, 0)
            ),
            pl.BlockSpec(
                (1, _SEG_LANES, block_kv), lambda ib, ih, i, j: (ib, 0, j)
            ),
        ]
        dq_inputs += [seg_q, seg_kv]
    (dq,) = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            segmented=segmented,
            block_q=block_q, block_kv=block_kv, num_kv_blocks=nk,
            window=window,
        ),
        grid=(b, h, nq, nk),
        in_specs=dq_in_specs,
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda ib, ih, i, j: (ib, ih, i, 0)
            ),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, s_q, d), q_t.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary",
            )
        ),
        interpret=interpret,
    )(*dq_inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def shard_kernel_over_mesh(kernel, q, k, v, segment_ids=None):
    """Run ``kernel(q, k, v, segment_ids)`` once per shard of the ambient
    mesh (:func:`dlrover_tpu.parallel.mesh.use_mesh`).

    GSPMD refuses a compiled Mosaic kernel in a program that spans more
    than one device ("Mosaic kernels cannot be automatically
    partitioned"), so there the kernel runs under ``shard_map``: batch
    over the data axes, heads over ``tp``, the sequence whole — the layout
    every rule table in ``parallel/sharding.py`` gives attention
    activations.  Attention is independent per example and per head, so
    no collective is needed inside.  One device or no mesh: a plain call.

    A caller that is already inside a ``shard_map`` (Ulysses calls the
    kernel between its two all_to_alls) has made some or all axes manual:
    only the axes still automatic are taken here, and when none is left
    the kernel is called as it is.
    """
    from jax.sharding import PartitionSpec as P

    from dlrover_tpu.parallel.mesh import DATA_AXES, current_mesh

    mesh = current_mesh()
    if mesh is None:
        return kernel(q, k, v, segment_ids)
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    auto = tuple(a for a in mesh.axis_names if a not in manual)
    if math.prod(mesh.shape[a] for a in auto) == 1:
        return kernel(q, k, v, segment_ids)
    data = tuple(a for a in DATA_AXES if a in auto)
    heads = "tp" if "tp" in auto else None
    qkv = P(data, None, heads, None)
    args, specs = (q, k, v), (qkv, qkv, qkv)
    if segment_ids is not None:
        args, specs = args + (segment_ids,), specs + (P(data, None),)
    return jax.shard_map(
        lambda q_, k_, v_, seg_=None: kernel(q_, k_, v_, seg_),
        # Nested, the region's own context mesh is the one to extend.
        mesh=None if manual else mesh, axis_names=frozenset(auto),
        in_specs=specs, out_specs=qkv, check_vma=False,
    )(*args)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8)
)
def _flash_attention(q, k, v, segment_ids, causal, block_q, block_kv,
                     interpret, window=None):
    out, _ = _fa_fwd(
        q, k, v, segment_ids, causal, block_q, block_kv, interpret, window
    )
    return out


def _fa_fwd(q, k, v, segment_ids, causal, block_q, block_kv, interpret,
            window=None):
    q_t = q.transpose(0, 2, 1, 3)
    k_t = k.transpose(0, 2, 1, 3)
    v_t = v.transpose(0, 2, 1, 3)
    out_t, lse = _flash_fwd(
        q_t, k_t, v_t, segment_ids,
        causal=causal, block_q=block_q, block_kv=block_kv, interpret=interpret,
        window=window,
    )
    return (
        out_t.transpose(0, 2, 1, 3),
        (q_t, k_t, v_t, out_t, lse, segment_ids),
    )


def _fa_bwd(causal, block_q, block_kv, interpret, window, res, do):
    q_t, k_t, v_t, out_t, lse, segment_ids = res
    do_t = do.transpose(0, 2, 1, 3)
    dq, dk, dv = _flash_bwd_pallas(
        q_t, k_t, v_t, out_t, lse, do_t, segment_ids,
        causal=causal, block_q=block_q, block_kv=block_kv,
        interpret=interpret, window=window,
    )
    return (
        dq.transpose(0, 2, 1, 3),
        dk.transpose(0, 2, 1, 3),
        dv.transpose(0, 2, 1, 3),
        None,  # segment ids are integer data, no cotangent
    )


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_gqa(
    q,
    k,
    v,
    segment_ids=None,
    block_q: int = 512,
    block_kv: int = 512,
    causal: bool = True,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
):
    """Blockwise fused attention; q (b, s, h, d), k/v (b, s, h_kv, d).

    ``segment_ids`` (b, s) runs the segmented kernel variant (causal ∧
    same-segment predicate fused inside every block — packed rows never
    materialize a (b, s, s) mask).  ``window`` (causal only) is a sliding
    window: key ``j`` is seen by query ``t`` iff ``0 <= t - j < window``,
    the query's own position counted; blocks wholly behind it are skipped
    like those above the diagonal, and it composes with ``segment_ids``.
    On a TPU the kernel is what was asked for: shapes that do not tile
    raise.  Off the TPU the kernel runs in interpret mode and untileable
    shapes take the XLA reference.
    """
    check_window(window, causal)
    b, s_q, h, d = q.shape
    s_kv, h_kv = k.shape[1], k.shape[2]
    block_q = min(block_q, s_q)
    block_kv = min(block_kv, s_kv)
    tileable = (
        s_q % block_q == 0
        and s_kv % block_kv == 0
        and h % h_kv == 0
        and block_q >= 8
        and block_kv >= 8
    )
    if interpret is None:
        interpret = pallas_interpret()
    if not tileable:
        if not interpret:
            raise ValueError(
                f"flash attention cannot tile q{tuple(q.shape)} "
                f"k{tuple(k.shape)} with blocks ({block_q}, {block_kv}); "
                f"on a TPU nothing falls back to a reference — give this "
                f"caller a path of its own (attention_impl='dot')"
            )
        return mha_reference(
            q, k, v, causal=causal, segment_ids=segment_ids, window=window)

    def kernel(q_, k_, v_, seg_):
        return _flash_attention(
            q_, k_, v_, seg_, causal, block_q, block_kv, interpret, window
        )

    if interpret:  # plain HLO: GSPMD partitions it itself
        return kernel(q, k, v, segment_ids)
    return shard_kernel_over_mesh(kernel, q, k, v, segment_ids)
