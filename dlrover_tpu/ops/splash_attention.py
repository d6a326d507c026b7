"""Splash-attention module replacement: JAX's tuned TPU sparse-flash kernel.

Reference parity: atorch's *module replace* optimization swaps HF attention
modules for tuned flash-attn CUDA kernels
(``auto/opt_lib/module_replace_optimization.py``,
``modules/transformer/layers.py``).  The TPU analog of "the tuned vendor
kernel" is ``jax.experimental.pallas.ops.tpu.splash_attention`` — same
blockwise online-softmax algorithm as :mod:`dlrover_tpu.ops.flash_attention`
(our own Pallas kernel, kept as the readable in-tree implementation and the
off-TPU path) but with deeper schedule tuning (fused bwd, kv-compute
sub-blocking).  Selected via ``LlamaConfig(attention_impl="splash")``.

Packed sequences run on the fast kernel too: ``segment_ids`` rides the
kernel's native ``SegmentIds(q, kv)`` argument (the causal ∧ same-segment
predicate is fused inside the kernel — no (b, s, s) mask ever exists), and
when the packer bounds document length (``max_segment_len``) the static
mask becomes a causal *band* — blocks further than one document length
below the diagonal are pruned from the schedule entirely, which is where
the Σᵢ sᵢ² ≪ s² FLOP saving is actually cashed in (dynamic segment ids
alone only mask, they don't skip).

On a TPU ``attention_impl="splash"`` means the library kernel or an error:
a shape it cannot tile raises and names the shape.  Off the TPU (the CPU
tests) the call takes the in-tree path, and that is observable: a one-time
warning plus the ``dlrover_attention_fallback_total{reason}`` counter in
/metrics, which every run on a chip asserts to be zero.

Layout adapter: model zoo uses q (b, s, h, d) / k,v (b, s, h_kv, d); splash
wants (h, s, d) per example with pre-scaled q, vmapped over batch.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.common.log import logger
from dlrover_tpu.common.platform import pallas_interpret

# Reasons already warned about (warn once per process, count every time).
_warned_reasons = set()

# The library's forward wraps its two results, ``out`` and ``logsumexp``, in
# this name.  A recomputation policy that keeps it hands the backward kernel
# the forward pass's own results, where it would otherwise run the forward
# kernel a second time to make them (models/hybrid.py::recompute_policy).
# Outside a ``jax.checkpoint`` the name is the identity.
KERNEL_RESULTS = "splash_attention_results"


def _record_fallback(reason: str):
    """One-time warning + always-on counter for splash-kernel fallbacks."""
    from dlrover_tpu.telemetry import metrics as tmetrics

    tmetrics.counter(
        "dlrover_attention_fallback_total",
        "Attention calls that fell back off the splash kernel, by reason.",
    ).inc(reason=reason)
    if reason not in _warned_reasons:
        _warned_reasons.add(reason)
        logger.warning(
            "splash attention: falling back to the in-tree path "
            "(reason=%s); subsequent fallbacks are counted in "
            "dlrover_attention_fallback_total, not re-warned", reason,
        )


def _build_kernel(
    s_q: int,
    s_kv: int,
    num_heads: int,
    block_q: int,
    block_kv: int,
    causal: bool,
    max_segment_len: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
):
    """One kernel a distinct static mask: causal, a causal band, or full.

    Two arguments give a band and promise different things.  ``window``
    IS the mask: key ``j`` is seen by query ``t`` iff ``0 <= t - j <
    window``, whatever the segments.  ``max_segment_len`` is a pruning
    hint: when no document spans more tokens, no in-document (q, k) pair
    is further apart, so its band is a *superset* of the true packed mask
    — ``SegmentIds`` supplies exactness, the band only prunes
    far-below-diagonal blocks from the schedule (the static FLOP saving).
    With both, the narrower band is built: the window masks, the hint
    cannot widen it.
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    band = min(
        (n for n in (window, max_segment_len) if n is not None), default=None)
    if causal and band is not None:
        head_mask = sm.LocalMask(
            (s_q, s_kv), window_size=(band - 1, 0), offset=0
        )
    elif causal:
        head_mask = sm.CausalMask((s_q, s_kv))
    else:
        head_mask = sm.FullMask((s_q, s_kv))
    mask = sm.MultiHeadMask([head_mask for _ in range(num_heads)])
    block_sizes = sk.BlockSizes(
        block_q=min(block_q, s_q),
        block_kv=min(block_kv, s_kv),
        block_kv_compute=min(block_kv, s_kv),
        block_q_dkv=min(block_q, s_q),
        block_kv_dkv=min(block_kv, s_kv),
        block_kv_dkv_compute=min(block_kv, s_kv),
        use_fused_bwd_kernel=True,
    )
    return sk.make_splash_mha(
        mask, block_sizes=block_sizes, head_shards=1, q_seq_shards=1,
        interpret=interpret, residual_checkpoint_name=KERNEL_RESULTS,
    )


# The kernel's blocks unless a caller says otherwise: what
# ``splash_attention_gqa`` runs and what ``mask_plan`` describes.
DEFAULT_BLOCK = 1024


def mask_plan(seq: int, window: Optional[int] = None,
              block_q: int = DEFAULT_BLOCK, block_kv: int = DEFAULT_BLOCK):
    """What the kernel's schedule visits for a causal mask (``window``
    None) or a sliding window over ``seq`` positions, for a model's
    ``lower`` span: the blocks, and the share of (q block, kv block) pairs
    the mask keeps: those with at least one seen (query, key) pair."""
    from dlrover_tpu.ops.flash_attention import block_live

    bq, bkv = min(block_q, seq), min(block_kv, seq)
    n_q, n_kv = -(-seq // bq), -(-seq // bkv)
    kept = sum(
        block_live(iq, ik, bq, bkv, True, window)
        for iq in range(n_q) for ik in range(n_kv))
    return dict(block_q=bq, block_kv=bkv, block_pairs=n_q * n_kv,
                kept=kept, kept_share=kept / (n_q * n_kv))


def kept_bytes(batch: int, seq: int, heads: int, head_dim: int, dtype) -> int:
    """What a policy that keeps ``KERNEL_RESULTS`` holds for one call as a
    model makes it (``interpret=None``), for a model's ``lower`` span:
    ``out`` in the call's dtype and ``logsumexp`` in float32, a row a
    (head, token).  0 off the TPU, where such a call takes the in-tree
    kernel, which carries no such name and is recomputed whole."""
    if pallas_interpret():
        return 0
    return batch * heads * seq * (head_dim * jnp.dtype(dtype).itemsize + 4)


def shapes_tileable(
    s_q: int,
    s_kv: int,
    h: int,
    h_kv: int,
    block_q: int,
    block_kv: int,
) -> bool:
    """Pure tileability predicate (backend-independent, unit-testable).

    Kernel-side constraints: sequences must divide by their effective
    blocks, the effective kv block (``bkv_compute = min(block_kv, s_kv)``)
    must be a lane multiple (128) and the q block a sublane multiple (8) —
    short sequences (shape-inference traces, tiny decode prefills) and odd
    user-set block sizes do not tile.  The head dim is not constrained: the
    installed kernel pads it to the lane width, and the v5e compiler
    accepts 32..256 (``tests/test_chip_compile.py`` keeps d=64 and d=128).
    """
    return (
        s_q % min(block_q, s_q) == 0
        and s_kv % min(block_kv, s_kv) == 0
        and min(block_kv, s_kv) % 128 == 0
        and min(block_q, s_q) % 8 == 0
        and h % h_kv == 0
    )


def splash_attention_gqa(
    q,
    k,
    v,
    segment_ids=None,
    block_q: int = DEFAULT_BLOCK,
    block_kv: int = DEFAULT_BLOCK,
    causal: bool = True,
    max_segment_len: Optional[int] = None,
    interpret: Optional[bool] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
):
    """Drop-in for :func:`flash_attention_gqa` backed by the library kernel.

    ``segment_ids`` (b, s) packed rows run the SAME fast kernel via its
    native ``SegmentIds`` argument; ``max_segment_len`` (packer row bound)
    additionally prunes blocks past the document-length band: a hint that
    masks nothing by itself.  ``window`` (causal only) is a mask: a sliding
    window in which key ``j`` is seen by query ``t`` iff ``0 <= t - j <
    window`` (the query's own position counted), built into the kernel's
    static mask so that blocks behind it are never visited; it composes
    with ``segment_ids``, and the off-TPU path honours it too.  On a TPU an
    untileable shape raises.  Off the TPU the call takes the in-tree
    Pallas/XLA path (counted, see the module docstring) — the swap never
    changes semantics, only the schedule.  Block defaults match
    ``LlamaConfig.flash_block_q/kv``.  ``interpret=True`` forces the library
    kernel in Pallas interpret mode (CPU correctness tests);
    ``interpret=False`` forces it compiled (compiling for a described chip).
    ``scale`` multiplies the scores before the softmax; ``None`` is
    ``1 / sqrt(head_dim)``.
    """
    from dlrover_tpu.ops.flash_attention import (
        check_window,
        flash_attention_gqa,
        shard_kernel_over_mesh,
    )

    check_window(window, causal)
    s_q, h, d = q.shape[1:]
    s_kv, h_kv = k.shape[1], k.shape[2]
    default_scale = 1.0 / math.sqrt(d)

    def in_tree(reason):
        _record_fallback(reason)
        # The in-tree kernel scales by 1/sqrt(d) itself: fold the rest of
        # a caller's scale into q.
        q_ = q if scale is None else q * jnp.asarray(
            scale / default_scale, q.dtype)
        # The in-tree kernel is tuned at <=512 blocks (its unfused bwd
        # has larger vmem footprints); cap here like the model's
        # attention_impl="flash" path does.
        return flash_attention_gqa(
            q_, k, v, segment_ids=segment_ids,
            block_q=min(block_q, 512), block_kv=min(block_kv, 512),
            causal=causal, window=window,
        )

    if interpret is None:
        if pallas_interpret():
            return in_tree("backend")
        interpret = False
    if not shapes_tileable(s_q, s_kv, h, h_kv, block_q, block_kv):
        if interpret:
            return in_tree("shape")
        raise ValueError(
            f"splash attention cannot tile q{tuple(q.shape)} "
            f"k{tuple(k.shape)} with blocks ({block_q}, {block_kv}); on a "
            f"TPU nothing falls back to another kernel — give this caller "
            f"a path of its own (attention_impl='dot')"
        )
    local = functools.partial(
        _splash_local, block_q=block_q, block_kv=block_kv, causal=causal,
        max_segment_len=max_segment_len, interpret=interpret,
        scale=default_scale if scale is None else scale, window=window,
    )
    if interpret:  # plain HLO: GSPMD partitions it itself
        return local(q, k, v, segment_ids)
    return shard_kernel_over_mesh(local, q, k, v, segment_ids)


def _splash_local(q, k, v, segment_ids, *, block_q, block_kv, causal,
                  max_segment_len, interpret, scale, window=None):
    """The library kernel on one device's (batch, heads) shard."""
    b, s_q, h, d = q.shape
    s_kv, h_kv = k.shape[1], k.shape[2]
    if h != h_kv:  # GQA: expand kv heads (splash MQA path needs h_kv == 1)
        k = jnp.repeat(k, h // h_kv, axis=2)
        v = jnp.repeat(v, h // h_kv, axis=2)
    kernel = _build_kernel(
        s_q, s_kv, h, block_q, block_kv, causal,
        max_segment_len=max_segment_len, interpret=interpret, window=window,
    )
    q_t = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)
    k_t = k.transpose(0, 2, 1, 3)
    v_t = v.transpose(0, 2, 1, 3)
    if segment_ids is not None:
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk,
        )

        seg = segment_ids.astype(jnp.int32)
        out = jax.vmap(
            lambda qe, ke, ve, se: kernel(qe, ke, ve, sk.SegmentIds(se, se))
        )(q_t, k_t, v_t, seg)
    else:
        out = jax.vmap(kernel)(q_t, k_t, v_t)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
