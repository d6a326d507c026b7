"""Grouped matrix product: rows sorted by group, one weight matrix a group.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies the first
``group_sizes[0]`` rows of ``lhs`` (m, k) by ``rhs[0]`` (k, n), the next
``group_sizes[1]`` rows by ``rhs[1]``, and so on.  The sizes may add up to
less than ``m``: the rows past their sum come out as zeros and send zeros
back, so a caller sizes ``lhs`` for the worst case and pays for the rows
that are there.  This is what a dropless expert layer needs
(``models/moe.py::RoutedExperts``): the (token, pick) pairs sorted by
expert, the held experts' weights stacked.

On a TPU the product is the library's Mosaic kernel
(``jax.experimental.pallas.ops.tpu.megablox``): its grid covers the tiles
the groups reach, so the work follows ``group_sizes`` and not ``m``.  The
backward pass is two more products of the same library: the rows'
gradient against the transposed weights (``gmm``), and the weights'
gradient group by group (``tgmm``); each has its own tiling
(:func:`tilings`).  Operands in the callers' dtype (bf16), accumulation
float32 inside the kernels, outputs in the operands' dtype.

Off the TPU (the CPU tests) the call is ``jax.lax.ragged_dot``, and that
is observable as splash's fallback is: one warning and the counter
``dlrover_moe_fallback_total{reason}``, which every run on a chip asserts
to be empty.  No option names the path.  A program that spans several
TPU devices is refused by name: GSPMD cannot partition a Mosaic call, and
the exchange that would give each chip its own rows (``ep``) is not built.
"""

import functools
import math

import jax
import jax.numpy as jnp

from dlrover_tpu.common.log import logger
from dlrover_tpu.common.platform import pallas_interpret

_warned_reasons = set()


def _record_fallback(reason: str):
    from dlrover_tpu.telemetry import metrics as tmetrics

    tmetrics.counter(
        "dlrover_moe_fallback_total",
        "Grouped expert products that ran off the TPU kernel, by reason.",
    ).inc(reason=reason)
    if reason not in _warned_reasons:
        _warned_reasons.add(reason)
        logger.warning(
            "grouped matmul: jax.lax.ragged_dot instead of the TPU kernel "
            "(reason=%s); further ones are counted in "
            "dlrover_moe_fallback_total, not warned about", reason,
        )


def _tile(size: int, most: int) -> int:
    """The largest multiple of 128 up to ``most`` that divides ``size``,
    or ``size`` itself where it is smaller than a lane tile."""
    if size <= 128:
        return size
    for tile in range(min(most, size) // 128 * 128, 0, -128):
        if size % tile == 0:
            return tile
    return 128


def _contraction_tile(size: int) -> int:
    """The whole contraction in one tile up to 2048 (no loop over it, an
    operand block of 2 MiB), else a divisor up to 1024."""
    return size if size <= 2048 else _tile(size, 1024)


# What one grouped product may hold in the kernel's fast memory: the
# compiler's scoped limit on a v5e is 16 MiB and it adds some 0.6 MiB of
# its own to the blocks counted here ((512, 2048, 896) counts 14.5 MiB and
# compiles; (512, 2048, 1024) counts 16.0 and is refused at 16.58).
_VMEM_BUDGET = 15 * 2 ** 20


def _fits(tm: int, tk: int, tn: int) -> bool:
    """Both operands' bf16 blocks and the output's, double-buffered, and
    the float32 accumulator."""
    blocks = tm * tk + tk * tn + tm * tn
    return 2 * blocks * 2 + tm * tn * 4 <= _VMEM_BUDGET


def _output_tile(tm: int, tk: int, size: int) -> int:
    """The widest output tile up to 1024 whose product fits the fast
    memory beside a contraction tile of ``tk``."""
    tn = _tile(size, 1024)
    while tn > 128 and not _fits(tm, tk, tn):
        tn = _tile(size, tn - 128)
    return tn


def tilings(m: int, k: int, n: int):
    """(tm, tk, tn) for the forward product, the rows' gradient and the
    weights' gradient of an (m, k) x (g, k, n) call.  ``tm`` tiles the
    rows in all three; the other two tile the contraction and the output
    of each product as the library names them.  Swept on a v5e at the
    benchmark's shapes (131072 rows, 2048 x 3584 and 1792 x 2048, a
    quarter of the rows in groups; PERF.md, PR 34): each is within 3% of
    the best of 18 to 27 tilings tried for its product.  Where the whole
    contraction (2048) and an output tile of 1024 do not fit the kernel's
    fast memory together (2048 x 2048 products: PR 36) the output tile
    narrows and the contraction stays whole."""
    tm = _tile(m, 512)

    def product(contraction, output):
        tk = _contraction_tile(contraction)
        return tm, tk, _output_tile(tm, tk, output)

    return (
        product(k, n),  # out = lhs . rhs
        product(n, k),  # dlhs = dout . rhs^T
        (tm, _tile(k, 1024), _tile(n, 1024)),  # drhs = lhs^T . dout
    )


def plan(m: int, k: int, n: int) -> dict:
    """What a call of these sizes will run, for the ``lower`` span."""
    if pallas_interpret():
        return {"path": "ragged_dot", "tiling": None}
    return {"path": "megablox", "tiling": [list(t) for t in tilings(m, k, n)]}


def _with_tail(group_sizes, m):
    """The library zeroes the rows of groups past ``rhs``'s: name the rows
    past the sizes' sum as one more group."""
    tail = m - jnp.sum(group_sizes, dtype=jnp.int32)
    return jnp.concatenate([group_sizes.astype(jnp.int32), tail[None]])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _megablox(lhs, rhs, sizes, tiles, interpret):
    from jax.experimental.pallas.ops.tpu.megablox import ops

    return ops.backend.gmm(
        lhs, rhs, sizes, lhs.dtype, tiles[0], jnp.int32(0),
        interpret=interpret)


def _megablox_fwd(lhs, rhs, sizes, tiles, interpret):
    return _megablox(lhs, rhs, sizes, tiles, interpret), (lhs, rhs, sizes)


def _megablox_bwd(tiles, interpret, residual, grad):
    from jax.experimental.pallas.ops.tpu.megablox import ops

    lhs, rhs, sizes = residual
    grad = grad.astype(lhs.dtype)
    grad_lhs = ops.backend.gmm(
        grad, rhs, sizes, lhs.dtype, tiles[1], jnp.int32(0),
        transpose_rhs=True, interpret=interpret)
    grad_rhs = ops.backend.tgmm(
        lhs.swapaxes(0, 1), grad, sizes, rhs.dtype, tiles[2], jnp.int32(0),
        rhs.shape[0], interpret=interpret)
    return grad_lhs, grad_rhs, None


_megablox.defvjp(_megablox_fwd, _megablox_bwd)


def _spans_devices() -> bool:
    from dlrover_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return False
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    return math.prod(
        mesh.shape[a] for a in mesh.axis_names if a not in manual) > 1


def grouped_matmul(lhs, rhs, group_sizes, interpret=None):
    """lhs: (m, k), rows sorted by group; rhs: (g, k, n); group_sizes:
    (g,) int32 with a sum of at most ``m``.  Returns (m, n) in
    ``lhs.dtype``, zeros in the rows past the sizes' sum.

    ``interpret=None`` is the rule of the module docstring.  ``True``
    forces the library kernel in Pallas interpret mode (the CPU tests of
    the kernel's path), ``False`` forces it compiled (compiling for a
    described chip)."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    if interpret is None:
        if pallas_interpret():
            _record_fallback("backend")
            return jax.lax.ragged_dot(
                lhs, rhs, group_sizes.astype(jnp.int32),
                preferred_element_type=jnp.float32,
            ).astype(lhs.dtype)
        if _spans_devices():
            raise NotImplementedError(
                "grouped_matmul: the TPU kernel runs on one device's rows "
                "and GSPMD cannot partition it; over several chips the "
                "expert layer needs its ep exchange (ROADMAP R1), which is "
                "not built")
        interpret = False
    tiles = tilings(m, k, n)
    if m % tiles[0][0]:
        raise ValueError(
            f"grouped_matmul: {m} rows do not divide by the row tile "
            f"{tiles[0][0]}; on a TPU nothing falls back to another "
            f"product: give the caller a multiple of 128 rows")
    return _megablox(lhs, rhs, _with_tail(group_sizes, m), tiles, interpret)
