"""Device-mesh construction — the TPU-native replacement for the reference's
named process-group fabric.

Reference parity: ``atorch/atorch/distributed/distributed.py:323``
(``create_parallel_group`` building NCCL groups from
``([("model",2),("pipeline",2),("data",4)], None)`` configs).  On TPU there
are no per-group communicators: one ``jax.sharding.Mesh`` with named axes
drives GSPMD, and XLA inserts the collectives.  This module owns axis naming,
device factorization, and hybrid ICI/DCN (multi-slice) layout.

Canonical axis order (outermost/slowest first — DCN-friendly dims first so
cross-slice traffic rides the data dim, ICI-heavy dims last):

    pp  — pipeline stages      (DCN ok)
    dp  — pure data parallel   (DCN ok)
    fsdp— data parallel w/ param sharding (ZeRO-3 analog; ICI preferred)
    ep  — expert parallel (MoE all-to-all)
    sp  — sequence/context parallel (ring attention / Ulysses)
    tp  — tensor parallel      (ICI required; innermost = fastest)
"""

import contextlib
import contextvars
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical order: DCN-tolerant axes first, ICI-hungry axes last.
AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")

# Axes over which model parameters are replicated (pure data dims).
DATA_AXES = ("dp", "fsdp")


@dataclass
class MeshConfig:
    """Sizes of each named mesh axis; -1 on `dp` means "fill remaining"."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1
    # Number of TPU slices (multi-slice via DCN); 1 = single slice.
    num_slices: int = 1

    def resolved(self, n_devices: int) -> "MeshConfig":
        """Fill the -1 axis so the product equals n_devices."""
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        fill = [a for a, s in sizes.items() if s == -1]
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if n_devices % fixed != 0:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed axes {sizes}"
            )
        rest = n_devices // fixed
        if not fill:
            if fixed != n_devices:
                raise ValueError(
                    f"mesh {sizes} covers {fixed} devices, have {n_devices}"
                )
        elif len(fill) == 1:
            sizes[fill[0]] = rest
        else:
            raise ValueError("at most one axis may be -1")
        out = MeshConfig(num_slices=self.num_slices, **sizes)
        return out

    def axis_sizes(self) -> Tuple[int, ...]:
        return tuple(getattr(self, a) for a in AXIS_ORDER)

    def total_devices(self) -> int:
        return math.prod(self.axis_sizes())


def build_mesh(
    config: MeshConfig,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the global mesh.

    Single-slice: ``mesh_utils.create_device_mesh`` lays devices out so the
    innermost (tp) axis maps to nearest-neighbor ICI links.  Multi-slice:
    ``create_hybrid_device_mesh`` puts the leading (pp/dp) axes on DCN.
    """
    devices = list(devices if devices is not None else jax.devices())
    config = config.resolved(len(devices))
    shape = config.axis_sizes()
    try:
        from jax.experimental import mesh_utils

        if config.num_slices > 1:
            # Leading axes span DCN: split pp/dp across slices.
            dcn_shape = _dcn_split(shape, config.num_slices)
            ici_shape = tuple(
                s // d for s, d in zip(shape, dcn_shape)
            )
            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices
            )
        else:
            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except Exception:
        # CPU test meshes (and odd shapes) fall back to a plain reshape.
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def _dcn_split(shape: Tuple[int, ...], num_slices: int) -> Tuple[int, ...]:
    """Distribute the slice count over the leading DCN-tolerant axes."""
    dcn = [1] * len(shape)
    remaining = num_slices
    for i, size in enumerate(shape):
        if remaining == 1:
            break
        g = math.gcd(size, remaining)
        dcn[i] = g
        remaining //= g
    if remaining != 1:
        raise ValueError(
            f"cannot split {num_slices} slices over mesh shape {shape}"
        )
    return tuple(dcn)


def simple_factorize(n: int, prefer_tp: int = 0) -> MeshConfig:
    """Pick a reasonable (dp, fsdp, tp) factorization of n devices.

    Used by dry-runs and auto-config when the user gives no strategy:
    tp gets up to `prefer_tp` (or up to 4 if n allows), fsdp gets the
    middle factor, dp the rest.
    """
    tp = prefer_tp or min(4, _largest_pow2_divisor(n))
    while n % tp != 0:
        tp //= 2
    rem = n // tp
    fsdp = _largest_pow2_divisor(rem)
    fsdp = min(fsdp, rem)
    dp = rem // fsdp
    return MeshConfig(dp=dp, fsdp=fsdp, tp=tp)


def _largest_pow2_divisor(n: int) -> int:
    p = 1
    while n % (p * 2) == 0:
        p *= 2
    return p


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# -- ambient mesh ----------------------------------------------------------
#
# Ring/Ulysses attention live *inside* a jitted model but need the concrete
# Mesh to open a shard_map region.  Rather than threading the mesh through
# every module config, the train step publishes it here for the duration of
# tracing (reference analog: atorch's process-group globals,
# ``distributed/distributed.py`` parallel_group(name) accessors).

_CURRENT_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "dlrover_tpu_mesh", default=None
)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    token = _CURRENT_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _CURRENT_MESH.reset(token)


def current_mesh() -> Optional[Mesh]:
    mesh = _CURRENT_MESH.get()
    if mesh is not None:
        return mesh
    # Fall back to the ambient `with mesh:` context if one is active.
    try:
        ambient = jax.sharding.get_mesh()
    except ValueError:
        # Inside jit/eval_shape tracing get_mesh() raises; a meshless
        # trace (e.g. a shape probe before the step is built) degrades
        # to single-shard semantics, which is shape-identical.
        return None
    return None if ambient.empty else ambient


def axis_size(mesh: Optional[Mesh], name: str) -> int:
    if mesh is None:
        return 1
    return mesh_axis_sizes(mesh).get(name, 1)
