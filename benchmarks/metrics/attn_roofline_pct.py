"""The attention kernels' share of their roofline: the least time a chip
could take for one step's attention (``flops.py``: six causal
multiplications a layer, q/k/v/o and their gradients moved once; the
larger of operations over peak FLOP/s and bytes over peak bandwidth, which
at these shapes is the operations) over the kernels' measured time."""

import flops
from metrics import attn_kernel_ms

UNIT = "%"


def read(run):
    measured = attn_kernel_ms.seconds_per_step(run)
    if measured is None or run["peak"] is None:
        return None
    cfg = run["config"]
    need, moved = flops.attention_kernel_cost(cfg, cfg["batch"], cfg["seq"])
    chips = run["cell"]["chips"]
    least, _bound = flops.least_seconds(need / chips, moved / chips, run["peak"])
    return 100.0 * least / measured
