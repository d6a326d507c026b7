"""The attention kernels' share of their roofline in a layer-pattern
configuration: the least time a chip could take for one step's attention
(``flops_granite_hybrid.py``: six causal multiplications in each attention
layer at the true head dim, q/k/v/o and their gradients moved once) over
the kernels' measured time.  A kernel that pads the head dim to the lane
width spends the padding's time and earns nothing for it here."""

import flops_granite_hybrid as flops
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
