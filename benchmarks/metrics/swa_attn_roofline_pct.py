"""The attention kernels' share of their roofline where layers differ in
their masks: the least time a chip could take for one step's attention
(``flops_afmoe.py``: six multiplications a layer over the (query, key)
pairs that layer's mask keeps, ``sum_t min(t + 1, window)`` a row under a
sliding window; q/k/v/o and their gradients moved once) over the kernels'
measured time (``attn_kernel_ms``: every splash call of the step, both
masks).  A kernel that visits a whole block the window half covers spends
that time and earns nothing for it here."""

import flops_afmoe as flops
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
