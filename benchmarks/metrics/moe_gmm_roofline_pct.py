"""The grouped-product kernels' share of their roofline: the least time a
chip could take for one step's expert products at the expected picks
(``flops_lfm2_moe.py::grouped_matmul_cost``: nine multiplications a routed
layer, each operand and result moved once) over the kernels' measured time.
A forward product recomputed in the backward pass spends its time and
earns nothing for it here."""

import flops_lfm2_moe as flops
from metrics import moe_gmm_ms

UNIT = "%"


def read(run):
    measured = moe_gmm_ms.seconds_per_step(run)
    if measured is None or run["peak"] is None:
        return None
    cfg = run["config"]
    need, moved = flops.grouped_matmul_cost(cfg, cfg["batch"], cfg["seq"])
    chips = run["cell"]["chips"]
    least, _bound = flops.least_seconds(need / chips, moved / chips, run["peak"])
    return 100.0 * least / measured
