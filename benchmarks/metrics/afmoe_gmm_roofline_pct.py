"""The grouped-product kernels' share of their roofline in an AFMoE
configuration: ``moe_gmm_roofline_pct`` with this family's count
(``flops_afmoe.py::grouped_matmul_cost``: nine multiplications a routed
layer over the pairs expected here, each operand and result moved once)
over the kernels' measured time (``moe_gmm_ms``).  A forward product
recomputed in the backward pass spends its time and earns nothing for it
here; the shared expert is a plain product and no part of either."""

import flops_afmoe as flops
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
