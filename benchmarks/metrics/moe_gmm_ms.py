"""Device time a step spends in the grouped-product kernels of the routed
experts (forward, the rows' gradient and the weights' gradient, and the
forward recomputed in the backward pass), from the trace: self time of the
matching operations, averaged over the devices, over the steps traced."""

import runlog
from reduce import xplane

UNIT = "ms"
# The names the trace gives the library's Mosaic calls: gmm, gmm.1, ...
# (the forward product and the rows' gradient) and tgmm, tgmm.1, ... (the
# weights' gradient).
KERNEL = r"^t?gmm(\.\d+)?$"


def seconds_per_step(run):
    trace = runlog.first(run, "trace")
    if not run["reduced"] or trace is None:
        return None
    steps = trace["step_to"] - trace["step_from"]
    total = xplane.op_seconds(run["reduced"], KERNEL)
    return None if total is None or steps <= 0 else total / steps


def read(run):
    seconds = seconds_per_step(run)
    return None if seconds is None else seconds * 1e3
