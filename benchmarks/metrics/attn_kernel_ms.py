"""Device time a step spends in the attention kernels (forward and
backward), from the trace: self time of the matching operations, averaged
over the devices, over the steps traced."""

import runlog
from reduce import xplane

UNIT = "ms"
# The names the trace gives the kernel's Mosaic calls: splash_mha_fwd...,
# splash_mha_dkv... (which also yields dq), and the in-tree flash kernel's.
KERNEL = r"^(splash_mha|flash_attention|flash_mha)"


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
