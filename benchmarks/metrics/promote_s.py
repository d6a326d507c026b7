"""SIGKILL to the standby's return from ``standby_barrier()``: the agent
noticing the death and activating the parked process."""

import runlog

UNIT = "s"


def read(run):
    activated = runlog.first(run, "activated")
    if run["t_kill"] is None or activated is None:
        return None
    return activated["t"] - run["t_kill"]
