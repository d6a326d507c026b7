"""The resumed process's ``create_sharded_state``: the state is built (and
its init program loaded) before the restore overwrites it."""

import runlog

UNIT = "s"


def read(run):
    device = runlog.first(run, "device", restart=1)
    state = device and runlog.first(run, "state", pid=device["pid"])
    return state["seconds"] if state else None
