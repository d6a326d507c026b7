"""The resumed process's activation (or start, if it was spawned cold) to
its first ``jax.devices()`` return: taking the chip."""

import runlog

UNIT = "s"


def read(run):
    device = runlog.first(run, "device", restart=1)
    if device is None:
        return None
    began = runlog.first(run, "activated", pid=device["pid"]) or runlog.first(
        run, "start", pid=device["pid"])
    return device["t"] - began["t"]
