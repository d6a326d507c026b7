"""Median host milliseconds the training thread spent inside an accepted
``save_checkpoint(block=False)`` in the window: its stall."""

import runlog

UNIT = "ms"


def read(run):
    stall = runlog.median(s["seconds"] for s, _g in runlog.window_saves(run))
    return None if stall is None else stall * 1e3
