"""Saves in the window the engine skipped because a drain was in flight."""

import runlog

UNIT = "count"


def read(run):
    opened = runlog.window_open(run)
    if opened is None:
        return None
    return sum(1 for e in runlog.of(run, "skipped") if e["t"] >= opened["t"])
