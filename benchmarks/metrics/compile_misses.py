"""Compilation-cache misses JAX reported inside the window (expected 0:
every shape is warmed before it)."""

import runlog

UNIT = "count"


def read(run):
    end = runlog.first(run, "end")
    return None if end is None else end["cache_in_window"]["misses"]
