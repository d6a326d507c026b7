"""Median over the window's fetch-to-fetch blocks of host seconds a step;
blocks that shared their time with the profiler are left out."""

import runlog

UNIT = "ms"


def read(run):
    per_step = runlog.median(s / n for n, s in runlog.blocks(run))
    return None if per_step is None else per_step * 1e3
