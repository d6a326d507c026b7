"""Median copy (and crc) into shared memory of the window's whole saves,
as the engine's "staged to shm" line gives it."""

import runlog

UNIT = "s"


def read(run):
    return runlog.median(g["memcpy_s"] for _s, g in runlog.whole_saves(run))
