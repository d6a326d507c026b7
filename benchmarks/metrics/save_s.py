"""Median, over the saves the engine accepted in the window and shared
memory held before its close, of the seconds from the ``save_checkpoint``
call to the stager's "staged to shm" for that step."""

import runlog

UNIT = "s"


def read(run):
    return runlog.median(
        staged["t"] - save["t_call"] for save, staged in runlog.whole_saves(run)
    )
