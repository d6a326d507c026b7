"""Seconds from the SIGKILL of the worker that holds the chip to the end
of the first step the next process completed: the downtime of
``goodput.py::_analyze``.  A resume that missed the driver's deadline reads
as the deadline."""

import runlog

UNIT = "s"


def read(run):
    if run["t_kill"] is None:
        return None
    fetches = runlog.resumed_fetches(run)
    if len(fetches) < run["params"]["resume_steps"]:
        return run.get("deadline_s")
    return fetches[0]["t"] - run["t_kill"]
