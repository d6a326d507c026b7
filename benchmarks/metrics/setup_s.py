"""Harness process start to the window's opening: imports, the agent, the
backend, state, compilation or its cache, the reference check, warm-up
and what the traffic needs before its window (a first save, the steps
before a kill)."""

import runlog

UNIT = "s"


def read(run):
    opened = run["t_kill"] if run["params"]["kill"] else (
        (runlog.window_open(run) or {}).get("t"))
    return None if opened is None else opened - run["t0"]
