"""A per-layer metric added by a file alone: the tests' own reader."""

import runlog

UNIT = "count"


def read(run):
    return len(runlog.window_fetches(run))
