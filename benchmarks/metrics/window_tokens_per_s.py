"""Tokens of all the window's fetch-to-fetch blocks over their host
seconds: the mean rate, stalls included.  In a traced run the blocks that
shared their time with the profiler are left out."""

import runlog

UNIT = "tokens/s"


def read(run):
    return runlog.mean_tokens_per_s(run)
