"""Tokens a step over the median, across the window's fetch-to-fetch blocks,
of host seconds a step.  The loss is fetched every ``log_every`` steps (a log
interval), which ends in the device's result, so each block's clock covers
finished work; the median over the blocks is what a rare stall of the host
does not move (``window_tokens_per_s`` is the mean, which it does)."""

import runlog

UNIT = "tokens/s"


def read(run):
    blocks = runlog.blocks(run, traced=True)
    if not blocks:
        return None
    return runlog.tokens_per_step(run) / runlog.median(
        s / n for n, s in blocks)
