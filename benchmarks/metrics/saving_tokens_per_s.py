"""The mean token rate of the window while saves are taken every
``save_every`` steps (``window_tokens_per_s`` under another name, because
here it is held beside ``save_s``: a save made faster by starving the step
shows in this number).  Against ``mistral7b.steady`` it is what saving
costs."""

import runlog

UNIT = "tokens/s"


def read(run):
    return runlog.mean_tokens_per_s(run)
