"""Model FLOP/s utilisation of a layer-pattern configuration: the
operations a token needs (``flops_granite_hybrid.py``: matmuls by layer
kind and of the head, causal attention in the attention layers, the scan as
the recurrence, nothing recomputed) times the tokens a second of the blocks
the profiler did not share, over chips times the peak of ``peaks.json``."""

import flops_granite_hybrid as flops
import runlog

UNIT = "%"


def read(run):
    tokens_per_s = runlog.mean_tokens_per_s(run)
    if tokens_per_s is None or run["peak"] is None:
        return None
    need = flops.train_flops_per_token(run["config"], run["config"]["seq"])
    peak = run["cell"]["chips"] * run["peak"]["bf16_flops_per_s"]
    return 100.0 * need * tokens_per_s / peak
