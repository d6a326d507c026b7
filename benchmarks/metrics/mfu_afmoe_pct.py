"""Model FLOP/s utilisation of an AFMoE configuration: the operations a
token needs (``flops_afmoe.py``: the layers' matmuls, the routers, the held
experts at the expected picks, the shared expert, the head over the held
vocabulary, attention over the pairs each layer's mask keeps, nothing
recomputed) times the tokens a second of the blocks the profiler did not
share, over chips times the peak of ``peaks.json``."""

import flops_afmoe as flops
import runlog

UNIT = "%"


def read(run):
    tokens_per_s = runlog.mean_tokens_per_s(run)
    if tokens_per_s is None or run["peak"] is None:
        return None
    need = flops.train_flops_per_token(run["config"], run["config"]["seq"])
    peak = run["cell"]["chips"] * run["peak"]["bf16_flops_per_s"]
    return 100.0 * need * tokens_per_s / peak
