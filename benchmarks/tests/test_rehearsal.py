"""Each course of the ``train_job`` driver, end to end on the CPU, on the
tiny cells that live only here (``cells/``: a configuration, traffic mixes,
cells and one per-layer metric, all added as files alone).  The flow is
the chip's; the verdict is not: off a TPU a run prints no result and exits
non-zero, and what it would have printed says ``correct: false``."""

import glob
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
# What a CPU run is allowed to get wrong, and nothing else.
EXPECTED = ("x cpu, not", "attention fell back",
            "no operation ran on the device")


def rehearse(workload, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", workload,
         "--seed", "3", "--seconds", "3", "--trace", str(trace),
         "--cells", os.path.join("benchmarks", "tests", "cells")],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300,
    )
    path = os.path.join(CHECKOUT, ".bench_runs", workload, "result.json")
    assert os.path.exists(path), proc.stderr[-3000:]
    with open(path) as f:
        return proc, json.load(f)


@pytest.mark.parametrize("workload, trace, chips, metrics", [
    ("tiny.steady", 0, 1, {"train_tokens_per_s", "setup_s"}),
    ("tiny.steady", 1, 1, {"compile_misses", "step_ms_p50",
                           "window_tokens_per_s", "fetches_in_window"}),
    ("tiny.saving", 0, 1, {"save_s", "setup_s"}),
    ("tiny.saving", 1, 1, {"ckpt_dispatch_ms", "ckpt_drain_s",
                           "ckpt_memcpy_s", "saves_skipped",
                           "saving_tokens_per_s"}),
    ("tiny.preempt", 0, 1, {"resume_s", "setup_s"}),
    ("tiny.preempt", 1, 1, {"promote_s", "backend_init_s", "state_init_s",
                            "restore_s", "first_step_s"}),
    ("tiny4.steady", 0, 4, {"train_tokens_per_s", "setup_s"}),
])
def test_rehearsal(workload, trace, chips, metrics):
    proc, result = rehearse(workload, trace)
    # the agent leaves a memory-only job's shm block behind; the run may not
    assert result["job_uid"]
    assert not glob.glob(f"/dev/shm/dlrover_tpu_ckpt_{result['job_uid']}_*")
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", "no result may be printed off a TPU"
    assert "no TPU" in proc.stderr or "not measured" in proc.stderr
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    unexpected = [p for p in result["problems"]
                  if not any(e in p for e in EXPECTED)]
    assert not unexpected, unexpected
    assert metrics <= set(result["metrics"]), result["metrics"]
    assert all(m["value"] is not None for m in result["metrics"].values())
    if "preempt" in workload:
        assert result["attempted"] == 1
    else:
        assert result["attempted"] > 0
    if "saving" in workload:
        # the save comes round every ``save_every`` steps of the window,
        # not only at its opening
        with open(os.path.join(CHECKOUT, ".bench_runs", workload,
                               "events.jsonl")) as f:
            events = [json.loads(line) for line in f]
        saves = [e for e in events if e["ev"] == "save" and not e.get("warm")]
        assert len(saves) > 3
        assert len({e["step"] for e in saves}) == len(saves)
