"""Dry runner: compile + time one real train step for a candidate strategy.

Reference parity: ``atorch/auto/dry_runner/dry_runner.py`` — profiling dry
runs that ground the strategy search in measured numbers.
"""

import time
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from dlrover_tpu.common.log import logger


@dataclass
class DryRunResult:
    ok: bool
    step_time_s: float = float("inf")
    compile_time_s: float = 0.0
    error: str = ""


class DryRunner:
    def __init__(self, warmup: int = 1, iters: int = 3):
        self._warmup = warmup
        self._iters = iters

    def profile(self, context, strategy=None) -> DryRunResult:
        """Finalize the context and time the jitted step on real devices."""
        try:
            t0 = time.perf_counter()
            result = context.finalize(strategy)
            batch = jax.device_put(
                context.sample_batch, result.batch_sharding
            )
            state, metrics = result.train_step(result.state, batch)
            float(metrics["loss"])  # the fetch waits for the step
            compile_time = time.perf_counter() - t0

            for _ in range(self._warmup - 1):
                state, metrics = result.train_step(state, batch)
            float(metrics["loss"])
            t1 = time.perf_counter()
            for _ in range(self._iters):
                state, metrics = result.train_step(state, batch)
            float(metrics["loss"])
            dt = (time.perf_counter() - t1) / self._iters
            return DryRunResult(
                ok=True, step_time_s=dt, compile_time_s=compile_time
            )
        except Exception as e:  # noqa: BLE001 — infeasible candidates OOM/fail
            logger.info("dry run failed: %s", str(e)[:200])
            return DryRunResult(ok=False, error=str(e)[:500])
