"""The training traffic: batches drawn on the host from ``(seed, step)``.

Every process of a job draws the same batch for the same step, so a
resumed process continues the data order of the one that was killed, and
no batch is seen twice, so nothing is memorised.  Uniform token ids, dense
causal rows (no padding, no packing).
"""

import queue
import threading

import numpy as np


def host_batch(seed, step, rows, seq, vocab):
    """``rows`` rows of ``seq`` + 1 ids; inputs are all but the last id of
    a row, labels all but the first."""
    ids = np.random.default_rng([seed, step]).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32
    )
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


class Prefetcher:
    """Draws the batch of step n + 1 on one thread while step n is being
    dispatched.  ``get(step)`` must be called with consecutive steps."""

    def __init__(self, seed, first_step, rows, seq, vocab):
        self._args = (seed, rows, seq, vocab)
        self._queue = queue.Queue(maxsize=1)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(first_step,), name="bench-data",
            daemon=True,
        )
        self._thread.start()

    def _run(self, step):
        while not self._stop.is_set():
            seed, *shape = self._args
            item = (step, host_batch(seed, step, *shape))
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def get(self, step):
        got, batch = self._queue.get()
        if got != step:
            raise RuntimeError(f"asked for step {step}, drew step {got}")
        return batch

    def close(self):
        self._stop.set()
        self._thread.join(timeout=5)
