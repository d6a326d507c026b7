"""The training worker of the ``train_job`` driver (run under ``tpurun``).

One script, three courses, chosen by the traffic's parameters and by
``DLROVER_RESTART_COUNT`` (which incarnation of the job's worker this is):

* steps (``steady``, ``saving``): build the sharded state, hold the
  reference against step 1, warm up, then dispatch steps for ``seconds``
  seconds, fetching the loss every ``log_every`` steps and, with
  ``save_every``, calling ``save_checkpoint(..., StorageType.MEMORY)``;
* to be killed (``kill``, first incarnation): train ``setup_steps`` steps
  fetching every loss, save step ``save_at`` with ``block=True``, report,
  and wait for the SIGKILL;
* resumed (``kill``, later incarnation): restore and train
  ``resume_steps`` steps.

Everything it learns goes, one JSON object a line, to the events file the
parent named; the parent never imports JAX and judges from that file.  A
standby parks in ``standby_barrier()`` before its first backend touch.
With ``trace`` the worker, which alone can trace the chip it holds, runs
the profiler for a few seconds of the window and reduces the trace itself.
"""

import importlib
import importlib.util
import json
import logging
import os
import re
import sys
import time

_T_START = time.time()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
for _p in (CHECKOUT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from workers.batches import Prefetcher, host_batch  # noqa: E402

with open(os.environ["BENCH_SPEC"]) as _f:
    SPEC = json.load(_f)
EVENTS = SPEC["events"]


def emit(ev, **kw):
    kw.update(ev=ev, pid=os.getpid())
    kw.setdefault("t", time.time())
    with open(EVENTS, "a") as f:
        f.write(json.dumps(kw) + "\n")


def load_object(spec):
    """``package.module:name`` -> the object."""
    module, name = spec.split(":")
    return getattr(importlib.import_module(module), name)


def load_file(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class _StagedLines(logging.Handler):
    """The engine says when shared memory holds a step only in a log line
    ("step N staged to shm (drain Xs, memcpy Ys, ...)"), and that it skipped
    a save because a drain was in flight only in another; a record's own
    time is when its thread wrote it."""

    _STAGED = re.compile(
        r"step (\d+) staged to shm \(drain ([0-9.]+)s, memcpy ([0-9.]+)s"
    )
    _SKIPPED = re.compile(r"step (\d+) memory save skipped")

    def emit(self, record):
        text = record.getMessage()
        m = self._STAGED.search(text)
        if m:
            emit("staged", t=record.created, step=int(m.group(1)),
                 drain_s=float(m.group(2)), memcpy_s=float(m.group(3)))
        m = self._SKIPPED.search(text)
        if m:
            emit("skipped", t=record.created, step=int(m.group(1)))


class Job:
    """What every course needs: the device, the model, the sharded state,
    the step, the checkpointer where the traffic saves, and the batches."""

    def __init__(self):
        import jax
        import optax

        from dlrover_tpu.agent.standby import standby_barrier
        from dlrover_tpu.common.platform import virtual_cpu_devices
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.parallel.sharding import PRESET_RULES
        from dlrover_tpu.trainer.step import (
            create_sharded_state,
            make_train_step,
        )

        self.jax = jax
        self.cache = cache = {"hits": 0, "misses": 0, "compiles": 0}

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                cache["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                cache["misses"] += 1

        def on_duration(event, _seconds, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cache["compiles"] += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

        # No backend touch above this line: a standby parks here while the
        # active worker holds the chip.
        if standby_barrier() is not None:
            emit("activated")
        # The agent's count of this worker's incarnations (a promoted
        # standby gets it with its activation).
        restart = int(os.environ.get("DLROVER_RESTART_COUNT", "0"))
        chips = int(SPEC["chips"])
        self.params = params = SPEC["params"]
        self.cfg = cfg = SPEC["config"]
        self.seed = seed = int(SPEC["seed"])
        self.tracing = bool(SPEC["trace"])
        virtual_cpu_devices(chips)  # the CPU rehearsal's mesh; no-op on a TPU
        self.devices = devices = jax.devices()
        emit("device", platform=devices[0].platform,
             kind=devices[0].device_kind, count=len(devices),
             cache_dir=jax.config.jax_compilation_cache_dir, restart=restart)
        if len(devices) != chips:
            raise RuntimeError(f"need {chips} devices, JAX reports {devices}")

        self.trace_dir = os.path.join(SPEC["workdir"], "trace")
        self.resumed = bool(params["kill"]) and restart > 0
        if self.tracing and self.resumed:
            # The whole resume is the window: trace from the moment the
            # chip is ours; what came before counts as idle.
            self.t_trace = self.start_trace()

        model_cfg = load_object(cfg["model"]["config_class"])(
            **{ours: cfg[theirs]
               for ours, theirs in cfg["model"]["from_source"].items()},
            **cfg["model"]["kwargs"],
        )
        model = load_object(cfg["model"]["class"])(model_cfg)
        self.batch_shape = (cfg["batch"], cfg["seq"], cfg["vocab_size"])
        mesh = build_mesh(MeshConfig(**cfg["mesh"]), devices)
        rules = PRESET_RULES[cfg["rules"]]
        opt = cfg["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"no optimizer {opt['name']!r}")
        t0 = time.time()
        self.state, self.shardings = create_sharded_state(
            model, optax.adamw(opt["learning_rate"], b2=opt["b2"]), mesh,
            rules, jax.random.key(seed),
            host_batch(seed, 1, *self.batch_shape),
        )
        jax.block_until_ready(self.state)
        self.step_fn = make_train_step(model, mesh, rules, self.shardings)
        leaves = jax.tree.leaves
        big = max(leaves(self.state.params), key=lambda x: x.size)
        emit("state", seconds=time.time() - t0,
             n_params=int(sum(x.size for x in leaves(self.state.params))),
             state_bytes=int(sum(x.nbytes for x in leaves(_view(self.state)))),
             # device ids that hold the shards of the largest parameter
             shard_devices=sorted(
                 {s.device.id for s in big.addressable_shards}))

        self.ckpt = None
        if params["save_every"] or params["kill"]:
            from dlrover_tpu.checkpoint import Checkpointer
            from dlrover_tpu.common.log import logger as program_logger

            program_logger.addHandler(_StagedLines())
            self.ckpt = Checkpointer(os.path.join(SPEC["workdir"], "ckpt"))
        self.step_no = 0
        self.annotate = jax.profiler.TraceAnnotation

    # -- the pieces ---------------------------------------------------------

    def start_data(self):
        self.data = Prefetcher(
            self.seed, self.step_no + 1, *self.batch_shape)

    def dispatch(self):
        self.step_no += 1
        with self.annotate("bench/data", step=self.step_no):
            batch = self.data.get(self.step_no)
        with self.annotate("bench/dispatch", step=self.step_no):
            self.state, metrics = self.step_fn(self.state, batch)
        return metrics["loss"]

    def fetch(self, loss, **kw):
        with self.annotate("bench/fetch", step=self.step_no):
            value = float(loss)
        emit("fetch", step=self.step_no, loss=value, **kw)
        return value

    def save(self, block=False, **kw):
        from dlrover_tpu.checkpoint import StorageType

        t0 = time.time()
        with self.annotate("bench/save", step=self.step_no):
            ok = self.ckpt.save_checkpoint(
                self.step_no, _view(self.state, self.params["save_view"]),
                StorageType.MEMORY, block=block,
            )
        emit("save", step=self.step_no, t_call=t0, seconds=time.time() - t0,
             accepted=bool(ok), blocking=block, **kw)
        if block and not ok:
            raise RuntimeError(f"shm save at step {self.step_no} failed")

    def start_trace(self):
        options = self.jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the host's stacks are not read
        options.host_tracer_level = 2    # the worker's own annotations are
        t = time.time()
        self.jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        return t

    def reduce_trace(self, t_start, t_stop, step_from, step_to):
        """Trace -> ``reduced.json``, here, where the trace is (the
        parent's metric readers take the numbers from the reduced file)."""
        import glob

        from reduce import xplane

        t0 = time.time()
        paths = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        out = os.path.join(SPEC["workdir"], "reduced.json")
        with open(out, "w") as f:
            json.dump(xplane.reduce_file(paths[-1]) if paths else None, f)
        emit("trace", t_start=t_start, t_stop=t_stop, step_from=step_from,
             step_to=step_to, xplane=paths[-1] if paths else None,
             reduced=out, reduce_s=time.time() - t0)

    def report_end(self, at_open):
        from dlrover_tpu.telemetry import metrics as tmetrics

        fallback = tmetrics.REGISTRY.get("dlrover_attention_fallback_total")
        stats = [d.memory_stats() or {} for d in self.devices]
        peaks = [s["peak_bytes_in_use"] for s in stats
                 if s.get("peak_bytes_in_use") is not None]
        emit(
            "end",
            peak_bytes=max(peaks, default=None),
            bytes_limit=stats[0].get("bytes_limit"),
            attention_fallbacks={
                dict(key).get("reason", ""): v
                for _name, key, v in (fallback.samples() if fallback else [])
            },
            cache_in_window={k: v - at_open[k] for k, v in self.cache.items()},
            cache=dict(self.cache),
        )

    def finish(self):
        self.data.close()
        if self.ckpt is not None:
            self.ckpt.close()

    # -- the courses --------------------------------------------------------

    def resume(self):
        params, view = self.params, self.params["save_view"]
        t0 = time.time()
        with self.annotate("bench/restore"):
            restored_step, restored = self.ckpt.load_checkpoint(
                _view(self.state, view), _view(self.shardings, view)
            )
        if restored_step is None:
            raise RuntimeError("nothing to restore in shm")
        self.state = self.state.replace(**restored)
        del restored
        emit("restored", step=int(restored_step), seconds=time.time() - t0)
        self.step_no = int(restored_step)
        self.start_data()
        for _ in range(params["resume_steps"]):
            hits = self.cache["hits"]
            loss = self.dispatch()
            self.fetch(loss, resumed=True,
                       step_cache_hits=self.cache["hits"] - hits)
        if self.tracing:
            self.jax.profiler.stop_trace()
            self.reduce_trace(self.t_trace, time.time(),
                              self.step_no - params["resume_steps"],
                              self.step_no)
        self.report_end(dict.fromkeys(self.cache, 0))
        self.finish()

    def first_step(self):
        """The reference on step 1's batch, before step 1 donates the
        parameters it reads; then step 1, which compiles or loads."""
        t0 = time.time()
        ref_loss = _reference_loss(
            self.jax, self.cfg, self.state.params,
            host_batch(self.seed, 1, *self.batch_shape))
        emit("reference", loss=ref_loss, seconds=time.time() - t0)
        self.start_data()
        t0 = time.time()
        first = self.fetch(self.dispatch(), warm=True)
        emit("compiled", seconds=time.time() - t0, loss=first,
             cache=dict(self.cache))

    def train_until_killed(self):
        params = self.params
        self.first_step()
        for _ in range(params["setup_steps"] - 1):
            self.fetch(self.dispatch(), warm=True)
            if self.step_no == params["save_at"]:
                self.save(block=True)
        self.report_end(dict(self.cache))
        emit("ready_to_die", step=self.step_no)
        while True:  # the parent SIGKILLs this process now
            time.sleep(1)

    def train_for_window(self):
        params, jax = self.params, self.jax
        self.first_step()
        for _ in range(params["warmup_steps"] - 1):
            self.fetch(self.dispatch(), warm=True)
        if params["save_every"]:
            # Sizes the shm block and compiles the snapshot copy, outside
            # the window; the next save then finds the block there.
            self.save(block=True, warm=True)

        seconds, log_every = float(SPEC["seconds"]), params["log_every"]
        at_open = dict(self.cache)
        t_open, open_step = time.time(), self.step_no
        emit("window_open", t=t_open, step=open_step)
        if params["save_every"]:
            self.save()
        trace_at = t_open + max(seconds - params["trace_seconds"], 0.0) / 2
        t_trace = trace_from = traced = None
        shared = False
        while True:
            t_block = time.time()
            for _ in range(log_every):
                loss = self.dispatch()
            # A block that shares its time with the profiler (running, or
            # stopping just before it) says so: the host-clock metrics of
            # a traced run leave it out.
            self.fetch(loss, traced=shared or t_trace is not None)
            shared = False
            block_s = time.time() - t_block
            if t_trace is not None and (
                time.time() - t_trace >= params["trace_seconds"]
            ):
                jax.profiler.stop_trace()
                traced = (t_trace, time.time(), trace_from, self.step_no)
                t_trace, shared = None, True
            now = time.time()
            if t_trace is None and now + block_s > t_open + seconds:
                break
            if params["save_every"] and (
                (self.step_no - open_step) % params["save_every"] == 0
            ):
                self.save()
            if (self.tracing and traced is None and t_trace is None
                    and now >= trace_at):
                t_trace, trace_from = self.start_trace(), self.step_no
        emit("window_close", step=self.step_no)
        self.report_end(at_open)
        if traced:
            self.reduce_trace(*traced)
        if self.ckpt is not None:
            # A drain is still in flight; how it ends belongs to the result.
            emit("staging_done", ok=bool(self.ckpt.wait_staging()))
        self.finish()


def main():
    emit("start", t_start=_T_START,
         standby=bool(os.environ.get("DLROVER_STANDBY_FIFO")))
    job = Job()
    if job.resumed:
        job.resume()
    elif job.params["kill"]:
        job.train_until_killed()
    else:
        job.train_for_window()


def _view(s, fields=("params", "opt_state", "step")):
    """Arrays only: TrainState's apply_fn and tx are code, rebuilt here."""
    return {name: getattr(s, name) for name in fields}


def _reference_loss(jax, cfg, params, batch):
    """Mean token loss of the whole batch under the plain reference, row
    by row on the program's own parameter tree (under its shardings)."""
    ref = load_file(os.path.join(CHECKOUT, cfg["reference"]), "bench_ref")
    loss_of_row = jax.jit(lambda p, ids, labels: ref.loss_of_row(
        cfg, p, ids, labels))
    total = 0.0
    for ids, labels in zip(batch["input_ids"], batch["labels"]):
        total += float(loss_of_row(params, ids, labels))
    return total / batch["labels"].size


if __name__ == "__main__":
    main()
