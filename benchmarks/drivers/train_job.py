"""Driver ``train_job``: one elastic training job under ``tpurun``.

This process runs ``launch.elastic_run.main`` in-process (the agent lives
here, as in ``chip_smoke.py``) and never imports JAX: a chip belongs to one
process, and the workers need it.  The agent starts
``workers/train_worker.py`` through ``launch.worker`` with a hot standby
beside it; what the workers learn comes back through an events file.  A
watcher thread SIGKILLs the active worker where the traffic says so, and
ends the job's processes when a deadline passes.

``run(spec)`` returns the run that ``runlog`` and the metric readers read,
with the driver's verdicts under ``problems`` (an empty list is
``correct``), ``attempted``, ``failed`` and ``device``.
"""

import glob
import json
import math
import os
import signal
import threading
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(BENCH, "workers", "train_worker.py")

# The mean loss of a step's thousands of tokens, computed in bf16 with the
# kernels, against the plain float32 forward.  bf16 keeps 8 significant
# bits and chip_smoke.py allows 2^-7 between two programs' losses; the mean
# averages the rounding out (1e-5 relative was observed at step 1, PERF.md),
# so this is eight times tighter: a head or a softmax in lower precision
# than the configuration states would not pass.
BF16_TOL = 2.0 ** -10
# A restore runs the same program on the same bits and the same batches:
# room for nothing but a reordered sum.
RESUME_TOL = 1e-5
# No run may outlast this, whatever stalls (the first run of a cell in a
# checkout, which compiles, is allowed 1200 s).
STALL_S = 1100.0


def children():
    """Pids whose parent is this process (Linux /proc)."""
    me, out = os.getpid(), []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        if int(fields[1]) == me and fields[0] != "Z":
            out.append(int(name))
    return out


def kill_children():
    for pid in children():
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def read_events(path):
    events = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError:
                    pass  # a line torn by the kill
    except OSError:
        pass
    return events


def close_enough(a, b, tol):
    """Relative only: a floor on the scale would pass any two small losses."""
    return abs(a - b) <= tol * max(abs(a), abs(b))


def run(spec, log):
    """``spec``: workdir, cell, config, params, seed, seconds, trace, t0."""
    from dlrover_tpu.launch import elastic_run

    workdir, params = spec["workdir"], spec["params"]
    events_path = os.path.join(workdir, "events.jsonl")
    open(events_path, "w").close()
    spec_path = os.path.join(workdir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(dict(spec, events=events_path, chips=spec["cell"]["chips"]), f)
    os.environ["BENCH_SPEC"] = spec_path
    os.environ["DLROVER_TELEMETRY_DIR"] = os.path.join(workdir, "telemetry")
    os.environ.pop("DLROVER_MASTER_ADDR", None)

    state = {"t_kill": None, "killed_pid": None, "timed_out": False}
    deadline_s = params.get("deadline_factor", 0) * spec["seconds"]
    job_done = threading.Event()
    stall_at = spec["t0"] + STALL_S

    def watch():
        while not job_done.wait(0.05):
            now = time.time()
            resume_late = state["t_kill"] and now > state["t_kill"] + deadline_s
            if now > stall_at or resume_late:
                if not state["timed_out"]:
                    log("deadline passed; ending the job's processes")
                state["timed_out"] = True
                kill_children()
                continue
            if not params["kill"] or state["t_kill"]:
                continue
            ready = [e for e in read_events(events_path)
                     if e["ev"] == "ready_to_die"]
            if ready:
                pid = ready[0]["pid"]
                state.update(t_kill=time.time(), killed_pid=pid)
                os.kill(pid, signal.SIGKILL)
                log(f"SIGKILLed the active worker (pid {pid})")

    watcher = threading.Thread(target=watch, name="watcher", daemon=True)
    watcher.start()
    try:
        rc = elastic_run.main([
            "--nnodes", "1",
            "--nproc_per_node", "1",
            "--accelerator", "tpu",
            "--hot-standby",
            "--max-restarts", "1" if params["kill"] else "0",
            "--monitor-interval", "0.25",
            "--log-dir", os.path.join(workdir, "logs"),
            WORKER,
        ])
    finally:
        job_done.set()
        watcher.join(timeout=10)
        # The agent (this process) owns the shm block: give it back.
        from dlrover_tpu.checkpoint.ckpt_saver import AsyncCheckpointSaver

        AsyncCheckpointSaver.reset()
        # ...which unlinks only a block the agent itself attached.  After
        # memory-only saves it never did, and the worker's block (GBs of
        # host memory) would outlive the run: every run takes its own away.
        uid = os.environ.pop("DLROVER_JOB_UID", None)
        for path in glob.glob(f"/dev/shm/dlrover_tpu_ckpt_{uid}_*"):
            os.unlink(path)
    left = children()
    kill_children()

    out = dict(spec, events=read_events(events_path), reduced=None,
               t_kill=state["t_kill"], deadline_s=deadline_s, job_uid=uid)
    trace = next((e for e in out["events"] if e["ev"] == "trace"), None)
    if trace is not None:
        with open(trace["reduced"]) as f:
            out["reduced"] = json.load(f)
    problems = judge(out, rc, state)
    if left:
        problems.append(f"processes left running: {left}")
    out["problems"] = problems
    return out


def judge(run, rc, state):
    """Everything that makes a run not ``correct``; also fills ``device``,
    ``attempted`` and ``failed``."""
    import runlog

    cell, params, problems = run["cell"], run["params"], []
    devices = runlog.of(run, "device")
    run["device"] = None
    run["attempted"], run["failed"] = 0, 0
    if not devices:
        return ["no worker reached the device (see the logs)"]
    ends = runlog.of(run, "end")
    peaks = [e["peak_bytes"] for e in ends if e.get("peak_bytes") is not None]
    run["device"] = {
        "platform": devices[0]["platform"],
        "kind": devices[0]["kind"],
        "count": devices[0]["count"],
        "memory_peak_bytes": max(peaks) if peaks else None,
    }
    if rc != 0:
        problems.append(f"tpurun exited {rc}")
    if state["timed_out"]:
        problems.append("a deadline passed")
    for d in devices:
        if d["platform"] != "tpu" or d["count"] != cell["chips"]:
            problems.append(
                f"worker {d['pid']} ran on {d['count']} x {d['platform']}, "
                f"not {cell['chips']} x tpu")

    reference, compiled = runlog.first(run, "reference"), runlog.first(
        run, "compiled")
    if reference is None or compiled is None:
        problems.append("no step 1 to hold against the reference")
    elif not close_enough(compiled["loss"], reference["loss"], BF16_TOL):
        problems.append(
            f"step 1 loss {compiled['loss']} != reference {reference['loss']}")
    fetches = runlog.of(run, "fetch")
    bad = [e for e in fetches if not math.isfinite(e["loss"])]
    if bad:
        problems.append(f"{len(bad)} fetched losses are not finite")
    for e in ends:
        if any(e["attention_fallbacks"].values()):
            problems.append(f"attention fell back: {e['attention_fallbacks']}")
    for e in runlog.of(run, "state"):
        if len(e["shard_devices"]) != cell["chips"]:
            problems.append(
                f"largest parameter on devices {e['shard_devices']}")

    if params["kill"]:
        run["attempted"] = 1
        problems += judge_resume(run, state)
        run["failed"] = 1 if problems else 0
        return problems

    opened, closed = runlog.window_open(run), runlog.first(run, "window_close")
    if opened is None or closed is None or not ends:
        return problems + ["the window never closed"]
    in_window = ends[-1]["cache_in_window"]
    if in_window["misses"] or in_window["compiles"]:
        problems.append(f"compiled inside the window: {in_window}")
    saves = runlog.window_saves(run)
    # A save the engine refused for another reason than a drain in flight
    # reports a staging that failed; so does the last drain, waited for.
    refused = [s for s in runlog.of(run, "save", accepted=False)
               if s["t_call"] >= opened["t"]]
    skipped = [e for e in runlog.of(run, "skipped") if e["t"] >= opened["t"]]
    staging = runlog.first(run, "staging_done")
    stager_failures = max(len(refused) - len(skipped), 0) + (
        1 if staging is not None and not staging["ok"] else 0)
    if params["save_every"] and not runlog.whole_saves(run):
        problems.append("no whole accepted save inside the window")
    run["attempted"] = closed["step"] - opened["step"] + len(saves)
    run["failed"] = len(
        [e for e in bad if e["t"] > opened["t"]]
    ) * params["log_every"] + stager_failures
    if run["failed"]:
        problems.append(f"{run['failed']} operations failed")
    if len(runlog.window_fetches(run)) < 2:
        problems.append("fewer than two loss fetches inside the window")
    return problems


def judge_resume(run, state):
    import runlog

    params, problems = run["params"], []
    if state["t_kill"] is None:
        return ["no worker got as far as being killed"]
    resumed = runlog.resumed_fetches(run)
    restored = runlog.first(run, "restored")
    if restored is None or len(resumed) < params["resume_steps"]:
        return ["no process resumed after the SIGKILL in time"]
    if resumed[0]["pid"] == state["killed_pid"]:
        problems.append("the killed pid resumed?")
    if restored["step"] != params["save_at"]:
        problems.append(
            f"restored step {restored['step']}, saved {params['save_at']}")
    before = {e["step"]: e["loss"] for e in runlog.of(run, "fetch")
              if e["pid"] == state["killed_pid"]}
    for e in resumed:
        want = before.get(e["step"])
        if want is None or not close_enough(e["loss"], want, RESUME_TOL):
            problems.append(
                f"step {e['step']}: loss {e['loss']} after the resume, "
                f"{want} before the kill")
    if resumed[0]["step_cache_hits"] < 1:
        problems.append("the resumed step was not served from the cache")
    return problems


def device_window(run):
    """``(busy_s, window_s)`` for the result's ``device``: operation time
    from the trace, averaged over the devices, and the traced window.
    After a kill the window is the whole resume: the seconds before the
    resumed process owned the chip and could trace count as idle."""
    from reduce import xplane

    if not run["reduced"] or not run["reduced"]["devices"]:
        return None, None
    busy = xplane.mean_over_devices(run["reduced"], "busy_s")
    if run["t_kill"] is not None:
        import runlog

        last = runlog.resumed_fetches(run)[-1]["t"]
        return busy, last - run["t_kill"]
    return busy, xplane.mean_over_devices(run["reduced"], "span_s")
