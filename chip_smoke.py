"""The quickest proof that the system still starts on the chip.

Drives the product's main path once, through the entry points a user calls,
at ``LlamaConfig.llama2_7b`` widths (depth is the only cut; weights are
random, from a seed):

* ``train`` + ``resume`` — one ``tpurun`` job (``launch.elastic_run`` →
  agent → ``launch.worker`` → ``scripts/chip_smoke_worker.py``): steps of
  ``make_train_step`` with splash attention, a Flash Checkpoint to shared
  memory, SIGKILL of the active worker, the agent promotes the standby
  parked before its first device touch (or respawns), the new process
  takes the chip, restores and reproduces the loss the killed one logged;
* ``serve`` — one ``ProcessReplica`` behind ``InferenceGateway``, four
  ``/generate`` requests, each completion scored in the replica against
  the plain forward of the same weights;
* ``--chips 4`` runs only the sharded path instead: the same ``tpurun`` job
  with one worker driving an fsdp=2 x tp=2 mesh, held against the same
  seed and batch on one device.

This process never imports JAX — a chip belongs to one process, and the
workers need it.  What it prints about the device is what the worker that
held the chip reported.  The last line of stdout is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit code is
0 only when every phase passed on a TPU.  Off the TPU the same flow runs
(``--tiny`` makes it small enough for a test) and ends ``"ok": false``.
"""

import argparse
import json
import math
import os
import random
import shutil
import signal
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

WORKER = os.path.join(REPO, "scripts", "chip_smoke_worker.py")
SEED = 0
# bf16 keeps 8 significant bits: two values that agree "to bf16 tolerance"
# differ by at most a couple of units in the last of them.  It is what a
# different program over the same numbers may cost (sharded against one
# device, paged decode against the plain forward).
BF16_TOL = 2.0 ** -7
# A restore runs the SAME program on the same bits, so its losses are the
# killed incarnation's; this leaves room for nothing but a reordered sum.
RESUME_TOL = 1e-5
# The whole run has 1200 s; leave room to report and clean up.
BUDGET_S = 1100.0

# Depth from ``memory_analysis()`` of the whole step compiled for a v5e
# chip (tests/test_chip_compile.py keeps the one-chip check): f32 params +
# AdamW at b4 x s2048, arguments + temporaries + the checkpoint's device
# snapshot.  One chip, depth 1: 5.19 + 2.35 + 5.19 = 12.7 GiB of 16 (depth
# 2 would need 18.1).  Four chips, depth 2: 1.86 + 2.37 + 1.86 GiB per
# chip, and its one-device comparison 7.45 + 3.17 GiB on the first.
_TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
             num_heads=4, num_kv_heads=2)
# (chips, tiny) -> what the worker builds; ``widths`` override llama2_7b's.
# ``steps`` is ``save_at + 2``: the loss of step k + 1 is taken on the saved
# parameters, that of step k + 2 after an update from the saved moments.
# The one fixed batch is memorised fast; ``lr`` keeps the loss O(1) and
# falling by several percent a step where the restore is compared, so a
# restore of another step's state cannot pass for the right one.
TRAIN_SPECS = {
    (1, False): dict(widths={}, layers=1, seq=2048, batch=4,
                     steps=7, save_at=5, lr=5e-5),
    (4, False): dict(widths={}, layers=2, seq=2048, batch=4,
                     steps=5, save_at=3, lr=5e-5),
    (1, True): dict(widths=_TINY, layers=2, seq=128, batch=4,
                    steps=7, save_at=5, lr=3e-3),
    (4, True): dict(widths=_TINY, layers=2, seq=128, batch=4,
                    steps=5, save_at=3, lr=3e-3),
}

# ``python -m dlrover_tpu.serving`` takes widths as arguments; these are
# LlamaConfig.llama2_7b's (tests/test_chip_smoke.py holds them equal).
SERVE_SPECS = {
    False: dict(worker=dict(vocab=32000, hidden=4096, intermediate=11008,
                            heads=32, kv_heads=32, layers=1, slots=4,
                            max_len=640, block_size=128, seed=SEED),
                prompt=512, gen=32, requests=4),
    True: dict(worker=dict(vocab=256, hidden=64, intermediate=128,
                           heads=4, kv_heads=2, layers=2, slots=4,
                           max_len=64, block_size=16, seed=SEED),
               prompt=32, gen=8, requests=4),
}


def say(msg):
    print(f"[chip_smoke +{time.time() - T0:6.1f}s] {msg}", flush=True)


T0 = time.time()


def remaining():
    return max(BUDGET_S - (time.time() - T0), 1.0)


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


# ---------------------------------------------------------------------------
# train + resume (and, on four chips, the sharded path)
# ---------------------------------------------------------------------------


def run_tpurun_job(workdir, chips, tiny):
    """One ``tpurun`` job in this process (the agent is in-process), a
    watcher thread that SIGKILLs the active worker once it reports, and
    a deadline that ends the job if it stalls.  Returns
    ``(events, t_kill, killed_pid, rc)``."""
    from dlrover_tpu.launch import elastic_run

    os.makedirs(workdir)
    events_path = os.path.join(workdir, "events.jsonl")
    open(events_path, "w").close()
    spec = dict(TRAIN_SPECS[(chips, tiny)], chips=chips, seed=SEED)
    os.environ["CHIP_SMOKE_EVENTS"] = events_path
    os.environ["CHIP_SMOKE_SPEC"] = json.dumps(spec)
    os.environ["CHIP_SMOKE_CKPT_DIR"] = os.path.join(workdir, "ckpt")
    os.environ["DLROVER_TELEMETRY_DIR"] = os.path.join(workdir, "telemetry")
    os.environ.pop("DLROVER_MASTER_ADDR", None)

    killed = {}
    job_done = threading.Event()
    deadline = time.time() + min(remaining() - 60.0, 900.0)

    def watch():
        while not job_done.wait(0.2):
            if time.time() > deadline:
                # Stalled: end every worker until the agent gives up.
                say("deadline passed; killing the job's processes")
                kill_children()
                continue
            if killed:
                continue
            trained = [e for e in read_events(events_path)
                       if e["ev"] == "trained"]
            if trained:
                pid = trained[0]["pid"]
                killed.update(pid=pid, t=time.time())
                os.kill(pid, signal.SIGKILL)
                say(f"SIGKILLed the active worker (pid {pid})")

    watcher = threading.Thread(target=watch, name="watcher", daemon=True)
    watcher.start()
    try:
        rc = elastic_run.main([
            "--nnodes", "1",
            "--nproc_per_node", "1",
            "--accelerator", "tpu",
            "--hot-standby",
            "--max-restarts", "1",
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
    return read_events(events_path), killed.get("t"), killed.get("pid"), rc


def judge_train(spec, chips, events):
    """What the first incarnation reported -> (problems, printable)."""
    problems = []
    device = next((e for e in events if e["ev"] == "device"), None)
    trained = next((e for e in events if e["ev"] == "trained"), None)
    if device is None or trained is None:
        return ["the worker never reported (see the logs)"], {}, device
    if device["platform"] != "tpu":
        problems.append(f"platform is {device['platform']!r}, not 'tpu'")
    if device["count"] != chips:
        problems.append(f"{device['count']} devices, expected {chips}")
    losses = trained["losses"]
    if len(losses) != spec["steps"] or not all(map(math.isfinite, losses)):
        problems.append(f"losses not {spec['steps']} finite values")
    elif not losses[-1] < losses[0]:
        problems.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    if not trained["has_tpu_custom_call"]:
        problems.append("no tpu_custom_call in the compiled step")
    if any(trained["attention_fallbacks"].values()):
        problems.append(
            f"attention fell back: {trained['attention_fallbacks']}"
        )
    out = {
        "layers": spec["layers"],
        "n_params": trained["n_params"],
        "state_bytes": trained["state_bytes"],
        "argument_bytes": trained["argument_bytes"],
        "temp_bytes": trained["temp_bytes"],
        "peak_bytes_in_use": trained["peak_bytes_in_use"],
        "bytes_limit": trained["bytes_limit"],
        "losses": losses,
        "compile_s": round(trained["compile_s"], 2),
        "steady_step_s": round(trained["steady_step_s"], 4),
        "save_s": round(trained["save_s"], 2),
        "cache_dir": device["cache_dir"],
    }
    if chips == 4:
        ref = trained["reference_losses"]
        out.update(
            reference_losses=ref,
            shard_devices=trained["shard_devices"],
            bytes_in_use=trained["bytes_in_use"],
            collectives=trained["collectives"],
        )
        if not all(close_enough(a, b, BF16_TOL)
                   for a, b in zip(losses, ref)):
            problems.append("sharded losses differ from one device's")
        if len(trained["shard_devices"]) != 4:
            problems.append(
                f"largest kernel on devices {trained['shard_devices']}"
            )
        used = trained["bytes_in_use"]
        if None in used or max(used) > 2 * min(used):
            problems.append(f"bytes_in_use not balanced: {used}")
        if not trained["collectives"]:
            problems.append("no collective in the compiled step")
    return problems, out, device


def judge_resume(spec, chips, events, t_kill, killed_pid, rc):
    problems = []
    trained = next((e for e in events if e["ev"] == "trained"), None)
    resumed = next((e for e in events if e["ev"] == "resumed"), None)
    if t_kill is None or trained is None:
        return ["no worker got as far as being killed"], {}
    if resumed is None:
        return [f"no process resumed after the SIGKILL (tpurun rc {rc})"], {}
    if rc != 0:
        problems.append(f"tpurun exited {rc}")
    if resumed["pid"] == killed_pid:
        problems.append("the killed pid resumed?")
    save_at = spec["save_at"]
    if resumed["restored_step"] != save_at:
        problems.append(
            f"restored step {resumed['restored_step']}, saved {save_at}"
        )
    # The killed incarnation saved after step k and logged the losses of
    # steps k + 1 (taken on the saved parameters) and k + 2 (after an
    # update from the saved moments) before it died.
    before = trained["losses"][save_at - 1:]
    want, got = before[1:], resumed["losses"]
    if len(got) != 2 or not all(
        close_enough(a, b, RESUME_TOL) for a, b in zip(got, want)
    ):
        problems.append(
            f"losses after resume {got} != {want} before the kill"
        )
    # The comparison guards something only while neighbouring steps are
    # told apart by far more than it allows.
    if any(close_enough(a, b, 4 * BF16_TOL)
           for a, b in zip(before, before[1:])):
        problems.append(
            f"losses {before} around the save are too close to tell a "
            f"restore of the wrong step"
        )
    if resumed["step_cache_hits"] < 1:
        problems.append("the resumed step was not served from the cache")
    if chips == 4 and len(resumed["shard_devices"]) != 4:
        problems.append(
            f"restored kernel on devices {resumed['shard_devices']}"
        )
    via_standby = any(
        e["ev"] == "activated" and e["pid"] == resumed["pid"] for e in events
    )
    return problems, {
        "via": "standby promotion" if via_standby else "respawn",
        "restored_step": resumed["restored_step"],
        "losses_before_kill": want,
        "losses_after_resume": got,
        "kill_to_first_step_s": round(resumed["t_first_step"] - t_kill, 2),
        "restore_s": round(resumed["restore_s"], 2),
        "compile_s_first": round(trained["compile_s"], 2),
        "compile_s_resumed": round(resumed["first_step_s"], 2),
        "cache_hits_resumed": resumed["cache"]["hits"],
        "cache_misses_resumed": resumed["cache"]["misses"],
    }


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def run_serve(workdir, tiny):
    from dlrover_tpu.serving.gateway import InferenceGateway, ProcessReplica
    from dlrover_tpu.telemetry.httpd import TelemetryHTTPServer

    spec = SERVE_SPECS[tiny]
    os.makedirs(workdir)
    replicas, spawn_errors = [], []

    def factory():
        t0 = time.time()
        try:
            replica = ProcessReplica(
                workdir, worker_args=spec["worker"],
                # A cold chip: process start, eager init at full width and
                # four tick compiles come before the ready file.
                spawn_timeout_s=min(remaining() - 120.0, 600.0),
                rpc_timeout_s=120.0,
            )
        except Exception as e:
            spawn_errors.append(repr(e))
            raise
        replica.ready_s = time.time() - t0
        replicas.append(replica)
        return replica

    gw = InferenceGateway(
        factory, n_replicas=1, spawn_attempts=1,
        default_gen_budget=spec["gen"],
    )
    http = TelemetryHTTPServer(
        serve_sources=gw.http_sources(), host="127.0.0.1", port=0
    )
    problems, out = [], {}
    try:
        gw.start()
        addr = http.start()
        while not replicas and not spawn_errors and remaining() > 60.0:
            time.sleep(0.2)
        if not replicas:
            return [f"no replica came up: {spawn_errors or 'timeout'}"], \
                out, None
        replica = replicas[0]
        device = replica.device
        out["ready_s"] = round(replica.ready_s, 2)
        if device["platform"] != "tpu":
            problems.append(
                f"replica serves from {device['platform']!r}, not 'tpu'"
            )
        rng = random.Random(SEED)
        vocab = spec["worker"]["vocab"]
        prompts = [
            [rng.randrange(vocab) for _ in range(spec["prompt"])]
            for _ in range(spec["requests"])
        ]
        results = [None] * len(prompts)

        def ask(i):
            url = (
                f"http://{addr}/generate?prompt="
                + ",".join(map(str, prompts[i]))
                + f"&budget={spec['gen']}&timeout={remaining() - 60.0:.0f}"
            )
            with urllib.request.urlopen(url, timeout=remaining()) as resp:
                results[i] = json.loads(resp.read())

        t0 = time.time()
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=remaining())
        out["requests_s"] = round(time.time() - t0, 2)
        worst = 0.0
        for i, res in enumerate(results):
            if not (res and res.get("ok")):
                problems.append(f"request {i} failed: {res}")
                continue
            tokens = res["tokens"]
            if tokens[: spec["prompt"]] != prompts[i] or \
                    len(tokens) != spec["prompt"] + spec["gen"]:
                problems.append(f"request {i}: wrong shape {len(tokens)}")
                continue
            # The reference runs where the weights and the chip are.
            check = replica.verify(tokens, spec["prompt"])
            for pos, (top, gap) in enumerate(
                zip(check["row_max"], check["margin"])
            ):
                if not (math.isfinite(top) and math.isfinite(gap)):
                    problems.append(f"request {i} token {pos}: not finite")
                elif gap > 2 * BF16_TOL * max(abs(top), 1.0):
                    problems.append(
                        f"request {i} token {pos}: chosen logit {gap:.4f} "
                        f"below the reference maximum {top:.4f}"
                    )
                worst = max(worst, gap)
        out["worst_margin"] = worst
        out["generated"] = [
            (r or {}).get("n_gen") for r in results
        ]
        return problems, out, device
    finally:
        http.stop()
        gw.stop()


# ---------------------------------------------------------------------------


def keep_logs(workdir):
    """Logs and events, where a chip run brings them back from."""
    dest = os.path.join(REPO, "chiprun_out", "chip_smoke")
    shutil.rmtree(dest, ignore_errors=True)
    keep = (".log", ".jsonl", ".json")
    for root, _dirs, files in os.walk(workdir):
        for name in files:
            if name.endswith(keep):
                rel = os.path.relpath(os.path.join(root, name), workdir)
                os.makedirs(os.path.dirname(os.path.join(dest, rel)),
                            exist_ok=True)
                shutil.copy(os.path.join(root, name),
                            os.path.join(dest, rel))
    return dest


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on four chips")
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths: the CPU rehearsal a test can afford")
    args = ap.parse_args(argv)

    from dlrover_tpu.common.platform import configure_compile_cache

    # Every process started below inherits the cache's place, and finds
    # the package (workers start as ``python -m dlrover_tpu...``).
    cache_dir = configure_compile_cache()
    os.environ["PYTHONPATH"] = os.pathsep.join(
        filter(None, [REPO, os.environ.get("PYTHONPATH")])
    )
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    say(f"workdir {workdir}; compile cache {cache_dir}")
    phases, device = {}, None

    spec = TRAIN_SPECS[(args.chips, args.tiny)]
    events, t_kill, killed_pid, rc = run_tpurun_job(
        os.path.join(workdir, "train"), args.chips, args.tiny
    )
    first = "mesh" if args.chips == 4 else "train"
    problems, info, device = judge_train(spec, args.chips, events)
    phases[first] = (problems, info)
    phases["resume"] = judge_resume(
        spec, args.chips, events, t_kill, killed_pid, rc
    )
    if args.chips == 1:
        problems, info, serve_device = run_serve(
            os.path.join(workdir, "serve"), args.tiny
        )
        phases["serve"] = (problems, info)
        if serve_device and device and (
            serve_device["platform"], serve_device["kind"]
        ) != (device["platform"], device["kind"]):
            problems.append(
                f"replica on {serve_device}, trainer on {device}"
            )
        device = device or serve_device

    left = children()
    if left:
        kill_children()
    logs = keep_logs(workdir)
    shutil.rmtree(workdir, ignore_errors=True)
    ok = True
    for name, (problems, info) in phases.items():
        print(json.dumps({"phase": name, "ok": not problems, **info,
                          "problems": problems}), flush=True)
        ok = ok and not problems
    if left:
        print(json.dumps({"left_running": left}), flush=True)
        ok = False
    if not ok:
        say(f"failed; logs kept in {logs}")
    shown = {"platform": None, "kind": None, "count": 0}
    if device:
        shown = {k: device[k] for k in shown}
    assert "jax" not in sys.modules, "the parent imported JAX"
    print(json.dumps({"ok": ok, "device": shown}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
