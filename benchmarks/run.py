"""The benchmark's command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data, found by name:
``workloads/<name>.json`` (configuration, traffic, chips, its metrics) ->
``configs/<config>.json``, ``traffic/<traffic>.json`` (driver kind and its
parameters) -> ``drivers/<kind>.py``, and one reader a metric,
``metrics/<metric>.py``.  ``README.md`` says how a later PR adds each.

The last line of stdout is the one JSON object the contract names.  With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (and ``device`` has ``busy_s`` and
``window_s``, and there is a ``breakdown``).  Off a TPU, or on fewer chips
than the cell asks for, the run exits non-zero and prints no result: what
it would have printed stays in ``<checkout>/.bench_runs/<name>/result.json``
for a rehearsal to read.  This process never imports JAX.
"""

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

T0 = time.time()
BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
for _p in (CHECKOUT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg):
    print(f"[bench +{time.time() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name, root=BENCH):
    """The cell's file and the files it names -> (cell, config, traffic)."""
    cell = load_json(root, "workloads", name + ".json")
    return (cell,
            load_json(root, "configs", cell["config"] + ".json"),
            load_json(root, "traffic", cell["traffic"] + ".json"))


def load_reader(name, cells):
    """``metrics/<name>.py``, beside the cell's files or with the benchmark."""
    for root in (cells, BENCH):
        path = os.path.join(root, "metrics", name + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{name}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module
    raise FileNotFoundError(f"no reader metrics/{name}.py")


def read_metrics(names, run, cells):
    out = {}
    for name in names:
        reader = load_reader(name, cells)
        value = reader.read(run)
        if value is not None:
            out[name] = {"value": value, "unit": reader.UNIT}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cells", default=BENCH,
                    help="where workloads/, configs/, traffic/ (and further "
                         "metrics/) are read from: the tests keep a tiny "
                         "cell of their own")
    args = ap.parse_args(argv)

    try:
        from dlrover_tpu.common.platform import configure_compile_cache
    except ImportError as e:
        log(f"the system under test is not in this checkout: {e}")
        return 2
    cell, config, traffic = load_cell(args.workload, args.cells)

    # Every process started below inherits the cache's place, and finds the
    # package (workers start as ``python -m dlrover_tpu...``).
    cache_dir = configure_compile_cache()
    os.environ["PYTHONPATH"] = os.pathsep.join(
        filter(None, [CHECKOUT, os.environ.get("PYTHONPATH")])
    )
    # The program's unix sockets default to a fixed /tmp directory; the
    # driver gives each side a TMPDIR of its own (a socket path must stay
    # under 108 bytes, so a long one keeps the default).
    sock_dir = os.path.join(tempfile.gettempdir(), "dlrover_tpu_sock")
    if len(sock_dir) <= 60:
        os.environ.setdefault("DLROVER_SOCK_DIR", sock_dir)
    workdir = os.path.join(CHECKOUT, ".bench_runs", args.workload)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    log(f"{args.workload}: workdir {workdir}; compile cache {cache_dir}")

    driver = importlib.import_module(f"drivers.{traffic['driver']}")
    run = driver.run(dict(
        workdir=workdir, cell=cell, config=config, params=traffic["params"],
        seed=args.seed, seconds=args.seconds, trace=args.trace, t0=T0,
    ), log)

    device = run["device"] or {"platform": None, "kind": None, "count": 0}
    run["peak"] = load_json(BENCH, "peaks.json").get(device["kind"])
    names = cell["per_layer"] if args.trace else cell["end_to_end"]
    result = {
        "correct": not run["problems"],
        "attempted": run["attempted"],
        "failed": run["failed"],
        "metrics": read_metrics(names, run, args.cells),
        "device": device,
    }
    if args.trace:
        from reduce import xplane

        busy_s, window_s = driver.device_window(run)
        device.update(busy_s=busy_s, window_s=window_s)
        if run["reduced"]:
            result["breakdown"] = {
                "device_ops": xplane.top_ops(run["reduced"], 10),
                "idle_gaps": xplane.longest_gaps(run["reduced"], 5),
            }
        if not busy_s:
            run["problems"].append("no operation ran on the device in the trace")
            result["correct"] = False
    missing = sorted(set(names) - set(result["metrics"]))
    if missing and not args.trace:
        run["problems"].append(f"metrics not measured: {missing}")
        result["correct"] = False
    if "jax" in sys.modules:
        run["problems"].append("the harness imported JAX")
        result["correct"] = False
    with open(os.path.join(workdir, "result.json"), "w") as f:
        json.dump(dict(result, problems=run["problems"],
                       job_uid=run.get("job_uid")), f)
    for problem in run["problems"]:
        log(f"problem: {problem}")
    on_the_chip = (device["platform"] == "tpu"
                   and device["count"] == cell["chips"]
                   and run["peak"] is not None)
    if not on_the_chip:
        log(f"not measured: the cell needs {cell['chips']} TPU chip(s) of a "
            f"kind in peaks.json, the workers found {device}")
        return 3
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
