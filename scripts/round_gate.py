"""End-of-round green gate: block the snapshot until the evidence is green.

Re-runs both driver checks before a snapshot and, while the bench is red
and wait budget remains, retries instead of recording a red number.

Usage:  python scripts/round_gate.py [--max-wait-s 2700] [--skip-bench]
                                     [--skip-chaos] [--skip-analysis]
                                     [--skip-doctor] [--skip-corruption]
                                     [--skip-perf] [--skip-packed]
                                     [--skip-kv] [--skip-serve]
                                     [--skip-serve-chaos] [--skip-kv-ha]
                                     [--skip-trace] [--skip-observer]
                                     [--accept-pragmas]

Writes GATE_STATUS.json and exits 0 only when:
  * dryrun_multichip(8) passes on a forced-CPU virtual mesh, AND
  * bench.py emits backend tpu with vs_baseline >= 1.0, AND
  * the static analyzer (python -m dlrover_tpu.analysis) reports zero
    unsuppressed findings over dlrover_tpu/ (--skip-analysis to waive)
    AND its per-code suppressed tally did not grow vs the previous
    GATE_STATUS.json (--accept-pragmas to re-baseline explicitly).
    The analysis record also carries the DLR018 wire-schema verdict
    (``comm_schema``: ok / additive / drift).

The chaos suite (tests/test_chaos.py, ``-m chaos``) runs report-only:
its pass/fail counts land in GATE_STATUS.json for the round record but
do not flip the gate — tier-1 already includes the fast chaos tests, so
gating twice would only double the flake surface.
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg):
    print(f"[gate +{time.time() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


T0 = time.time()


def run_dryrun(timeout_s=900):
    """dryrun_multichip(8) in a subprocess with a scrubbed env (the entry
    forces CPU config-first, so this never touches a chip)."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    try:
        res = subprocess.run(
            [sys.executable, "-c",
             "import __graft_entry__ as g; g.dryrun_multichip(8)"],
            cwd=REPO, env=env, timeout=timeout_s,
            capture_output=True, text=True,
        )
        ok = res.returncode == 0
        if not ok:
            log(f"dryrun rc={res.returncode}\n{res.stderr[-2000:]}")
        return {"ok": ok, "rc": res.returncode,
                "tail": res.stdout.strip().splitlines()[-3:]}
    except subprocess.TimeoutExpired:
        return {"ok": False, "rc": 124, "tail": ["timeout"]}


def run_bench(budget_s=480, allow_archive=False):
    """bench.py in a subprocess; returns the parsed JSON line (or None).

    allow_archive=False forbids the BENCH_LAST_GREEN.json fallback so the
    retry loop keeps pressing for a FRESH on-chip number while wait
    budget remains; only the final attempt may take the archive."""
    env = dict(os.environ)
    env.setdefault("BENCH_BUDGET_S", str(budget_s))
    env["BENCH_NO_ARCHIVE_FALLBACK"] = "0" if allow_archive else "1"
    # The hard-kill deadline must track the budget bench.py actually runs
    # with (operator may have set BENCH_BUDGET_S larger).
    effective_budget = float(env["BENCH_BUDGET_S"])
    try:
        res = subprocess.run(
            [sys.executable, "bench.py"], cwd=REPO, env=env,
            timeout=effective_budget + 120, capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        log("bench.py exceeded its own watchdog + 120s")
        return None
    for line in reversed(res.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except (ValueError, json.JSONDecodeError):
            continue
    log(f"no JSON line from bench.py; stderr tail:\n{res.stderr[-1500:]}")
    return None


def run_chaos(timeout_s=900):
    """Report-only chaos sweep: every fault-injection scenario, including
    the slow ones tier-1 skips.  Parses pytest's summary line into
    pass/fail counts; a red chaos number is recorded, not gating."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-m", "chaos",
             "tests/test_chaos.py", "-p", "no:cacheprovider"],
            cwd=REPO, env=env, timeout=timeout_s,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"passed": 0, "failed": 0, "rc": 124, "error": "timeout"}
    passed = failed = 0
    for line in reversed(res.stdout.strip().splitlines()):
        toks = line.replace(",", " ").split()
        for i, tok in enumerate(toks):
            if tok == "passed" and i:
                passed = int(toks[i - 1])
            elif tok in ("failed", "error", "errors") and i:
                failed += int(toks[i - 1])
        if passed or failed:
            break
    if res.returncode != 0:
        log(f"chaos suite rc={res.returncode}\n{res.stdout[-1500:]}")
    return {"passed": passed, "failed": failed, "rc": res.returncode}


def run_corruption_drill(timeout_s=900):
    """Report-only checkpoint-trust drill: the corruption chaos scenarios
    (bitflip / truncate / stale tracker / shm crc) plus the end-to-end
    bitflip+kill reform drill.  Records pass/fail counts in
    GATE_STATUS.json; never gates — tier-1 already runs these, so gating
    twice would only double the flake surface."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-m", "chaos",
             "-k", "corrupt or quarantine or stale_tracker",
             "tests/test_chaos.py", "-p", "no:cacheprovider"],
            cwd=REPO, env=env, timeout=timeout_s,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"passed": 0, "failed": 0, "rc": 124, "error": "timeout"}
    passed = failed = 0
    for line in reversed(res.stdout.strip().splitlines()):
        toks = line.replace(",", " ").split()
        for i, tok in enumerate(toks):
            if tok == "passed" and i:
                passed = int(toks[i - 1])
            elif tok in ("failed", "error", "errors") and i:
                failed += int(toks[i - 1])
        if passed or failed:
            break
    if res.returncode != 0:
        log(f"corruption drill rc={res.returncode}\n{res.stdout[-1500:]}")
    return {"passed": passed, "failed": failed, "rc": res.returncode}


def run_doctor(timeout_s=600):
    """Report-only doctor smoke: re-run the doctor chaos scenario with
    bundle export armed, then run ``python -m dlrover_tpu.doctor`` on the
    exported bundle and record whether the incident report names the
    injected fault.  Never gates — the round record just shows whether
    the postmortem loop closes on this tree."""
    import tempfile

    out = {"ok": False, "names_injected_fault": False}
    with tempfile.TemporaryDirectory(prefix="gate_doctor_") as export_dir:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["DLROVER_CHAOS_EXPORT_DIR"] = export_dir
        try:
            res = subprocess.run(
                [sys.executable, "-m", "pytest", "-q", "-m", "chaos",
                 "-k", "doctor", "tests/test_chaos.py",
                 "-p", "no:cacheprovider"],
                cwd=REPO, env=env, timeout=timeout_s,
                capture_output=True, text=True,
            )
        except subprocess.TimeoutExpired:
            out["error"] = "chaos doctor scenario timeout"
            return out
        out["scenario_rc"] = res.returncode
        import glob

        bundles = sorted(
            glob.glob(os.path.join(export_dir, "bundle_*.tar.gz"))
        )
        if not bundles:
            out["error"] = "chaos run exported no bundle"
            return out
        out["bundle"] = os.path.basename(bundles[-1])
        try:
            doc = subprocess.run(
                [sys.executable, "-m", "dlrover_tpu.doctor", bundles[-1],
                 "--out-dir", export_dir, "--json"],
                cwd=REPO, env=env, timeout=120,
                capture_output=True, text=True,
            )
        except subprocess.TimeoutExpired:
            out["error"] = "doctor timeout"
            return out
        if doc.returncode != 0:
            out["error"] = f"doctor rc={doc.returncode}"
            log(f"doctor stderr tail:\n{doc.stderr[-1000:]}")
            return out
        try:
            report = json.loads(doc.stdout)
        except (ValueError, json.JSONDecodeError):
            out["error"] = "doctor emitted no JSON"
            return out
        faults = [
            i for i in report.get("incidents", [])
            if i.get("trigger") == "injected_fault"
        ]
        out["incidents"] = len(report.get("incidents", []))
        out["total_cost_pts"] = report.get("total_cost_pts")
        if faults:
            out["names_injected_fault"] = True
            out["fault_point"] = faults[0].get("fault_point")
            out["first_failing_rank"] = faults[0].get("first_failing_rank")
        out["ok"] = res.returncode == 0 and bool(faults)
    return out


def run_perf(bench_result):
    """Report-only perf reconciliation: price the round's bench number
    against the cost model's calibrated prediction and append the
    comparison to the perf ledger, so the round record carries a
    measured-vs-predicted delta instead of a bare throughput.  Never
    gates — the bench stage already decides green/red, and a prediction
    miss is a finding for the record, not a reason to block a snapshot.

    Runs in-process (no subprocess, no sleeping): the cost model is a
    pure read of the calibration history plus one O_APPEND write."""
    out = {"ok": False}
    try:
        from dlrover_tpu.telemetry import costmodel

        # Honor the env override like every other ledger writer, but
        # default to the gate's REPO (tests sandbox it) rather than the
        # costmodel's baked-in repo root.
        ledger = os.environ.get(costmodel.ENV_LEDGER_PATH) or os.path.join(
            REPO, costmodel.LEDGER_BASENAME
        )
        cal = costmodel.load_calibration(REPO)
        bench_result = bench_result if isinstance(bench_result, dict) else {}
        n_params = int(
            bench_result.get("n_params") or cal.get("n_params") or 0
        )
        if not n_params:
            out["error"] = "no parameter count to predict from"
            return out
        pred = costmodel.predict_tokens_per_sec(
            n_params, backend="v5e", repo=REPO
        )
        out["predicted_tokens_per_sec"] = round(
            pred["predicted_tokens_per_sec"], 1
        )
        out["calibration"] = {"mfu": pred["mfu_used"],
                              "source": cal["source"]}
        measured = None
        if (
            not bench_result.get("error")
            and bench_result.get("backend") == "tpu"
        ):
            measured = float(bench_result.get("value") or 0.0) or None
        out["measured_tokens_per_sec"] = measured
        out["blind"] = measured is None
        if measured and out["predicted_tokens_per_sec"]:
            out["delta_pct"] = round(
                100.0 * (measured - out["predicted_tokens_per_sec"])
                / out["predicted_tokens_per_sec"], 1,
            )
        else:
            out["delta_pct"] = None
        out["wus"] = _wus_evidence(
            costmodel, n_params, pred["predicted_tokens_per_sec"]
        )
        costmodel.append_ledger(
            {
                "source": "gate",
                "backend": bench_result.get("backend"),
                "tokens_per_sec": measured,
                "predicted_tpu_tokens_per_sec":
                    out["predicted_tokens_per_sec"],
                "delta_pct": out["delta_pct"],
                "measured": measured is not None,
                "blind": out["blind"],
                "archived": bool(bench_result.get("archived")),
                "calibration_source": cal["source"],
                "n_params": n_params,
                "wus": out["wus"],
            },
            path=ledger,
        )
        out["ledger"] = os.path.basename(ledger)
        out["ok"] = True
    except Exception as e:  # noqa: BLE001 — report-only, never gates
        out["error"] = str(e)
    return out


def _wus_evidence(costmodel, n_params, predicted_tps):
    """Weight-update-sharding evidence for the round record: read the
    AOT evidence pair out of AOT_SLICE.json (scripts/aot_slice_compile.py
    compiles llama-7B+int8 with and without the scatter plan) and price
    its collective delta with the cost model.  Returns None when the
    pair hasn't been compiled on this tree yet.

    ``predicted_tokens_per_sec_no_overlap`` is the worst case (every
    added collective serialized after compute);
    ``predicted_tokens_per_sec_overlapped`` is the design point — the
    param all-gather hidden under the next microbatch's forward in the
    1F1B schedule (parallel/pipeline.py)."""
    try:
        with open(os.path.join(REPO, "AOT_SLICE.json")) as f:
            programs = json.load(f).get("programs", [])
    except (OSError, ValueError):
        return None
    pair = next(
        (p for p in programs if p.get("name") == "llama7b_wus_int8_pair"),
        None,
    )
    if pair is None:
        return None
    ev = {
        "ok": pair.get("ok"),
        "topology": pair.get("topology"),
        "n_replica": pair.get("n_replica"),
        "census_delta": pair.get("census_delta"),
        "hbm_drop_bytes_per_chip": pair.get("hbm_drop_bytes_per_chip"),
    }
    delta = pair.get("predicted") or {}
    wus_params = (pair.get("wus") or {}).get("n_params") or n_params
    frac = costmodel.wus_collective_fraction(
        delta, wus_params, repo=REPO
    )
    ev["modeled_collective_fraction"] = (
        round(frac, 4) if frac is not None else None
    )
    if frac is not None and predicted_tps:
        ev["predicted_tokens_per_sec_no_overlap"] = round(
            predicted_tps * (1.0 - frac), 1
        )
        ev["predicted_tokens_per_sec_overlapped"] = round(
            predicted_tps, 1
        )
    ev["opt_hbm_bytes_saved_per_chip"] = delta.get(
        "opt_hbm_bytes_saved_per_chip"
    )
    return ev


def run_packed_census(timeout_s=600):
    """Report-only packed long-context census: ``bench.py probe_packed``
    sweeps document-length mixtures at s=8192 through the real
    first-fit packer and prices the segment layout with the mask-aware
    cost model (segment-sparse Σᵢ sᵢ² vs dense-causal b·s²).  The probe
    appends its own perf-history entries; this stage records the
    sweep in GATE_STATUS.json.  ``ok`` means the headline mean-1k
    mixture cleared the >=2x attention-FLOP reduction the packed
    pipeline promises.  Never gates — the census is a cost-model
    output, not a measurement.  Forced CPU: pure host-side arithmetic,
    never touches a chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable, "bench.py", "probe_packed"], cwd=REPO,
            env=env, timeout=timeout_s, capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "timeout"}
    payload = None
    for line in reversed(res.stdout.strip().splitlines()):
        try:
            payload = json.loads(line)
            break
        except (ValueError, json.JSONDecodeError):
            continue
    if payload is None:
        log(f"probe_packed emitted no JSON; stderr tail:\n"
            f"{res.stderr[-1000:]}")
        return {"ok": False, "rc": res.returncode, "error": "no JSON"}
    return {
        "ok": bool(payload.get("ok")),
        "seq_len": payload.get("seq_len"),
        "headline_mixture": payload.get("headline_mixture"),
        "headline_reduction": payload.get("value"),
        "blind": payload.get("blind"),
        "mixtures": {
            m["mixture"]: {
                "docs": m.get("docs"),
                "packing_efficiency": m.get("packing_efficiency"),
                "reduction": m.get("reduction"),
                "packed_pred_tok_s": m.get("packed_pred_tok_s"),
                "dense_pred_tok_s": m.get("dense_pred_tok_s"),
            }
            for m in payload.get("mixtures", [])
        },
    }


def run_kv(timeout_s=600):
    """Report-only sharded-embedding stage: ``bench.py probe_kv --run``
    spins up a small real-process 2-shard service (dim 16, 30k keys),
    measures aggregate service capacity, runs the SIGKILL reshard
    drill, and appends kind="kv" ledger entries; the probe then fronts
    the full KV history (including the official 1/2/4-shard points).
    ``ok`` means entries exist, shard scaling clears the 2.5x floor,
    and the drill lost zero rows.  Never gates — tier-1 owns kv
    correctness; this is the round record's "the embedding plane still
    scales and fails over losslessly" receipt.  Forced CPU: real
    processes, loopback RPC, never touches a chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable, "bench.py", "probe_kv", "--run"], cwd=REPO,
            env=env, timeout=timeout_s, capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "timeout"}
    payload = None
    for line in reversed(res.stdout.strip().splitlines()):
        try:
            payload = json.loads(line)
            break
        except (ValueError, json.JSONDecodeError):
            continue
    if payload is None:
        log(f"probe_kv emitted no JSON; stderr tail:\n{res.stderr[-1000:]}")
        return {"ok": False, "rc": res.returncode, "error": "no JSON"}
    return {
        "ok": bool(payload.get("ok")),
        "aggregate_rows_per_s": payload.get("value"),
        "scaling_vs_1shard": payload.get("scaling_vs_1shard"),
        "scaling_floor": payload.get("scaling_floor"),
        "single_node_gather_rows_per_s":
            payload.get("single_node_gather_rows_per_s"),
        "contended_retention": payload.get("contended_retention"),
        "reshard_recovery_s": payload.get("reshard_recovery_s"),
        "reshard_lost_rows": payload.get("reshard_lost_rows"),
        "ledger_entries": payload.get("ledger_entries"),
    }


def run_serve(timeout_s=600):
    """Report-only inference-gateway stage: ``bench.py probe_serve
    --run`` replays the scaled mean-1k lognormal mixture through the
    legacy slot-pool engine and the paged+chunked gateway on the CPU
    harness, appends the kind="serve" ledger entry (with the calibrated
    blind TPU serving prediction), and fronts the serving history.
    ``ok`` means the gateway cleared the 2x tokens/s floor vs legacy.
    Never gates — tier-1 owns serving correctness (including the
    SIGKILL replay drill); this is the round record's "the serving
    plane still out-schedules the slot pool" receipt.  Forced CPU:
    in-process engines, never touches a chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable, "bench.py", "probe_serve", "--run"],
            cwd=REPO, env=env, timeout=timeout_s, capture_output=True,
            text=True,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "timeout"}
    payload = None
    for line in reversed(res.stdout.strip().splitlines()):
        try:
            payload = json.loads(line)
            break
        except (ValueError, json.JSONDecodeError):
            continue
    if payload is None:
        log(f"probe_serve emitted no JSON; stderr tail:\n"
            f"{res.stderr[-1000:]}")
        return {"ok": False, "rc": res.returncode, "error": "no JSON"}
    return {
        "ok": bool(payload.get("ok")),
        "gateway_tokens_per_sec": payload.get("value"),
        "legacy_tokens_per_sec": payload.get("legacy_tokens_per_sec"),
        "speedup_vs_legacy": payload.get("speedup_vs_legacy"),
        "speedup_floor": payload.get("speedup_floor"),
        "servput_pct": payload.get("servput_pct"),
        "prefix_hit_tokens": payload.get("prefix_hit_tokens"),
        "kv_occupancy_ratio": payload.get("kv_occupancy_ratio"),
        "predicted_tokens_per_sec":
            payload.get("predicted_tokens_per_sec"),
        "blind": payload.get("blind"),
        "ledger_entries": payload.get("ledger_entries"),
    }


def run_serve_chaos(timeout_s=300):
    """Report-only serving-fleet chaos stage: ``scripts/
    serve_chaos_drill.py`` kills a busy replica of a 2-live + 1-standby
    scripted fleet twice — once with a warm standby (promotion), once
    with the pool drained (cold spawn) — prices both reforms with the
    servput accountant, floods a brownout gateway to rung 3 and watches
    the hysteretic release, and smokes the Brain warehouse's
    incident-row rendering of the fleet verdicts.  ``ok`` means zero
    lost/duplicated completions, the promoted reform lost strictly
    fewer servput points than the cold one, and the brownout ladder
    engaged and released.  Never gates — tier-1 owns the real-process
    SIGKILL drill (tests/test_serving_fleet.py); this is the round
    record's "failover still beats cold respawn" receipt.  Forced CPU:
    in-process scripted replicas, never touches a chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join("scripts", "serve_chaos_drill.py")],
            cwd=REPO, env=env, timeout=timeout_s,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "timeout"}
    payload = None
    for line in reversed(res.stdout.strip().splitlines()):
        try:
            payload = json.loads(line)
            break
        except (ValueError, json.JSONDecodeError):
            continue
    if payload is None:
        log(f"serve_chaos_drill emitted no JSON; stderr tail:\n"
            f"{res.stderr[-1000:]}")
        return {"ok": False, "rc": res.returncode, "error": "no JSON"}
    return {
        "ok": bool(payload.get("ok")),
        "zero_loss": payload.get("zero_loss"),
        "promotions": payload.get("promotions"),
        "promoted_reform_pts": payload.get("promoted_reform_pts"),
        "cold_reform_pts": payload.get("cold_reform_pts"),
        "delta_pts": payload.get("delta_pts"),
        "brownout": payload.get("brownout"),
        "warehouse_triggers": payload.get("warehouse_triggers"),
        "report_renders_incidents":
            payload.get("report_renders_incidents"),
    }


def run_kv_ha(timeout_s=300):
    """Report-only KV high-availability stage: ``scripts/
    kv_ha_drill.py`` runs the replicated embedding shard's failure
    story in-process — sync chain-delta replication, bounded-staleness
    follower reads, anti-entropy, then a dead primary promoted under a
    new lease epoch and a dead unreplicated shard chain-restored — and
    prices both recoveries.  ``ok`` means zero acked-row loss on both
    paths, promotion strictly cheaper than chain restore, and the
    Brain warehouse rendering the ``kv_failover`` incidents and the
    hot-key skew rows.  Never gates — tier-1 owns the real-process
    SIGKILL promotion drill (tests/test_kv_replication.py); this is
    the round record's "promotion still beats chain restore" receipt.
    Forced CPU: in-process shards, loopback RPC, never touches a chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable, os.path.join("scripts", "kv_ha_drill.py")],
            cwd=REPO, env=env, timeout=timeout_s,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "timeout"}
    payload = None
    for line in reversed(res.stdout.strip().splitlines()):
        try:
            payload = json.loads(line)
            break
        except (ValueError, json.JSONDecodeError):
            continue
    if payload is None:
        log(f"kv_ha_drill emitted no JSON; stderr tail:\n"
            f"{res.stderr[-1000:]}")
        return {"ok": False, "rc": res.returncode, "error": "no JSON"}
    return {
        "ok": bool(payload.get("ok")),
        "zero_loss": payload.get("zero_loss"),
        "replica_reads": payload.get("replica_reads"),
        "anti_entropy": payload.get("anti_entropy"),
        "promotion": payload.get("promotion"),
        "chain_restore": payload.get("chain_restore"),
        "promotion_beats_chain_restore":
            payload.get("promotion_beats_chain_restore"),
        "warehouse_triggers": payload.get("warehouse_triggers"),
        "report_renders_incidents":
            payload.get("report_renders_incidents"),
        "report_renders_hot_keys":
            payload.get("report_renders_hot_keys"),
    }


def run_trace(timeout_s=600):
    """Report-only tracing/SLO stage: ``scripts/trace_probe.py`` drives
    a fully-sampled traffic burst through the paged gateway, counts the
    spans each request produced, reconstructs the richest trace and
    checks causal order, and snapshots the SLO engine — the round
    record's "a sampled request's timeline is reconstructible and the
    burn-rate engine evaluates" receipt.  Never gates — tier-1
    (tests/test_tracing.py) owns tracing correctness, including the
    cross-process SIGKILL drill.  Forced CPU: in-process replica, never
    touches a chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable, os.path.join("scripts", "trace_probe.py")],
            cwd=REPO, env=env, timeout=timeout_s,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "timeout"}
    payload = None
    for line in reversed(res.stdout.strip().splitlines()):
        try:
            payload = json.loads(line)
            break
        except (ValueError, json.JSONDecodeError):
            continue
    if payload is None:
        log(f"trace_probe emitted no JSON; stderr tail:\n"
            f"{res.stderr[-1000:]}")
        return {"ok": False, "rc": res.returncode, "error": "no JSON"}
    return {
        "ok": bool(payload.get("ok")),
        "requests": payload.get("requests"),
        "completed": payload.get("completed"),
        "span_total": payload.get("span_total"),
        "span_counts": payload.get("span_counts"),
        "sampled_traces": payload.get("sampled_traces"),
        "reconstruction": payload.get("reconstruction"),
        "slo": payload.get("slo"),
    }


def run_observer(timeout_s=300):
    """Report-only fleet-observer stage: ``scripts/observer_probe.py``
    federates a scripted mini fleet (two known-value workers, a fake
    gateway, a real kv shard), checks the merged counters and fleet p50
    against hand-built oracles, runs the black-box canaries green, then
    flips the gateway to shedding while ``/healthz`` stays ready and
    watches the ``canary_divergence`` verdict fire — the round record's
    "the black-box plane still sees what the white-box plane misses"
    receipt.  Never gates — tier-1 owns observer correctness, including
    the wedged-replica real-process drill (tests/test_observer.py).
    Forced CPU: scripted HTTP sources, loopback only, never touches a
    chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join("scripts", "observer_probe.py")],
            cwd=REPO, env=env, timeout=timeout_s,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "timeout"}
    payload = None
    for line in reversed(res.stdout.strip().splitlines()):
        try:
            payload = json.loads(line)
            break
        except (ValueError, json.JSONDecodeError):
            continue
    if payload is None:
        log(f"observer_probe emitted no JSON; stderr tail:\n"
            f"{res.stderr[-1000:]}")
        return {"ok": False, "rc": res.returncode, "error": "no JSON"}
    return {
        "ok": bool(payload.get("ok")),
        "kv_tier": payload.get("kv_tier"),
        "baseline_probes_ok": payload.get("baseline_probes_ok"),
        "counter_sum": payload.get("counter_sum"),
        "fleet_p50": payload.get("fleet_p50"),
        "oracle_p50": payload.get("oracle_p50"),
        "divergence_verdicts": payload.get("divergence_verdicts"),
        "fleetz_sources": payload.get("fleetz_sources"),
        "top_renders": payload.get("top_renders"),
    }


def run_warehouse():
    """Report-only telemetry-warehouse stage: backfill the repo's flat
    perf history into a fresh warehouse db and smoke the report CLI, so
    GATE_STATUS.json records that cross-job history is ingestible and
    renderable this round.  Never gates — tier-1 owns warehouse
    correctness; this is the round record's "the data spine works"
    receipt.

    Runs in-process except for the CLI smoke, which exercises the real
    ``python -m dlrover_tpu.brain report`` entrypoint."""
    out = {"ok": False}
    db = os.path.join(REPO, "GATE_WAREHOUSE.sqlite")
    try:
        if os.path.exists(db):
            os.remove(db)
        from dlrover_tpu.brain.warehouse import TelemetryWarehouse

        wh = TelemetryWarehouse(db)
        try:
            counts = wh.backfill(root=REPO)
            out["ingested"] = counts
            out["runs"] = len(wh.runs())
            out["perf_records"] = len(wh.records(kind="perf", limit=100000))
        finally:
            wh.close()
        proc = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.brain", "report",
             "--db", db, "--json", "-"],
            capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        out["report_cli_rc"] = proc.returncode
        if proc.returncode == 0:
            report = json.loads(proc.stdout)
            out["report_jobs"] = len(report.get("jobs", {}))
            out["report_perf_entries"] = len(report.get("perf_trend", []))
        else:
            out["error"] = proc.stderr.strip()[-500:]
        out["db"] = os.path.basename(db)
        out["ok"] = (
            proc.returncode == 0
            and sum(counts.values()) > 0
            and out.get("report_perf_entries", 0) > 0
        )
    except Exception as e:  # noqa: BLE001 — report-only, never gates
        out["error"] = str(e)
    finally:
        # The gate db is a smoke artifact, not round state.
        try:
            if os.path.exists(db):
                os.remove(db)
        except OSError:
            pass
    return out


def run_brain_plan():
    """Report-only capacity-planner smoke: backfill the repo's flat
    perf history into a throwaway warehouse, ask ``python -m
    dlrover_tpu.brain plan`` to price a 2-replica/1-standby fleet
    against it, and record the verdict + headroom in GATE_STATUS.json.
    Never gates — tier-1 owns planner correctness; this is the round
    record's "the decision plane prices a proposal end to end" receipt.
    """
    out = {"ok": False}
    db = os.path.join(REPO, "GATE_BRAIN_PLAN.sqlite")
    try:
        if os.path.exists(db):
            os.remove(db)
        from dlrover_tpu.brain.warehouse import TelemetryWarehouse

        wh = TelemetryWarehouse(db)
        try:
            out["ingested"] = wh.backfill(root=REPO)
        finally:
            wh.close()
        proc = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.brain", "plan",
             "--db", db, "--replicas", "2", "--standbys", "1",
             "--json", "-"],
            capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        out["plan_cli_rc"] = proc.returncode
        if proc.returncode == 0:
            plan = json.loads(proc.stdout)
            out["verdict"] = plan.get("verdict")
            out["headroom_pct"] = plan.get("headroom_pct")
            cap = plan.get("capacity") or {}
            out["capacity_source"] = cap.get("source")
            out["fleet_tokens_per_sec"] = cap.get("fleet_tokens_per_sec")
            out["traffic_windows"] = (plan.get("traffic") or {}).get(
                "windows")
            out["config_draft_lines"] = len(
                (plan.get("config_draft") or {}).get("lines") or [])
        else:
            out["error"] = proc.stderr.strip()[-500:]
        out["db"] = os.path.basename(db)
        out["ok"] = (
            proc.returncode == 0
            and out.get("verdict") is not None
            and out.get("fleet_tokens_per_sec", 0) > 0
        )
    except Exception as e:  # noqa: BLE001 — report-only, never gates
        out["error"] = str(e)
    finally:
        # The gate db is a smoke artifact, not round state.
        try:
            if os.path.exists(db):
                os.remove(db)
        except OSError:
            pass
    return out


def run_analysis(timeout_s=300, previous=None, accept_pragmas=False):
    """Static-analyzer gate: the checked-in tree must lint clean AND
    stay inside the pragma budget.

    Unsuppressed findings fail the gate — this is what keeps the DLR001
    donation class (the PR 3 SIGSEGV) from re-landing between rounds.
    Suppressed counts are diffed per code against the previous round's
    GATE_STATUS.json (``previous``): growth fails unless the round ran
    with --accept-pragmas, which re-baselines explicitly.  The DLR018
    wire-schema verdict (``comm_schema``) rides along in the summary so
    the round record shows schema compatibility, not just "no
    findings"."""
    from dlrover_tpu.analysis.gate import analysis_summary

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.analysis",
             "dlrover_tpu", "--json"],
            cwd=REPO, env=env, timeout=timeout_s,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "rc": 124, "error": "timeout"}
    try:
        payload = json.loads(res.stdout)
    except (ValueError, json.JSONDecodeError):
        log(f"analysis emitted no JSON; stderr tail:\n{res.stderr[-1500:]}")
        return {"ok": False, "rc": res.returncode, "error": "no JSON"}
    summary = analysis_summary(
        payload, res.returncode,
        previous=previous, accept_pragmas=accept_pragmas,
    )
    if summary["rc"] != 0:
        for f in payload.get("findings", [])[:10]:
            log(f"analysis: {f['path']}:{f['line']}: {f['code']} "
                f"{f['message'][:100]}")
    for line in summary["pragma_budget"]["grew"]:
        log(f"analysis pragma budget {'re-baselined' if accept_pragmas else 'exceeded'}: {line}")
    return summary


sys.path.insert(0, REPO)
from bench import MAX_ARCHIVE_STALENESS_S  # noqa: E402 — shared cap


def _archive_lineage(sha):
    """Where the archived bench's commit sits relative to HEAD.

    Returns ``(is_ancestor, distance)``: a wall-clock staleness cap alone
    can accept a number measured on an abandoned/rebased line that is not
    in HEAD's history at all — ancestry is what proves "this round's code
    line, a few commits behind" vs "some other branch".  distance is the
    commit count HEAD is ahead (-1 when unknown)."""
    if not sha:
        return False, -1
    try:
        anc = subprocess.run(
            ["git", "merge-base", "--is-ancestor", sha, "HEAD"],
            cwd=REPO, capture_output=True, text=True, timeout=30,
        )
        if anc.returncode != 0:
            return False, -1
        cnt = subprocess.run(
            ["git", "rev-list", "--count", f"{sha}..HEAD"],
            cwd=REPO, capture_output=True, text=True, timeout=30,
        )
        dist = int(cnt.stdout.strip()) if cnt.returncode == 0 else -1
        return True, dist
    except (subprocess.TimeoutExpired, OSError, ValueError):
        return False, -1


def bench_green(result):
    if (
        result is None
        or result.get("backend") != "tpu"
        or result.get("vs_baseline", 0.0) < 1.0
        or result.get("error")
    ):
        return False
    if result.get("archived"):
        # The 12h cap bounds the archive to this round's window; the
        # ancestry check additionally proves the number was measured ON
        # THIS code line (archived_sha reachable from HEAD), not on a
        # rebased-away or parallel branch that happens to be recent.
        # Both verdicts land in the payload (and GATE_STATUS.json) for
        # audit.
        if result.get("staleness_s", float("inf")) > MAX_ARCHIVE_STALENESS_S:
            return False
        sha = result.get("archived_sha")
        if not sha:
            # bench.emit records the sha whenever git works; an archive
            # without one predates that (or was written in a sandbox), so
            # the staleness cap above is the only lineage evidence.
            log("archived bench has no sha; accepting on staleness alone")
            return True
        is_ancestor, distance = _archive_lineage(sha)
        result["archived_sha_is_ancestor"] = is_ancestor
        result["archived_sha_distance"] = distance
        if not is_ancestor:
            log(f"archived bench sha {result.get('archived_sha', '?')[:12]} "
                "is not an ancestor of HEAD — rejecting the archive")
        return is_ancestor
    return True


def telemetry_snapshot():
    """Observability evidence for the round record: exercise the metric
    adapters in-process (SpeedMonitor -> registry) and snapshot the
    registry plus the latest GOODPUT.json online attribution if a
    goodput run left one behind."""
    snap = {}
    try:
        from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor
        from dlrover_tpu.telemetry import metrics as telemetry_metrics

        sm = SpeedMonitor()
        sm.collect_global_step(1, time.time())
        snap["metric_series"] = telemetry_metrics.REGISTRY.counts()
        snap["prometheus_bytes"] = len(telemetry_metrics.REGISTRY.render())
    except Exception as e:  # noqa: BLE001 — evidence, not a gate input
        snap["error"] = str(e)
    try:
        with open(os.path.join(REPO, "GOODPUT.json")) as f:
            online = json.load(f).get("summary", {}).get("online", {})
        if online:
            snap["online_goodput"] = {
                k: online.get(k)
                for k in ("goodput_pct", "phases", "events_ingested")
            }
    except (OSError, ValueError):
        pass
    return snap


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-wait-s", type=float, default=2700.0,
                    help="total budget to keep retrying a red bench")
    ap.add_argument("--retry-sleep-s", type=float, default=300.0)
    ap.add_argument("--skip-bench", action="store_true",
                    help="gate the dryrun only (no healthy chip expected)")
    ap.add_argument("--skip-chaos", action="store_true",
                    help="skip the report-only fault-injection sweep")
    ap.add_argument("--skip-doctor", action="store_true",
                    help="skip the report-only doctor/bundle smoke stage")
    ap.add_argument("--skip-corruption", action="store_true",
                    help="skip the report-only checkpoint corruption drill")
    ap.add_argument("--skip-warehouse", action="store_true",
                    help="skip the report-only telemetry-warehouse "
                    "backfill + report-CLI smoke")
    ap.add_argument("--skip-perf", action="store_true",
                    help="skip the report-only bench-vs-prediction "
                         "reconciliation stage")
    ap.add_argument("--skip-packed", action="store_true",
                    help="skip the report-only packed long-context "
                         "attention-FLOP census (bench.py probe_packed)")
    ap.add_argument("--skip-kv", action="store_true",
                    help="skip the report-only sharded-embedding bench "
                         "+ reshard drill (bench.py probe_kv --run)")
    ap.add_argument("--skip-serve", action="store_true",
                    help="skip the report-only serving bench "
                         "(bench.py probe_serve --run)")
    ap.add_argument("--skip-serve-chaos", action="store_true",
                    help="skip the report-only serving-fleet failover "
                         "drill (scripts/serve_chaos_drill.py)")
    ap.add_argument("--skip-kv-ha", action="store_true",
                    help="skip the report-only KV failover drill "
                         "(scripts/kv_ha_drill.py)")
    ap.add_argument("--skip-trace", action="store_true",
                    help="skip the report-only tracing/SLO probe "
                         "(scripts/trace_probe.py)")
    ap.add_argument("--skip-observer", action="store_true",
                    help="skip the report-only fleet-observer probe "
                         "(scripts/observer_probe.py)")
    ap.add_argument("--skip-brain", action="store_true",
                    help="skip the report-only brain-plan capacity "
                         "smoke (python -m dlrover_tpu.brain plan)")
    ap.add_argument("--skip-analysis", action="store_true",
                    help="waive the static-analyzer gate (escape hatch "
                         "for rounds that intentionally carry findings)")
    ap.add_argument("--accept-pragmas", action="store_true",
                    help="re-baseline the analyzer pragma budget: a "
                         "suppressed-findings tally that grew vs the "
                         "previous GATE_STATUS.json passes (and is "
                         "recorded as explicitly accepted)")
    args = ap.parse_args()

    status = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S")}

    log("running dryrun_multichip(8) on forced-CPU virtual mesh")
    status["dryrun"] = run_dryrun()
    log(f"dryrun ok={status['dryrun']['ok']}")

    if args.skip_analysis:
        status["analysis"] = {"skipped": True, "ok": True}
    else:
        log("running static analyzer over dlrover_tpu/")
        prev_analysis = None
        try:
            with open(os.path.join(REPO, "GATE_STATUS.json")) as f:
                prev_analysis = json.load(f).get("analysis")
        except (OSError, ValueError):
            pass
        status["analysis"] = run_analysis(
            previous=prev_analysis,
            accept_pragmas=args.accept_pragmas,
        )
        log(f"analysis ok={status['analysis']['ok']} "
            f"findings={status['analysis'].get('finding_count')} "
            f"suppressed={status['analysis'].get('suppressed_count')} "
            f"schema={status['analysis'].get('comm_schema', {}).get('status')}")

    if args.skip_chaos:
        status["chaos"] = {"skipped": True}
    else:
        log("running chaos suite (report-only)")
        status["chaos"] = run_chaos()
        log(f"chaos passed={status['chaos']['passed']} "
            f"failed={status['chaos']['failed']}")

    if args.skip_corruption:
        status["corruption_drill"] = {"skipped": True}
    else:
        log("running checkpoint corruption drill (report-only)")
        status["corruption_drill"] = run_corruption_drill()
        log(f"corruption drill "
            f"passed={status['corruption_drill']['passed']} "
            f"failed={status['corruption_drill']['failed']}")

    if args.skip_doctor:
        status["doctor"] = {"skipped": True}
    else:
        log("running doctor/bundle smoke (report-only)")
        status["doctor"] = run_doctor()
        log(f"doctor ok={status['doctor']['ok']} "
            f"names_injected_fault="
            f"{status['doctor'].get('names_injected_fault')}")

    analysis_ok = status["analysis"]["ok"]
    if args.skip_bench:
        status["bench"] = {"skipped": True}
        green = status["dryrun"]["ok"] and analysis_ok
    else:
        attempt = 0
        # Fresh attempts while wait budget remains; exactly one final
        # attempt (archive fallback allowed) once it runs out.  The
        # budget check re-runs AFTER each bench (a bench can take ~10
        # min; deciding only before it starts overshot --max-wait-s by a
        # sleep + a whole extra fresh attempt).
        last_chance = args.retry_sleep_s > args.max_wait_s
        while True:
            attempt += 1
            log(f"bench attempt {attempt}"
                + (" (final; archive fallback allowed)" if last_chance else ""))
            result = run_bench(allow_archive=last_chance)
            status["bench"] = result or {"error": "no output"}
            if bench_green(result):
                kind = ("ARCHIVED green (staleness "
                        f"{result.get('staleness_s', 0):.0f}s)"
                        if result.get("archived") else "green")
                log(f"bench {kind}: {result['value']:,} tok/s on "
                    f"{result['backend']}")
                break
            if last_chance:
                log("out of wait budget; bench stays red")
                break
            if time.time() - T0 + args.retry_sleep_s > args.max_wait_s:
                last_chance = True
                log("wait budget exhausted mid-attempt; one final attempt "
                    "with archive fallback, no sleep")
                continue
            log(f"bench red ({(result or {}).get('error', 'no output')}); "
                f"sleeping {args.retry_sleep_s:.0f}s before the retry")
            time.sleep(args.retry_sleep_s)
        green = (
            status["dryrun"]["ok"]
            and analysis_ok
            and bench_green(status.get("bench"))
        )

    if args.skip_perf:
        status["perf"] = {"skipped": True}
    else:
        log("reconciling bench vs cost-model prediction (report-only)")
        status["perf"] = run_perf(status.get("bench"))
        log(f"perf ok={status['perf']['ok']} "
            f"delta_pct={status['perf'].get('delta_pct')}")

    if args.skip_packed:
        status["packed"] = {"skipped": True}
    else:
        log("packed long-context census (report-only)")
        status["packed"] = run_packed_census()
        log(f"packed ok={status['packed']['ok']} "
            f"reduction={status['packed'].get('headline_reduction')}x "
            f"@ s={status['packed'].get('seq_len')}")

    if args.skip_kv:
        status["kv"] = {"skipped": True}
    else:
        log("sharded-embedding bench + reshard drill (report-only)")
        status["kv"] = run_kv()
        log(f"kv ok={status['kv']['ok']} "
            f"aggregate={status['kv'].get('aggregate_rows_per_s')} rows/s "
            f"reshard_recovery_s={status['kv'].get('reshard_recovery_s')} "
            f"lost_rows={status['kv'].get('reshard_lost_rows')}")

    if args.skip_serve:
        status["serve"] = {"skipped": True}
    else:
        log("serving bench: legacy vs paged gateway (report-only)")
        status["serve"] = run_serve()
        log(f"serve ok={status['serve']['ok']} "
            f"gateway={status['serve'].get('gateway_tokens_per_sec')} tok/s "
            f"speedup={status['serve'].get('speedup_vs_legacy')}x "
            f"servput={status['serve'].get('servput_pct')}%")

    if args.skip_serve_chaos:
        status["serve_chaos"] = {"skipped": True}
    else:
        log("serving-fleet failover drill: promotion vs cold spawn "
            "(report-only)")
        status["serve_chaos"] = run_serve_chaos()
        log(f"serve_chaos ok={status['serve_chaos']['ok']} "
            f"promoted={status['serve_chaos'].get('promoted_reform_pts')} "
            f"cold={status['serve_chaos'].get('cold_reform_pts')} "
            f"delta={status['serve_chaos'].get('delta_pts')} pts "
            f"brownout={(status['serve_chaos'].get('brownout') or {}).get('peak')}"
            f"->released="
            f"{(status['serve_chaos'].get('brownout') or {}).get('released')}")

    if args.skip_kv_ha:
        status["kv_ha"] = {"skipped": True}
    else:
        log("kv failover drill: promotion vs chain restore "
            "(report-only)")
        status["kv_ha"] = run_kv_ha()
        promo = status["kv_ha"].get("promotion") or {}
        restore = status["kv_ha"].get("chain_restore") or {}
        log(f"kv_ha ok={status['kv_ha']['ok']} "
            f"promotion={promo.get('unavailable_s')}s "
            f"chain_restore={restore.get('unavailable_s')}s "
            f"zero_loss={status['kv_ha'].get('zero_loss')}")

    if args.skip_trace:
        status["trace"] = {"skipped": True}
    else:
        log("tracing/SLO probe: sampled burst + reconstruction "
            "(report-only)")
        status["trace"] = run_trace()
        recon = status["trace"].get("reconstruction") or {}
        log(f"trace ok={status['trace']['ok']} "
            f"spans={status['trace'].get('span_total')} "
            f"recon_spans={recon.get('span_count')} "
            f"causal={recon.get('causal')}")

    if args.skip_observer:
        status["observer"] = {"skipped": True}
    else:
        log("fleet-observer probe: federation oracle + canary "
            "divergence (report-only)")
        status["observer"] = run_observer()
        log(f"observer ok={status['observer']['ok']} "
            f"divergence={status['observer'].get('divergence_verdicts')} "
            f"fleet_p50={status['observer'].get('fleet_p50')} "
            f"sources={status['observer'].get('fleetz_sources')}")

    if args.skip_warehouse:
        status["warehouse"] = {"skipped": True}
    else:
        log("warehouse backfill + report-CLI smoke (report-only)")
        status["warehouse"] = run_warehouse()
        log(f"warehouse ok={status['warehouse']['ok']} "
            f"ingested={status['warehouse'].get('ingested')}")

    if args.skip_brain:
        status["brain_plan"] = {"skipped": True}
    else:
        log("brain-plan capacity smoke: price a 2-replica fleet "
            "against backfilled history (report-only)")
        status["brain_plan"] = run_brain_plan()
        log(f"brain_plan ok={status['brain_plan']['ok']} "
            f"verdict={status['brain_plan'].get('verdict')} "
            f"headroom={status['brain_plan'].get('headroom_pct')}% "
            f"source={status['brain_plan'].get('capacity_source')}")

    status["telemetry"] = telemetry_snapshot()
    status["green"] = green
    with open(os.path.join(REPO, "GATE_STATUS.json"), "w") as f:
        json.dump(status, f, indent=2)
    log(f"GATE {'GREEN' if green else 'RED'} -> GATE_STATUS.json")
    sys.exit(0 if green else 1)


if __name__ == "__main__":
    main()
