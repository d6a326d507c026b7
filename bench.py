"""Benchmark entry: prints ONE JSON line with the headline metric.

Metric: sustained training throughput (tokens/s) of the flagship
GPT-2-small-scale llama model on one TPU chip, bf16, seq=1024.
``vs_baseline`` compares against the recorded reference-class throughput for
this chip in BASELINE_TOKENS_PER_SEC; 1.0 = parity.

Exactly one JSON line is
emitted on stdout under every condition — success, TPU-unavailable CPU
fallback, exception, or wall-clock timeout — with an ``error`` field when
the number is not a clean TPU measurement.  Progress goes to stderr.
"""

import json
import os
import sys
import threading
import time

# Reference-class number: a well-tuned torch GPT-2-small on one A100-class
# chip sustains ~1.5e5 tok/s at seq 1024; scaled to a v5e chip's peak bf16
# FLOPs this lands near 1.0e5 tok/s.  Parity bar until a measured reference
# number replaces it.
BASELINE_TOKENS_PER_SEC = 1.0e5

BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "480"))

_emitted = False


def log(msg):
    print(f"[bench +{time.time() - T_START:6.1f}s] {msg}", file=sys.stderr, flush=True)


LAST_GREEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "BENCH_LAST_GREEN.json")
# An archive older than this cannot stand in for a fresh measurement —
# 12h bounds it to the current round's window (rounds are ~12h), so a
# previous round's number can never certify this round's code.  Shared
# with scripts/round_gate.py (which imports it from here).
MAX_ARCHIVE_STALENESS_S = 12 * 3600.0


_emit_lock = threading.Lock()


def _print_once(payload) -> bool:
    """The exactly-one-JSON-line contract, under a lock: the worker
    thread (archived fallback) and the main-thread watchdog can race."""
    global _emitted
    with _emit_lock:
        if _emitted:
            return False
        _emitted = True
    print(json.dumps(payload), flush=True)
    _append_ledger(payload)
    return True


def _append_ledger(payload):
    """Every emitted result — green, fallback, archived or partial —
    lands in the append-only perf ledger so the trajectory is recorded
    even when the round is blind.  Best-effort; stdout already carries
    the line of record."""
    try:
        from dlrover_tpu.telemetry import costmodel

        backend = payload.get("backend", "")
        entry = {
            "source": "bench",
            "backend": backend,
            "tokens_per_sec": payload.get("value"),
            "vs_baseline": payload.get("vs_baseline"),
            # A completed timing loop reports steps; a watchdog partial
            # or an init failure does not.
            "measured": "steps" in payload,
            "blind": bool(payload.get("blind"))
            or backend != "tpu",
            "unix": round(time.time(), 1),
        }
        for k in (
            "mfu", "n_params", "steps", "predicted_tpu_tokens_per_sec",
            "cpu_proxy_tokens_per_sec", "error", "archived",
        ):
            if payload.get(k) is not None:
                entry[k] = payload[k]
        costmodel.append_ledger(entry)
    except Exception as e:  # noqa: BLE001 — the ledger is advisory
        log(f"perf ledger append failed: {e}")


def emit(value, vs_baseline, backend, error=None, extra=None):
    """Print the single JSON result line (at most once)."""
    payload = {
        "metric": "train_throughput_gpt2s_1chip",
        "value": round(float(value), 1),
        "unit": "tokens/s",
        "vs_baseline": round(float(vs_baseline), 3),
        "backend": backend,
    }
    if error:
        payload["error"] = str(error)[:500]
    if extra:
        payload.update(extra)
    if not _print_once(payload):
        return
    if backend == "tpu" and not error:
        _archive_green(payload)


def _archive_green(payload):
    """Persist a green on-chip result so a later run without a chip
    degrades to 'stale green, flagged' instead of a CPU number."""
    try:
        import subprocess

        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(LAST_GREEN), capture_output=True, text=True,
            timeout=10,
        ).stdout.strip() or None
    except Exception:  # noqa: BLE001 — archive without the SHA
        sha = None
    rec = dict(payload)
    rec["archived_ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    rec["archived_unix"] = round(time.time(), 1)
    rec["archived_sha"] = sha
    try:
        with open(LAST_GREEN, "w") as f:
            json.dump(rec, f, indent=1)
        log(f"archived green result -> {os.path.basename(LAST_GREEN)}")
    except OSError as e:
        log(f"could not archive green result: {e}")


def _emit_archived_green(reason) -> bool:
    """On an unreachable accelerator, publish the round's last green
    on-chip measurement (staleness-flagged) instead of a CPU number.
    Returns False when no archive exists (caller then measures CPU) or
    when BENCH_NO_ARCHIVE_FALLBACK=1 — the gate sets that on its early
    retry attempts so an accelerator that comes back mid-wait still
    yields a FRESH measurement rather than the archive."""
    if os.environ.get("BENCH_NO_ARCHIVE_FALLBACK") == "1":
        return False
    try:
        with open(LAST_GREEN) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return False
    age = time.time() - rec.get("archived_unix", 0)
    if age > MAX_ARCHIVE_STALENESS_S:
        log(f"archived green is {age / 3600:.1f}h old (cap "
            f"{MAX_ARCHIVE_STALENESS_S / 3600:.0f}h); ignoring it")
        return False
    payload = {k: v for k, v in rec.items() if k != "archived_unix"}
    payload["archived"] = True
    payload["staleness_s"] = round(age, 1)
    payload["fallback_reason"] = str(reason)[:300]
    if _print_once(payload):
        log(f"emitted archived green ({age / 3600:.1f}h old) "
            f"instead of CPU fallback")
    return True


T_START = time.time()
_progress = {"value": 0.0, "backend": "none", "note": "timed out before backend init"}


def init_backend():
    """Initialize a JAX backend, retrying TPU, falling back to CPU."""
    import jax

    from dlrover_tpu.common.platform import configure_compile_cache

    configure_compile_cache()
    err = None
    for attempt in range(3):
        try:
            devs = jax.devices()
            platform = devs[0].platform
            log(f"backend up: {len(devs)} x {devs[0].device_kind} ({platform})")
            return jax, devs, platform, None
        except Exception as e:  # backend init failure
            err = e
            log(f"backend init attempt {attempt + 1}/3 failed: {e}")
            time.sleep(3 * (attempt + 1))
    # TPU (or default) backend unrecoverable — prefer the archived green,
    # else measure on host CPU so the driver still gets a real number.
    if _emit_archived_green(f"tpu unavailable: {err}"):
        return None, None, "archived", None
    log("falling back to CPU backend")
    try:
        jax.config.update("jax_platforms", "cpu")
        devs = jax.devices()
        return jax, devs, "cpu-fallback", f"tpu unavailable: {err}"
    except Exception as e2:
        raise RuntimeError(f"no backend at all: tpu={err}; cpu={e2}") from e2


def _work():
    try:
        _progress["note"] = "initializing backend"
        jax, devices, platform, backend_err = init_backend()
        if platform == "archived":
            return  # archived green already emitted
        _progress["backend"] = platform
        run(jax, devices, platform, backend_err)
    except Exception as e:
        import traceback

        traceback.print_exc(file=sys.stderr)
        emit(0.0, 0.0, _progress["backend"], error=f"{type(e).__name__}: {e}")


def main():
    """Watchdog-from-the-main-thread: backend init can hang inside a C
    call that never returns to the interpreter, so a SIGALRM handler
    would never run.  The measurement therefore runs on a daemon thread
    while the main thread only sleeps — it can always emit the
    partial/error line and hard-exit."""
    worker = threading.Thread(target=_work, name="bench", daemon=True)
    worker.start()
    worker.join(timeout=BUDGET_S)
    if worker.is_alive():
        log(f"wall-clock budget {BUDGET_S}s exhausted; emitting partial result")
        emit(
            _progress["value"],
            _progress["value"] / BASELINE_TOKENS_PER_SEC,
            _progress["backend"],
            error=f"timeout after {BUDGET_S}s: {_progress['note']}",
        )
        sys.stdout.flush()
        os._exit(0)


def run(jax, devices, platform, backend_err):
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.telemetry.costmodel import build_train_program

    _progress["note"] = "building model/state"
    # BENCH_FP8=dynamic|delayed measures the fp8 matmul path (the v5e has
    # no native fp8 MXU mode — on it this measures the cast overhead;
    # v5p+/Trillium get the ~2x matmul rate).
    fp8_mode = os.environ.get("BENCH_FP8", "")
    cfg = LlamaConfig(
        vocab_size=32000,
        hidden_size=768,
        intermediate_size=2048,
        num_layers=12,
        num_heads=12,
        num_kv_heads=12,
        max_seq_len=1024,
        # Measured on v5e (scripts/perf_probe.py): splash-attention kernel
        # beats the in-tree Pallas FA-2 by ~9%, unrolled layers beat
        # nn.scan by ~22% (XLA schedules across layer boundaries), bf16
        # logits into the loss save the f32 round trip — together
        # 92.8 -> 70.0 ms/step at batch 8.
        # CPU fallback uses fused-dot attention: the Pallas kernels run
        # in interpret mode off-TPU — orders of magnitude too slow to
        # even finish the warmup inside the bench budget.
        attention_impl="splash" if platform == "tpu" else "dot",
        # Per-shape best blocks: at the bench shape (s=1024) the round-3/4
        # sweeps measured q/kv 512 marginally but consistently ahead
        # (118.7-118.8k tok/s vs 117.9-118.2k at 1024); 1024 stays the
        # LlamaConfig default because it wins from s=4096 up.
        flash_block_q=512,
        flash_block_kv=512,
        # CPU fallback scans layers: unrolled 12-layer compile on host CPU
        # did not finish inside the round-3 budget.  The fallback number
        # is flagged via ``error`` either way; it just has to exist.
        scan_layers=platform != "tpu",
        logits_f32_output=False,
        use_fp8=bool(fp8_mode),
        fp8_scaling=fp8_mode or "dynamic",
    )
    model = LlamaModel(cfg)
    batch, seq = (8, 1024) if platform == "tpu" else (1, 512)

    mesh = build_mesh(MeshConfig(dp=-1), devices[:1])
    rules = PRESET_RULES["dp"]
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq + 1))
    sample = {
        "input_ids": jnp.asarray(ids[:, :-1], jnp.int32),
        "labels": jnp.asarray(ids[:, 1:], jnp.int32),
    }
    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(3e-4, b2=0.95))
    # One build path shared with perf_probe and the AOT cost model
    # (telemetry/costmodel.py) — the program measured here is the
    # program the oracle predicts.
    state, step_fn, sample = build_train_program(
        model, opt, mesh, rules, sample
    )
    log("state created; compiling train step")

    # Warmup/compile.  Sync via the loss value — the step chain makes it
    # depend on every preceding step.
    _progress["note"] = "compiling/warmup step"
    state, metrics = step_fn(state, sample)
    warm_loss = float(metrics["loss"])
    log(f"compiled; warmup loss={warm_loss:.4f}")

    # Calibration chunk (synced) sizes the measured run; the measured run
    # itself syncs ONCE at the end.
    _progress["note"] = "calibrating"
    t0 = time.perf_counter()
    for _ in range(3):
        state, metrics = step_fn(state, sample)
    float(metrics["loss"])
    est_step = (time.perf_counter() - t0) / 3
    n_steps = max(5, min(100, int(8.0 / max(est_step, 1e-4))))
    log(f"calibrated {est_step * 1000:.1f} ms/step; timing {n_steps} steps")

    # If SIGALRM fires inside the unsynced loop, what we have is the
    # calibration estimate, not a measurement — say so in the error field.
    _progress["note"] = (
        f"timing {n_steps} steps; value is a 3-step calibration ESTIMATE, "
        f"not a measurement"
    )
    _progress["value"] = batch * seq / max(est_step, 1e-4)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step_fn(state, sample)
    float(metrics["loss"])  # single sync: chain makes it depend on all steps
    total_dt = time.perf_counter() - t0
    total_steps = n_steps
    tokens_per_sec = batch * seq * total_steps / total_dt
    _progress["value"] = tokens_per_sec
    log(f"{total_steps} steps, {total_dt:.2f}s, {tokens_per_sec:,.0f} tok/s")
    # Model FLOPs estimate for MFU: 6 * params * tokens (fwd+bwd).
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    on_chip = platform == "tpu"
    mfu_denom = None
    if on_chip:
        # The attached chip's published bf16 peak, keyed by what the
        # device reports; an unknown device_kind raises.
        from dlrover_tpu.telemetry import costmodel

        mfu_denom = costmodel.chip_spec(
            costmodel.attached_generation(devices[0].device_kind)
        )["peak_flops"]
    extra = {"steps": total_steps, "n_params": int(n_params)}
    if mfu_denom:
        extra["mfu"] = round(6 * n_params * tokens_per_sec / mfu_denom, 4)
    vs_baseline = tokens_per_sec / BASELINE_TOKENS_PER_SEC
    if not on_chip:
        # A raw-CPU vs_baseline is meaningless (round-3/4/5 lesson:
        # 0.000/0.001 said nothing about the code).  Publish the
        # cost-model prediction for the TPU config plus a
        # history-calibrated CPU proxy instead, all flagged blind.
        from dlrover_tpu.telemetry import costmodel

        extra["blind"] = True
        extra["cpu_tokens_per_sec"] = round(tokens_per_sec, 1)
        pred = costmodel.predict_tokens_per_sec(
            int(n_params), tokens_per_step=8 * 1024, backend="v5e"
        )
        extra["predicted_tpu_tokens_per_sec"] = round(
            pred["predicted_tokens_per_sec"], 1
        )
        extra["prediction_mfu"] = round(pred["mfu_used"], 4)
        extra["prediction_calibration"] = pred["calibration_source"]
        proxy = costmodel.calibrated_cpu_proxy(tokens_per_sec)
        if proxy is not None:
            extra["cpu_proxy_tokens_per_sec"] = round(
                proxy["proxy_tokens_per_sec"], 1
            )
            extra["cpu_proxy_scale"] = round(proxy["scale"], 1)
            vs_baseline = (
                proxy["proxy_tokens_per_sec"] / BASELINE_TOKENS_PER_SEC
            )
        else:
            vs_baseline = (
                pred["predicted_tokens_per_sec"] / BASELINE_TOKENS_PER_SEC
            )
    emit(
        tokens_per_sec,
        vs_baseline,
        platform,
        error=backend_err,
        extra=extra,
    )


# ----------------------------------------------------------------------
# probe_packed: packed long-context attention-FLOP census
#
# ``python bench.py probe_packed`` sweeps document-length mixtures at
# s=8192, packs them with the real first-fit packer, and prices the
# resulting segment layout with the mask-aware cost model
# (telemetry/costmodel.packed_attention_summary): segment-sparse
# attention pays Σᵢ sᵢ² where dense causal pays b·s².  One ledger entry
# per mixture lands in the program's perf history with the same calibrated/blind
# machinery as the headline bench; one JSON summary line goes to stdout.
# The census is host-side arithmetic — it never touches a chip.

PACKED_SEQ = 8192
PACKED_ROWS = 8

# (name, target mean doc length, lognormal sigma; sigma=None -> uniform
# in [32, 2*mean)).  mean-1k lognormal is the headline mixture the
# acceptance bar (>= 2x attention-FLOP reduction) is judged on.
PACKED_MIXTURES = (
    ("lognormal_mean1k", 1024, 1.0),
    ("lognormal_mean2k", 2048, 0.8),
    ("uniform_short", 256, None),
)
PACKED_HEADLINE = "lognormal_mean1k"


def _mixture_lengths(mean, sigma, rng, total_tokens):
    """Document lengths for one mixture, enough to fill the row budget."""
    import math

    lengths = []
    budget = total_tokens
    while budget > 0:
        if sigma is None:
            n = int(rng.randint(32, 2 * mean))
        else:
            mu = math.log(mean) - sigma * sigma / 2.0
            n = int(rng.lognormal(mu, sigma))
        n = max(16, min(n, PACKED_SEQ))
        lengths.append(n)
        budget -= n
    return lengths


def probe_packed():
    """Packed vs dense attention-FLOP sweep at s=8192; see module note."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # host-side census
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.data.packing import lm_batch_from_rows, pack_documents
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.telemetry import costmodel

    backend = jax.default_backend()
    blind = backend != "tpu"
    # Flagship bench dims at long context: the FLOP census prices the
    # program bench.py would run at s=8192.
    cfg = LlamaConfig(
        vocab_size=32000,
        hidden_size=768,
        intermediate_size=2048,
        num_layers=12,
        num_heads=12,
        num_kv_heads=12,
        max_seq_len=PACKED_SEQ,
    )
    shapes = jax.eval_shape(
        LlamaModel(cfg).init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    head_dim = cfg.hidden_size // cfg.num_heads
    rng = np.random.RandomState(0)
    results = []
    for name, mean, sigma in PACKED_MIXTURES:
        lengths = _mixture_lengths(
            mean, sigma, rng, PACKED_ROWS * PACKED_SEQ
        )
        # Token values are irrelevant to the census; the packer only
        # needs lengths to lay out segment ids.
        rows = list(
            pack_documents(
                (np.ones(n, np.int32) for n in lengths), PACKED_SEQ
            )
        )[:PACKED_ROWS]
        batch = lm_batch_from_rows(rows)
        pred = costmodel.packed_vs_dense_prediction(
            n_params,
            batch["segment_ids"],
            cfg.num_heads,
            head_dim,
            cfg.num_layers,
            backend="v5e",
        )
        res = {
            "mixture": name,
            "rows": pred["rows"],
            "seq_len": pred["seq_len"],
            "docs": pred["docs"],
            "packing_efficiency": round(pred["packing_efficiency"], 4),
            "attn_flops_packed": pred["attn_flops_packed"],
            "attn_flops_dense": pred["attn_flops_dense"],
            "reduction": round(pred["reduction"], 3),
            "packed_pred_tok_s": round(pred["packed_pred_tok_s"], 1),
            "dense_pred_tok_s": round(pred["dense_pred_tok_s"], 1),
        }
        results.append(res)
        costmodel.append_ledger(
            {
                "source": "probe_packed",
                "backend": backend,
                # The census is a cost-model output, never a chip
                # timing: measured stays False even on a live TPU, and
                # a CPU host additionally blind-flags the entry.
                "measured": False,
                "blind": blind,
                "n_params": n_params,
                "calibration_source": pred["calibration_source"],
                "mfu_used": round(pred["mfu_used"], 4),
                "unix": round(time.time(), 1),
                **res,
            }
        )
        log(
            f"probe_packed {name}: {res['docs']} docs, "
            f"efficiency {res['packing_efficiency']:.3f}, "
            f"attention-FLOP reduction {res['reduction']:.2f}x, "
            f"predicted {res['packed_pred_tok_s']:,.0f} vs "
            f"{res['dense_pred_tok_s']:,.0f} tok/s"
        )
    headline = next(r for r in results if r["mixture"] == PACKED_HEADLINE)
    payload = {
        "metric": "packed_attention_flop_reduction",
        "value": headline["reduction"],
        "unit": "x_vs_dense_causal",
        "seq_len": PACKED_SEQ,
        "backend": backend,
        "blind": blind,
        "n_params": n_params,
        "headline_mixture": PACKED_HEADLINE,
        "ok": headline["reduction"] >= 2.0,
        "mixtures": results,
    }
    print(json.dumps(payload), flush=True)
    return payload


# ----------------------------------------------------------------------
# probe_kv: sharded embedding-store perf front
#
# ``python bench.py probe_kv`` fronts the KV perf history the same way
# the step bench fronts token throughput: it reads every ``kind="kv"``
# entry in the program's perf history (appended by scripts/kv_bench.py,
# kv_bench_mt.py and kv_bench_dist.py), summarizes the latest
# single-node floor, contended retention, and distributed scaling, and
# flags regressions against the best prior round.  ``--run`` first
# executes a small 2-shard kv_bench_dist so CI rounds without a prior
# ledger still produce a live number.

KV_SCALING_FLOOR = 2.5  # acceptance: 4-shard aggregate vs 1-shard


def probe_kv(run_bench: bool = False):
    from dlrover_tpu.telemetry import costmodel

    root = os.path.dirname(os.path.abspath(__file__))
    if run_bench:
        import subprocess

        subprocess.run(
            [
                sys.executable,
                os.path.join(root, "scripts", "kv_bench_dist.py"),
                "--dim", "16", "--keyspace", "30000", "--batch", "4096",
                "--iters", "8", "--shards", "1,2", "--reshard",
                "--out", os.path.join(root, "KV_BENCH_DIST.json"),
            ],
            check=True,
            cwd=root,
        )

    entries = [
        e for e in costmodel.read_ledger() if e.get("kind") == "kv"
    ]
    by_source = {}
    for e in entries:
        by_source.setdefault(e.get("source", "?"), []).append(e)

    def latest(source, key, **match):
        rows = [
            e for e in by_source.get(source, ())
            if key in e
            and all(e.get(k) == v for k, v in match.items())
        ]
        return rows[-1] if rows else None

    single = latest("kv_bench", "gather_rows_per_s")
    contended = latest("kv_bench_mt", "contended_gather_rows_per_s")
    dist_points = {
        n: latest("kv_bench_dist", "aggregate_rows_per_s", shards=n)
        for n in (1, 2, 4)
    }
    drill = latest("kv_bench_dist", "recovery_s", event="reshard_drill")

    scaling = None
    if dist_points.get(4) and dist_points.get(1):
        scaling = dist_points[4].get("scaling_vs_1shard")
    elif dist_points.get(2) and dist_points.get(1):
        scaling = dist_points[2].get("scaling_vs_1shard")

    payload = {
        "metric": "kv_aggregate_rows_per_s",
        "value": (
            dist_points[4]["aggregate_rows_per_s"]
            if dist_points.get(4)
            else (
                dist_points[2]["aggregate_rows_per_s"]
                if dist_points.get(2) else None
            )
        ),
        "unit": "rows/s",
        "ledger_entries": len(entries),
        "single_node_gather_rows_per_s": (
            single.get("gather_rows_per_s") if single else None
        ),
        "contended_retention": (
            contended.get("retention_vs_1thread") if contended else None
        ),
        "scaling_vs_1shard": scaling,
        "scaling_floor": KV_SCALING_FLOOR,
        "reshard_recovery_s": drill.get("recovery_s") if drill else None,
        "reshard_lost_rows": drill.get("lost_rows") if drill else None,
        "ok": bool(entries)
        and (scaling is None or scaling >= KV_SCALING_FLOOR)
        and (drill is None or drill.get("lost_rows", 1) == 0),
    }
    print(json.dumps(payload), flush=True)
    return payload


# ----------------------------------------------------------------------
# probe_serve: inference-gateway perf front
#
# ``python bench.py probe_serve`` fronts the serving perf history the
# way probe_kv fronts the embedding plane: it reads every
# ``kind="serve"`` entry in the program's perf history (appended by
# scripts/serve_bench.py), summarizes the latest legacy-vs-gateway
# comparison at the scaled mean-1k mixture, and carries the calibrated
# blind TPU serving prediction.  ``--run`` first executes the bench so
# CI rounds without a prior ledger still produce a live number.

SERVE_SPEEDUP_FLOOR = 2.0  # acceptance: gateway vs legacy slot pool


def probe_serve(run_bench: bool = False):
    from dlrover_tpu.telemetry import costmodel

    root = os.path.dirname(os.path.abspath(__file__))
    if run_bench:
        import subprocess

        subprocess.run(
            [
                sys.executable,
                os.path.join(root, "scripts", "serve_bench.py"),
                "--out", os.path.join(root, "SERVE_BENCH.json"),
            ],
            check=False,  # a red speedup still writes the ledger entry
            cwd=root,
        )

    entries = [
        e for e in costmodel.read_ledger() if e.get("kind") == "serve"
    ]
    latest = entries[-1] if entries else {}
    speedup = latest.get("speedup_vs_legacy")
    payload = {
        "metric": "serve_gateway_tokens_per_sec",
        "value": latest.get("gateway_tokens_per_sec"),
        "unit": "tok/s",
        "ledger_entries": len(entries),
        "legacy_tokens_per_sec": latest.get("legacy_tokens_per_sec"),
        "speedup_vs_legacy": speedup,
        "speedup_floor": SERVE_SPEEDUP_FLOOR,
        "servput_pct": latest.get("servput_pct"),
        "prefix_hit_tokens": latest.get("prefix_hit_tokens"),
        "kv_occupancy_ratio": latest.get("kv_occupancy_ratio"),
        "blind": latest.get("blind"),
        "predicted_tokens_per_sec":
            latest.get("predicted_tokens_per_sec"),
        "predicted_ttft_s": latest.get("predicted_ttft_s"),
        "predicted_tpot_s": latest.get("predicted_tpot_s"),
        "ok": bool(entries)
        and speedup is not None
        and speedup >= SERVE_SPEEDUP_FLOOR,
    }
    print(json.dumps(payload), flush=True)
    return payload


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "probe_packed":
        probe_packed()
    elif len(sys.argv) > 1 and sys.argv[1] == "probe_kv":
        probe_kv(run_bench="--run" in sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "probe_serve":
        probe_serve(run_bench="--run" in sys.argv[2:])
    else:
        main()
