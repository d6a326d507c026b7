"""XLA cost-model oracle: predicted step time / MFU without chips.

``scripts/aot_slice_compile.py`` proved the flagship programs compile
for real slice topologies and reads ``compiled.cost_analysis()``
flops/bytes per step.  This module holds that pipeline as a library
(the script imports from here) and the half that turns the numbers
into a *prediction*:

* a per-backend peak-FLOPs table;
* a calibration factor (achieved MFU) read from
  ``BENCH_LAST_GREEN.json`` where a checkout still has one, else from
  the newest measured TPU entry in the program's perf history, else a
  default;
* readers and an appender for ``perf_history.jsonl`` at the repo root
  (git-ignored; not the driver's ledger).  Nothing in
  the tree writes either file any more: a prediction made here is a
  planning input (``brain/decision``, ``auto/``), never a measurement
  (ROADMAP D1(b)).

Nothing here imports jax at module import time: the AOT helpers are
used from subprocesses that must pin the platform first.
"""

import json
import os
import time
from typing import Any, Dict, List, Optional

from dlrover_tpu.common.log import logger

# Peak dense bf16 FLOP/s per chip, by TPU generation (the names the
# planner prices topologies with).  v5e: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip.
PEAK_FLOPS = {
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

# Aggregate ICI bytes/s per chip (order-of-magnitude constants from the
# published interconnect specs; used only for modeled fractions, never
# for pass/fail gates).
ICI_BW_BYTES = {
    "v5e": 2.0e11,
    "v5p": 6.0e11,
    "v6e": 4.5e11,
}

# HBM bytes/s per chip (published memory-bandwidth specs).  Serving
# decode is bandwidth-bound — every generated token re-reads the
# weights plus the request's KV blocks — so the serving predictor
# splits prefill (FLOPs-bound) from decode (HBM-bound) on these.
HBM_BW_BYTES = {
    "v5e": 8.19e11,
    "v5p": 2.765e12,
    "v6e": 1.64e12,
}

# Per-chip HBM capacity (spec-sheet GiB).  The decision plane's layout
# feasibility filter needs capacity, not just bandwidth, and must stay
# importable without jax — so the table lives here rather than on
# ``auto.analyser.DeviceContext`` (which imports jax at module scope).
CHIP_HBM_CAPACITY_BYTES = {
    "v4": 32 << 30,
    "v5e": 16 << 30,
    "v5p": 95 << 30,
    "v6e": 32 << 30,
}

# When no green measurement exists to calibrate against, assume the
# flagship's achieved MFU class (round-2 measured 0.48 at bench shape;
# 0.40 is the conservative default for unmeasured programs).
DEFAULT_ASSUMED_MFU = 0.40


# ``jax.Device.device_kind`` of an ATTACHED chip -> its generation row
# above.  A number looked up for the device a process actually holds is
# keyed by what that device reports, never by the platform name: every
# TPU calls itself "tpu".  The strings are what the installed libtpu
# reports for each generation the tables price
# (``jax.experimental.topologies.get_topology_desc``: v4:2x2x1, v5e:2x2,
# v5p:2x2x1, v6e:2x2); a v5p calls itself plain "TPU v5".
DEVICE_KIND_GENERATION = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v6 lite": "v6e",
}


def _row(table: Dict[str, float], backend: str) -> float:
    try:
        return table[backend]
    except KeyError:
        raise KeyError(
            f"no cost-model row for chip generation {backend!r} "
            f"(known: {sorted(table)})"
        ) from None


def chip_spec(backend: str = "v5e") -> Dict[str, float]:
    """One row of the per-generation tables: peak FLOPs, ICI and HBM
    bandwidth, and HBM capacity for ``backend``.  An unknown generation
    raises ``KeyError``."""
    return {
        "backend": backend,
        "peak_flops": _row(PEAK_FLOPS, backend),
        "ici_bw_bytes": _row(ICI_BW_BYTES, backend),
        "hbm_bw_bytes": _row(HBM_BW_BYTES, backend),
        "hbm_capacity_bytes": _row(CHIP_HBM_CAPACITY_BYTES, backend),
    }


def attached_generation(device_kind: str = "") -> str:
    """Generation row of the attached device (``jax.devices()[0]``
    unless ``device_kind`` is given).  Raises ``KeyError`` naming the
    kind when it is not in :data:`DEVICE_KIND_GENERATION`: a peak
    assumed for a device nobody identified is not a measurement's
    denominator."""
    if not device_kind:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_KIND_GENERATION[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to costmodel.DEVICE_KIND_GENERATION with its source "
            f"(known: {sorted(DEVICE_KIND_GENERATION)})"
        ) from None


ENV_LEDGER_PATH = "DLROVER_PERF_LEDGER"
# The program's own history file.  Deliberately NOT the name of the
# driver's checked-in ledger: nothing in this package reads or writes
# that file.
LEDGER_BASENAME = "perf_history.jsonl"

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def repo_root() -> str:
    return _REPO_ROOT


def ledger_path() -> str:
    return os.environ.get(
        ENV_LEDGER_PATH, os.path.join(_REPO_ROOT, LEDGER_BASENAME)
    )


# ----------------------------------------------------------------------
# AOT compile + cost extraction (promoted from scripts/aot_slice_compile)

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

# HLO element bit widths for the census (bytes = ceil(elems * bits / 8)).
_HLO_DTYPE_BITS = {
    "pred": 8, "s4": 4, "u4": 4, "s8": 8, "u8": 8,
    "s16": 16, "u16": 16, "s32": 32, "u32": 32, "s64": 64, "u64": 64,
    "f8e4m3fn": 8, "f8e5m2": 8, "f8e4m3b11fnuz": 8, "f8e4m3fnuz": 8,
    "f8e5m2fnuz": 8, "bf16": 16, "f16": 16, "f32": 32, "f64": 64,
    "c64": 64, "c128": 128,
}

_HLO_SHAPE_RE = None  # compiled lazily; regex import stays top-level-free


def _hlo_result_bytes(result_part: str) -> int:
    """Total bytes of every typed buffer in an HLO result declaration
    (handles tuple results like ``(f32[8,128]{1,0}, f32[8,128]{1,0})``)."""
    import re

    global _HLO_SHAPE_RE
    if _HLO_SHAPE_RE is None:
        _HLO_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
    total = 0
    for dtype, dims in _HLO_SHAPE_RE.findall(result_part):
        bits = _HLO_DTYPE_BITS.get(dtype)
        if bits is None:
            continue
        elems = 1
        for d in dims.split(","):
            if d:
                elems *= int(d)
        total += (elems * bits + 7) // 8
    return total


def collective_census(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Count + size every collective in an optimized HLO dump.

    Returns ``{op: {"count": n, "bytes": b}}`` for each op in
    :data:`COLLECTIVE_OPS` that appears.  ``bytes`` sums the RESULT
    buffer sizes (for an all-gather that's the gathered output; for an
    all-reduce the reduced tensor), a stable proxy for bytes-on-the-wire
    that lets the perf gate diff baselines against WUS programs.  Async
    pairs count once: ``-start`` lines are counted, ``-done`` lines
    (which re-declare the same buffer) are skipped.
    """
    census: Dict[str, Dict[str, int]] = {}
    for line in hlo_text.splitlines():
        for op in COLLECTIVE_OPS:
            marker = None
            for suffix in ("(", "-start("):
                if f" {op}{suffix}" in line or f"={op}{suffix}" in line:
                    marker = f"{op}{suffix}"
                    break
            if marker is None:
                continue
            head = line.split(marker, 1)[0]
            # The result type sits between '=' and the op name.
            result_part = head.split("=", 1)[1] if "=" in head else head
            entry = census.setdefault(op, {"count": 0, "bytes": 0})
            entry["count"] += 1
            entry["bytes"] += _hlo_result_bytes(result_part)
            break
    return census


def predict_wus_delta(abstract_state, plan) -> Dict[str, Any]:
    """Predicted per-chip effect of a weight-update-sharding plan
    (``parallel/wus.py``) — what the AOT census should show.

    Two collective predictions, because the lowering is
    toolchain-dependent (see the wus module docstring):

    * ``ideal``: literal reduce-scatter + all-gather — same ring bytes
      as the one all-reduce it replaces (delta 0; the win is HBM+FLOPs);
    * ``observed``: this jaxlib's all-reduce + dynamic-slice + all-gather
      materialization — one extra G*(N-1)/N of gather traffic.

    A census that matches ``observed`` today and drifts toward ``ideal``
    after a toolchain upgrade is the ledger telling us XLA started
    fusing the scatter.
    """
    if plan is None:
        return {"enabled": False}
    import jax

    from dlrover_tpu.parallel import wus

    n = plan.n_replica
    scattered_grad_bytes = 0
    for ab, base_sh, grad_sh in zip(
        jax.tree.leaves(abstract_state.params),
        jax.tree.leaves(plan.base_params),
        jax.tree.leaves(plan.grad_shardings),
    ):
        if not hasattr(ab, "shape"):
            continue
        if getattr(base_sh, "spec", None) == getattr(grad_sh, "spec", None):
            continue  # leaf stayed in base layout; its update is replicated
        elems = 1
        for d in ab.shape:
            elems *= d
        scattered_grad_bytes += elems * ab.dtype.itemsize
    ring = scattered_grad_bytes * (n - 1) // n
    return {
        "enabled": True,
        "mode": plan.mode,
        "axes": list(plan.axes),
        "n_replica": n,
        "scattered_grad_bytes": scattered_grad_bytes,
        "opt_hbm_bytes_saved_per_chip": wus.scattered_bytes(
            abstract_state, plan
        ),
        "update_flops_factor": 1.0 / n,
        "collective_bytes_per_chip": {
            "baseline_all_reduce": 2 * ring,
            "ideal": {"reduce_scatter": ring, "all_gather": ring},
            "observed": {
                "all_reduce": 2 * ring,
                "all_gather": ring,
            },
            "overhead_vs_baseline": ring,
        },
    }


def abstract_sharded_state(model, optimizer, mesh, rules, batch_abs):
    """create_sharded_state's eval-shape half: the abstract TrainState
    with NamedShardings attached — enough to lower, nothing allocated."""
    import jax
    from flax import linen as nn
    from flax.linen import partitioning as nn_partitioning

    from dlrover_tpu.trainer.step import TrainState, use_mesh

    def _build(rng, ids):
        variables = model.init(rng, ids)
        params = variables["params"]
        extra = {k: v for k, v in variables.items() if k != "params"}
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=optimizer,
            variables=extra,
        )

    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
        # batch_abs entries are ShapeDtypeStructs: they must enter as
        # eval_shape ARGUMENTS (abstracted), not as closure captures a
        # traced model would try to index.  The rng key is created
        # INSIDE the traced function: a concrete jax.random.key() here
        # would initialize the default backend of a caller whose whole
        # point is compiling WITHOUT devices.
        abs_state = jax.eval_shape(
            lambda ids: _build(jax.random.key(0), ids),
            batch_abs["input_ids"],
        )
        specs = nn.get_partition_spec(abs_state)
        shardings = nn.logical_to_mesh_sharding(specs, mesh, list(rules))
    abs_state = nn.unbox(abs_state)
    shardings = nn.unbox(shardings)
    abs_with_sharding = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abs_state, shardings,
    )
    return abs_with_sharding, shardings


def _held_bytes(mem) -> Optional[int]:
    """What one chip must hold to run the program: arguments, outputs
    that alias no donated argument, and temporaries.  The temporaries
    alone read 0 for a small program, and leave out the state."""
    if mem is None:
        return None
    return int(
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
        + mem.temp_size_in_bytes
    )


def compile_and_analyze(lowered, name: str, topology: str,
                        n_params: int = 0) -> dict:
    """Shared compile + HLO/cost/memory extraction for the train-step
    programs: one analysis contract, one place to change it."""
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    txt = compiled.as_text()
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    return {
        "name": name,
        "topology": topology,
        "n_params": n_params,
        "ok": True,
        "compile_s": round(compile_s, 1),
        "collectives": sorted(
            {op for op in COLLECTIVE_OPS if op in txt}
        ),
        "collective_census": collective_census(txt),
        "flops_per_step": cost.get("flops"),
        "hbm_bytes_per_chip": _held_bytes(mem),
        "output_bytes": cost.get("bytes accessed output", None),
    }


# ----------------------------------------------------------------------
# Mask-aware attention FLOPs (packed long-context accounting)
#
# The 6·N·tokens formula below is PARAMETER FLOPs only — it misses the
# attention s² term entirely, which is exactly the term sequence packing
# shapes.  These helpers make the attention budget explicit: dense
# causal pays s² per row, a packed row pays Σᵢ sᵢ² over its documents
# (from the OBSERVED segment-length histogram, not an assumed mixture),
# so a packed run's predicted MFU/tokens-per-sec stops being dishonest.


def attention_pair_flops(
    pair_sum: float,
    num_heads: int,
    head_dim: int,
    num_layers: int,
    causal: bool = True,
    training: bool = True,
) -> float:
    """Attention matmul FLOPs for a (q, k)-pair budget ``pair_sum``.

    ``pair_sum`` is Σ s² over rows (dense) or Σᵢ sᵢ² over documents
    (packed).  Two matmuls (q·kᵀ and p·v) at 2·d MACs → 4·d FLOPs per
    pair per head per layer; causal halves the live pairs; training
    triples forward FLOPs (one forward + two backward matmul passes).
    """
    f = 4.0 * float(pair_sum) * num_heads * head_dim * num_layers
    if causal:
        f *= 0.5
    if training:
        f *= 3.0
    return f


def packed_pair_sum(hist: Dict[int, int]) -> float:
    """Σᵢ sᵢ² from a document-length histogram {length: count} (the
    output of ``data.packing.segment_histogram``)."""
    return float(sum(int(n) * int(n) * int(c) for n, c in hist.items()))


def packed_attention_summary(
    segment_ids,
    num_heads: int,
    head_dim: int,
    num_layers: int,
    causal: bool = True,
    training: bool = True,
) -> Dict[str, Any]:
    """Observed (b, s) segment ids → packed vs dense attention FLOPs.

    ``attn_flops_packed`` uses the mask-aware Σᵢ sᵢ² budget;
    ``attn_flops_dense`` is what the same batch would cost as dense
    causal rows; ``reduction`` is their ratio (the ≥2x acceptance
    number); ``packing_efficiency`` is real tokens over row capacity.
    """
    import numpy as np

    from dlrover_tpu.data.packing import segment_histogram

    seg = np.asarray(segment_ids)
    if seg.ndim == 1:
        seg = seg[None]
    b, s = seg.shape
    hist = segment_histogram(seg)
    packed_pairs = packed_pair_sum(hist)
    dense_pairs = float(b) * float(s) * float(s)
    kw = dict(
        num_heads=num_heads, head_dim=head_dim, num_layers=num_layers,
        causal=causal, training=training,
    )
    packed = attention_pair_flops(packed_pairs, **kw)
    dense = attention_pair_flops(dense_pairs, **kw)
    real = int((seg > 0).sum())
    return {
        "rows": int(b),
        "seq_len": int(s),
        "docs": int(sum(hist.values())),
        "real_tokens": real,
        "packing_efficiency": real / float(b * s) if b * s else 0.0,
        "segment_length_hist": {int(k): int(v) for k, v in hist.items()},
        "attn_flops_packed": packed,
        "attn_flops_dense": dense,
        "reduction": dense / packed if packed > 0 else float("inf"),
    }


def packed_vs_dense_prediction(
    n_params: int,
    segment_ids,
    num_heads: int,
    head_dim: int,
    num_layers: int,
    backend: str = "v5e",
    mfu: Optional[float] = None,
    repo: Optional[str] = None,
) -> Dict[str, Any]:
    """Predicted tokens/s for a packed batch vs the same batch priced as
    dense causal: parameter FLOPs (6·N·tokens) plus the mask-aware /
    dense attention term respectively.  Feeds
    ``StepPhaseProfiler.set_packed_prediction`` — a model output,
    labeled as such by every consumer.
    """
    attn = packed_attention_summary(
        segment_ids, num_heads, head_dim, num_layers
    )
    tokens = attn["rows"] * attn["seq_len"]
    base = 6.0 * float(n_params) * float(tokens)
    packed_pred = predict_tokens_per_sec(
        n_params, tokens_per_step=tokens, backend=backend,
        flops_per_step=base + attn["attn_flops_packed"],
        mfu=mfu, repo=repo,
    )
    dense_pred = predict_tokens_per_sec(
        n_params, tokens_per_step=tokens, backend=backend,
        flops_per_step=base + attn["attn_flops_dense"],
        mfu=mfu, repo=repo,
    )
    return {
        **attn,
        "tokens_per_step": tokens,
        "param_flops": base,
        "packed_pred_tok_s": packed_pred["predicted_tokens_per_sec"],
        "dense_pred_tok_s": dense_pred["predicted_tokens_per_sec"],
        "mfu_used": packed_pred["mfu_used"],
        "calibration_source": packed_pred["calibration_source"],
        "backend": backend,
    }


# ----------------------------------------------------------------------
# Calibration + prediction


def load_calibration(repo: Optional[str] = None) -> Dict[str, Any]:
    """The achieved-MFU calibration factor from the last green on-chip
    measurement.  Preference order: ``BENCH_LAST_GREEN.json`` (carries
    ``mfu`` directly), then the newest measured non-blind TPU entry in
    the ledger, then :data:`DEFAULT_ASSUMED_MFU`."""
    repo = repo or _REPO_ROOT
    green = os.path.join(repo, "BENCH_LAST_GREEN.json")
    try:
        with open(green) as f:
            rec = json.load(f)
        if rec.get("mfu"):
            return {
                "mfu": float(rec["mfu"]),
                "tokens_per_sec": float(rec.get("value", 0.0)),
                "n_params": int(rec.get("n_params", 0)),
                "source": "BENCH_LAST_GREEN.json",
            }
    except (OSError, ValueError, TypeError):
        pass
    for entry in reversed(read_ledger()):
        if (
            entry.get("measured")
            and not entry.get("blind")
            and entry.get("mfu")
            and entry.get("backend") == "tpu"
        ):
            return {
                "mfu": float(entry["mfu"]),
                "tokens_per_sec": float(entry.get("tokens_per_sec", 0.0)),
                "n_params": int(entry.get("n_params", 0)),
                "source": LEDGER_BASENAME,
            }
    return {
        "mfu": DEFAULT_ASSUMED_MFU,
        "tokens_per_sec": 0.0,
        "n_params": 0,
        "source": "assumed",
    }


def predict_step_time(flops_per_step: float, backend: str = "v5e",
                      mfu: Optional[float] = None,
                      repo: Optional[str] = None) -> Dict[str, Any]:
    """flops/step → predicted seconds/step on ``backend``."""
    peak = _row(PEAK_FLOPS, backend)
    cal = None
    if mfu is None:
        cal = load_calibration(repo)
        mfu = cal["mfu"]
    step_s = float(flops_per_step) / (peak * mfu)
    return {
        "predicted_step_s": step_s,
        "mfu_used": mfu,
        "peak_flops": peak,
        "calibration_source": cal["source"] if cal else "caller",
    }


def predict_tokens_per_sec(
    n_params: int,
    tokens_per_step: int = 8192,
    backend: str = "v5e",
    flops_per_step: Optional[float] = None,
    mfu: Optional[float] = None,
    repo: Optional[str] = None,
) -> Dict[str, Any]:
    """Predicted training throughput on ``backend``.

    Uses measured ``flops_per_step`` from ``compiled.cost_analysis()``
    when the caller has one (the AOT path), else the 6·N·tokens
    parameter-FLOPs estimate, the formula the calibration's MFU was
    reckoned with, so a prediction calibrated on a measured run
    round-trips to that run's own throughput.
    """
    if flops_per_step is None:
        flops_per_step = 6.0 * float(n_params) * float(tokens_per_step)
    pred = predict_step_time(flops_per_step, backend, mfu=mfu, repo=repo)
    step_s = pred["predicted_step_s"]
    pred["predicted_tokens_per_sec"] = (
        float(tokens_per_step) / step_s if step_s > 0 else 0.0
    )
    pred["flops_per_step"] = float(flops_per_step)
    pred["backend"] = backend
    return pred


def predict_serving_tokens_per_sec(
    n_params: int,
    prompt_tokens: int = 1024,
    gen_tokens: int = 64,
    slots: int = 8,
    backend: str = "v5e",
    kv_bytes_per_token: float = 0.0,
    param_bytes: Optional[float] = None,
    mfu: Optional[float] = None,
    repo: Optional[str] = None,
) -> Dict[str, Any]:
    """Predicted serving throughput on ``backend``: the prefill /
    decode split.

    Prefill is FLOPs-bound — 2·N parameter-FLOPs per prompt token
    (forward only; half the training constant), priced at peak·MFU
    like a training step.  Decode is HBM-bandwidth-bound — every
    batched decode tick re-reads the full weights once plus each
    active request's accumulated KV, and the weight read amortizes
    over ``slots`` concurrent requests.  Steady-state generated
    tokens/s is then ``gen / (t_prefill + gen·t_tick/slots)`` — the
    per-request device-time demand with prefill serialized and decode
    shared, the same roofline split vLLM-style gateways report.

    Returns TTFT (the prefill latency), TPOT (one decode tick) and
    the decode-bound fraction alongside the headline prediction.
    """
    peak = _row(PEAK_FLOPS, backend)
    hbm = _row(HBM_BW_BYTES, backend)
    cal = None
    if mfu is None:
        cal = load_calibration(repo)
        mfu = cal["mfu"]
    if param_bytes is None:
        param_bytes = 2.0 * float(n_params)  # bf16 weights
    prompt_tokens = max(1, int(prompt_tokens))
    gen_tokens = max(1, int(gen_tokens))
    slots = max(1, int(slots))

    # Prefill: forward-only parameter FLOPs over the whole prompt.
    prefill_flops = 2.0 * float(n_params) * float(prompt_tokens)
    t_prefill = prefill_flops / (peak * mfu)

    # Decode tick: one weight pass + the mean per-request KV context
    # (prompt plus half the generation, the average over the stream)
    # for every active slot.
    mean_ctx = float(prompt_tokens) + float(gen_tokens) / 2.0
    tick_bytes = (
        float(param_bytes)
        + float(slots) * mean_ctx * float(kv_bytes_per_token)
    )
    t_tick = tick_bytes / hbm

    t_decode_per_req = float(gen_tokens) * t_tick / float(slots)
    t_req = t_prefill + t_decode_per_req
    gen_tok_s = float(gen_tokens) / t_req if t_req > 0 else 0.0
    total_tok_s = (
        float(prompt_tokens + gen_tokens) / t_req if t_req > 0 else 0.0
    )
    return {
        "predicted_tokens_per_sec": gen_tok_s,
        "predicted_total_tokens_per_sec": total_tok_s,
        "ttft_s": t_prefill,
        "tpot_s": t_tick,
        "prefill_s": t_prefill,
        "decode_s": t_decode_per_req,
        "decode_bound_fraction": (
            t_decode_per_req / t_req if t_req > 0 else 0.0
        ),
        "prompt_tokens": prompt_tokens,
        "gen_tokens": gen_tokens,
        "slots": slots,
        "mfu_used": mfu,
        "peak_flops": peak,
        "hbm_bw_bytes": hbm,
        "backend": backend,
        "calibration_source": cal["source"] if cal else "caller",
    }


def wus_collective_fraction(
    wus_delta: Dict[str, Any],
    n_params: int,
    tokens_per_step: int = 8192,
    backend: str = "v5e",
    mfu: Optional[float] = None,
    repo: Optional[str] = None,
) -> Optional[float]:
    """Modeled fraction of device-step time spent in the WUS
    collectives: collective seconds (observed-lowering bytes over the
    ICI bandwidth constant) over collective + compute seconds.  Feeds
    ``StepPhaseProfiler.set_collective_fraction`` — a model, clearly
    labeled as such in every record it produces, because one fused XLA
    program exposes no host-visible boundary to time."""
    if not wus_delta.get("enabled"):
        return None
    observed = wus_delta["collective_bytes_per_chip"]["observed"]
    bw = _row(ICI_BW_BYTES, backend)
    t_coll = float(sum(observed.values())) / bw
    t_comp = predict_step_time(
        6.0 * float(n_params) * float(tokens_per_step),
        backend, mfu=mfu, repo=repo,
    )["predicted_step_s"]
    if t_coll + t_comp <= 0:
        return None
    return t_coll / (t_coll + t_comp)


# ----------------------------------------------------------------------
# The perf ledger


def append_ledger(entry: Dict[str, Any],
                  path: Optional[str] = None) -> Optional[str]:
    """Append one record to the append-only perf ledger (one
    ``os.write`` of one full line on an O_APPEND fd, same crash-safety
    contract as the event log).  Stamps ``ts`` when absent.  Never
    raises; returns the path written, or None on failure."""
    path = path or ledger_path()
    rec = dict(entry)
    rec.setdefault("ts", time.strftime("%Y-%m-%dT%H:%M:%S"))
    try:
        line = (json.dumps(rec, default=str) + "\n").encode()
        fd = os.open(path, os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
        try:
            os.write(fd, line)
        finally:
            os.close(fd)
        return path
    except (OSError, ValueError, TypeError) as e:
        logger.warning("perf ledger append failed: %s", e)
        return None


def read_ledger(path: Optional[str] = None) -> List[Dict[str, Any]]:
    """All ledger records, tolerating one torn trailing line."""
    path = path or ledger_path()
    out: List[Dict[str, Any]] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue  # torn trailing line
    except OSError:
        pass
    return out
