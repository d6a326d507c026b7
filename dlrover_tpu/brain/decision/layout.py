"""Analytic layout planner: the AMP-style enumerator for the decision
plane.

The measured-by-default search (``auto/engine/search.py``) enumerates
``dp×fsdp×tp×sp`` and dry-runs the top-K — correct but expensive, and
blind to pipeline/expert axes, remat policy and grad-accum.  This
planner closes ROADMAP item 3 the AMP way (arXiv 2210.07297): expand
the space to ``pp×dp×fsdp×ep×sp×tp`` plus remat and grad-accum, score
every candidate with the calibrated analytic cost model from
``telemetry/costmodel.py`` (achieved-MFU calibration, per-generation
peak FLOPS/ICI/HBM tables), then confirm only the top-K with the AOT
compile probe's real XLA cost/memory and cross-check against
``warehouse.best_known_config`` history.

Everything here is deterministic and jax-free at import time (the AOT
probe is an injected callable): a plan must be reproducible from its
warehouse inputs alone, which DLR013 enforces over this package.
"""

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from dlrover_tpu.common.log import logger
from dlrover_tpu.telemetry import costmodel

MESH_AXES = ("pp", "dp", "fsdp", "ep", "sp", "tp")

# Remat recompute overhead: rematerialization replays roughly one extra
# forward pass, and forward is ~1/3 of the fwd+bwd FLOPs.
_REMAT_COMPUTE_FACTOR = 4.0 / 3.0
# Activation footprint divisor under remat — the same /5 the analyser's
# HBM model uses, so both filters agree on feasibility.
_REMAT_ACT_DIVISOR = 5.0

# Keep only this fraction of chip HBM for the plan (XLA scratch, infeed
# and fragmentation eat the rest) — search.py's 0.9 feasibility margin.
HBM_HEADROOM = 0.9


@dataclass
class LayoutProfile:
    """The jax-free slice of ``auto.analyser.ModelProfile`` the planner
    scores on, plus the MoE expert count the analyser profile lacks."""

    num_params: int
    batch_size: int
    seq_len: int
    num_layers: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    param_bytes: int = 0
    flops_per_token: float = 0.0
    num_experts: int = 0

    def __post_init__(self):
        if not self.param_bytes:
            self.param_bytes = 2 * int(self.num_params)  # bf16
        if not self.flops_per_token:
            # Dense-transformer rule of thumb (same as the analyser).
            self.flops_per_token = 6.0 * float(self.num_params)

    @classmethod
    def from_model_profile(cls, profile: Any,
                           num_experts: int = 0) -> "LayoutProfile":
        """Adapt an ``auto.analyser.ModelProfile`` (duck-typed; no
        import of the jax-heavy module here)."""
        return cls(
            num_params=int(profile.num_params),
            batch_size=int(profile.batch_size),
            seq_len=int(profile.seq_len),
            num_layers=int(profile.num_layers),
            hidden_size=int(profile.hidden_size),
            num_heads=int(profile.num_heads),
            num_kv_heads=int(profile.num_kv_heads),
            param_bytes=int(profile.param_bytes),
            flops_per_token=float(profile.flops_per_token),
            num_experts=int(num_experts),
        )

    def flops_per_step(self) -> float:
        return self.flops_per_token * self.batch_size * self.seq_len

    def tokens_per_step(self) -> int:
        return int(self.batch_size) * int(self.seq_len)


@dataclass
class LayoutCandidate:
    """One point in the layout space with its analytic score."""

    mesh: Dict[str, int]
    remat: bool
    grad_accum: int
    est_step_s: float = 0.0
    compute_s: float = 0.0
    comm_s: float = 0.0
    bubble_s: float = 0.0
    hbm_bytes: float = 0.0
    feasible: bool = True
    probe: Optional[Dict[str, Any]] = None  # AOT confirmation, top-K only

    def key(self) -> str:
        axes = "x".join(str(self.mesh.get(a, 1)) for a in MESH_AXES)
        return f"{axes}/remat={int(self.remat)}/ga={self.grad_accum}"

    def as_dict(self) -> Dict[str, Any]:
        d = {
            "mesh": dict(self.mesh),
            "remat": bool(self.remat),
            "grad_accum": int(self.grad_accum),
            "est_step_s": self.est_step_s,
            "compute_s": self.compute_s,
            "comm_s": self.comm_s,
            "bubble_s": self.bubble_s,
            "hbm_bytes": self.hbm_bytes,
            "feasible": bool(self.feasible),
            "key": self.key(),
        }
        if self.probe is not None:
            d["probe"] = self.probe
        return d


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def enumerate_layouts(
    profile: LayoutProfile,
    n_devices: int,
    max_pp: int = 4,
    max_tp: int = 8,
    max_sp: int = 4,
    grad_accums: Tuple[int, ...] = (1, 2, 4),
) -> List[LayoutCandidate]:
    """Every feasible ``pp×dp×fsdp×ep×sp×tp`` factorization of the
    device count, crossed with remat policy and grad-accum.

    Constraints mirror ``auto/engine/search.py`` (tp divides heads and
    kv-heads, sp divides seq-len and kv-heads, dp·fsdp bounded by the
    microbatch) plus the pipeline/expert axes the search lacks (pp
    divides layers; ep divides experts and rides the dp axis).
    """
    cands: List[LayoutCandidate] = []
    kv = max(profile.num_kv_heads, 1)
    heads = max(profile.num_heads, 1)
    layers = max(profile.num_layers, 1)
    for pp in _divisors(n_devices):
        if pp > max_pp or layers % pp:
            continue
        rest_pp = n_devices // pp
        for tp in _divisors(rest_pp):
            if tp > max_tp or heads % tp or kv % tp:
                continue
            rest_tp = rest_pp // tp
            for sp in _divisors(rest_tp):
                if sp > max_sp or profile.seq_len % sp or kv % sp:
                    continue
                rest_sp = rest_tp // sp
                for fsdp in _divisors(rest_sp):
                    dp = rest_sp // fsdp
                    # Expert parallelism rides the dp axis: ep ranks
                    # each hold num_experts/ep experts.
                    eps = [1]
                    if profile.num_experts > 1:
                        eps = [e for e in _divisors(profile.num_experts)
                               if dp % e == 0]
                    for ep in eps:
                        for ga in grad_accums:
                            if profile.batch_size % ga:
                                continue
                            micro = profile.batch_size // ga
                            if dp * fsdp > micro:
                                continue
                            for remat in (False, True):
                                cands.append(LayoutCandidate(
                                    mesh={"pp": pp, "dp": dp,
                                          "fsdp": fsdp, "ep": ep,
                                          "sp": sp, "tp": tp},
                                    remat=remat,
                                    grad_accum=ga,
                                ))
    return cands


def estimate_layout_hbm(
    profile: LayoutProfile,
    cand: LayoutCandidate,
    zero_level: int = 3,
    dtype_bytes: int = 2,
) -> float:
    """Per-chip HBM for a candidate — the analyser's model extended
    with grad-accum microbatching and the ep expert shard."""
    m = cand.mesh
    tp, fsdp = m.get("tp", 1), m.get("fsdp", 1)
    dp, sp, pp = m.get("dp", 1), m.get("sp", 1), m.get("pp", 1)
    ep = m.get("ep", 1)

    model_shard = tp * pp * (fsdp if zero_level >= 3 else 1) * ep
    opt_shard = tp * pp * fsdp * ep
    params = profile.param_bytes / model_shard
    grads = profile.param_bytes / model_shard
    moments = 2 * 4 * profile.num_params / opt_shard  # f32 adam m+v

    micro = profile.batch_size / max(cand.grad_accum, 1)
    tokens = micro * profile.seq_len / max(dp * fsdp * sp, 1)
    act_per_layer = 14 * tokens * max(profile.hidden_size, 1) * dtype_bytes
    acts = act_per_layer * max(profile.num_layers, 1) / max(pp, 1)
    if cand.remat:
        acts /= _REMAT_ACT_DIVISOR
    return params + grads + moments + acts


def score_layout(
    profile: LayoutProfile,
    cand: LayoutCandidate,
    spec: Dict[str, float],
    mfu: float,
    n_devices: int,
) -> LayoutCandidate:
    """Fill the candidate's analytic step-time decomposition: compute
    at calibrated MFU, fsdp/tp/ep collectives at ICI bandwidth, and
    the pipeline bubble — the roofline split the analyser uses, priced
    off the per-generation tables instead of a live DeviceContext."""
    m = cand.mesh
    peak = spec["peak_flops"]
    bw = max(spec["ici_bw_bytes"], 1.0)

    compute = profile.flops_per_step() / (peak * mfu * max(n_devices, 1))
    if cand.remat:
        compute *= _REMAT_COMPUTE_FACTOR

    comm = 0.0
    fsdp, tp, dp = m.get("fsdp", 1), m.get("tp", 1), m.get("dp", 1)
    pp, ep, ga = m.get("pp", 1), m.get("ep", 1), cand.grad_accum
    if fsdp > 1:
        # all-gather fwd + all-gather bwd + reduce-scatter grads per
        # microbatch: weights move once per accumulation step.
        comm += 3 * profile.param_bytes / bw * ga
    if tp > 1:
        per_layer = (
            4 * profile.batch_size * profile.seq_len
            * max(profile.hidden_size, 1) * 2
            / max(dp * fsdp, 1)
        )
        comm += profile.num_layers * per_layer * (tp - 1) / tp / bw
    if ep > 1:
        # MoE dispatch/combine all-to-all: activations cross the ep
        # group twice per layer.
        per_layer = (
            2 * profile.batch_size * profile.seq_len
            * max(profile.hidden_size, 1) * 2
            / max(dp * fsdp, 1)
        )
        comm += profile.num_layers * per_layer * (ep - 1) / ep / bw

    # GPipe bubble: (pp-1)/(m+pp-1) of the step with m microbatches.
    bubble = 0.0
    if pp > 1:
        micro_n = max(ga, 1)
        bubble = (compute + comm) * (pp - 1) / (micro_n + pp - 1)

    cand.compute_s = compute
    cand.comm_s = comm
    cand.bubble_s = bubble
    cand.est_step_s = compute + comm + bubble
    cand.hbm_bytes = estimate_layout_hbm(profile, cand)
    cand.feasible = (
        cand.hbm_bytes < HBM_HEADROOM * spec["hbm_capacity_bytes"]
    )
    return cand


def plan_layout(
    profile: LayoutProfile,
    n_devices: int,
    backend: str = "v5e",
    top_k: int = 3,
    mfu: Optional[float] = None,
    repo: Optional[str] = None,
    probe: Optional[Callable[[LayoutCandidate], Dict[str, Any]]] = None,
    warehouse: Optional[Any] = None,
    model_config: Optional[Dict[str, Any]] = None,
    max_pp: int = 4,
    max_tp: int = 8,
    max_sp: int = 4,
    grad_accums: Tuple[int, ...] = (1, 2, 4),
) -> Dict[str, Any]:
    """The decision-plane layout proposal.

    Enumerate → score analytically (calibrated MFU + generation
    tables) → AOT-probe the top-K when a probe callable is injected
    (real XLA flops/memory override the analytic HBM check) →
    cross-check the winner against ``warehouse.best_known_config``
    history for the same model/mesh fingerprint.
    """
    cal_source = "caller"
    if mfu is None:
        cal = costmodel.load_calibration(repo)
        mfu, cal_source = cal["mfu"], cal["source"]
    spec = costmodel.chip_spec(backend)

    cands = enumerate_layouts(
        profile, n_devices, max_pp=max_pp, max_tp=max_tp,
        max_sp=max_sp, grad_accums=grad_accums,
    )
    for c in cands:
        score_layout(profile, c, spec, mfu, n_devices)
    feasible = [c for c in cands if c.feasible]
    pool = feasible or cands
    pool.sort(key=lambda c: c.est_step_s)
    top = pool[:max(top_k, 1)]

    if probe is not None:
        capacity = HBM_HEADROOM * spec["hbm_capacity_bytes"]
        for c in top:
            try:
                c.probe = dict(probe(c) or {})
            except Exception as e:  # probe is best-effort confirmation
                c.probe = {"error": str(e)}
                continue
            hbm = c.probe.get("hbm_bytes_per_chip")
            if isinstance(hbm, (int, float)) and hbm > 0:
                c.probe["fits_hbm"] = bool(hbm < capacity)
                if not c.probe["fits_hbm"]:
                    c.feasible = False
        # A probe-refuted leader yields to the next confirmed layout.
        top.sort(key=lambda c: (not c.feasible, c.est_step_s))

    best = top[0] if top else None
    history = None
    if warehouse is not None and best is not None:
        try:
            fp_payload = {
                "model": model_config or {},
                "mesh": {"n_devices": int(n_devices),
                         "backend": backend},
            }
            from dlrover_tpu.brain.warehouse import config_fingerprint
            known = warehouse.best_known_config(
                config_fingerprint(fp_payload)
            )
            if known:
                history = {
                    "fingerprint": known.get("fingerprint"),
                    "score": known.get("score"),
                    "score_source": known.get("score_source"),
                    "config": known.get("config"),
                    "agrees": _history_agrees(best, known),
                }
        except Exception as e:
            logger.debug("layout history cross-check failed: %s", e)

    result = {
        "backend": backend,
        "n_devices": int(n_devices),
        "mfu": float(mfu),
        "calibration_source": cal_source,
        "n_candidates": len(cands),
        "n_feasible": len(feasible),
        "best": best.as_dict() if best else None,
        "top_k": [c.as_dict() for c in top],
        "history": history,
    }
    if best is not None:
        logger.info(
            "brain layout plan: %s est %.4fs/step (%d candidates, "
            "%d feasible, mfu=%.2f/%s)",
            best.key(), best.est_step_s, len(cands), len(feasible),
            mfu, cal_source,
        )
    return result


def _history_agrees(best: LayoutCandidate,
                    known: Dict[str, Any]) -> Optional[bool]:
    """Does warehouse history's best-known config name the same mesh?
    None when history carries no comparable mesh record."""
    cfg = known.get("config")
    if not isinstance(cfg, dict):
        return None
    mesh = cfg.get("mesh") or cfg.get("mesh_sizes")
    if not isinstance(mesh, dict):
        return None
    return all(
        int(mesh.get(a, 1)) == int(best.mesh.get(a, 1))
        for a in MESH_AXES if a in mesh
    )
