"""AOT-compile the flagship programs for REAL TPU slice topologies.

Round-5, VERDICT ask #6: the 8-virtual-CPU-device dryrun proves the
sharded programs execute; this proves the REAL programs compile with the
real XLA TPU compiler for real slice hardware — no chips needed.
``jax.experimental.topologies`` builds a device-less PJRT topology (e.g.
v5e 4x4) and ``jit(...).lower(...).compile()`` runs the full TPU
compilation pipeline against it, so layout/memory/collective lowering
are all exercised exactly as on the slice.

Programs:
  1. llama-7B-shape fsdp x tp train step on a v5e-16 (4x4) topology
     (BASELINE config #3's compile half, ~55s);
  2. a 65B-class GLM fsdp x tp train step on a 64-chip v5p topology
     (config #5's compile half, ~60s);
  3. llama-7B at a 131,072-token context, ring attention sp=8 x fsdp=4
     on a 32-chip v5p topology (the long-context recipe, ~85s — the
     slowest program);
  4. the Local-SGD int8 DCN outer sync on a genuine 2-slice (dcn, fsdp)
     multislice topology (num_slices=2, devices carrying slice_index);
  5. the weight-update-sharding evidence pair: llama-7B + int8 Adam on
     a dp=2 x fsdp=4 x tp=2 v5e-16 mesh, compiled with and without
     ``weight_update_sharding="scatter"`` — collective census delta and
     compiler-verified per-chip HBM drop (parallel/wus.py).

Prints one JSON line with every program's analysis (compiled bytes and
collectives, never a time on a chip); asserts the expected collectives
appear in the compiled HLO.  Tiny-config regression:
tests/test_aot_topology.py.

Usage: python scripts/aot_slice_compile.py  (no TPU needed: the topology
client never dials a device.)
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg):
    print(f"[aot +{time.time() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


T0 = time.time()


# The AOT pipeline lives in the telemetry cost model (shared with the
# decision plane's probe); the old private names stay as aliases for the
# program functions below.
from dlrover_tpu.telemetry.costmodel import (  # noqa: E402
    COLLECTIVE_OPS as _COLLECTIVE_OPS,
    abstract_sharded_state as _abstract_sharded_state,
    compile_and_analyze as _lib_compile_and_analyze,
)


def _compile_and_analyze(lowered, name: str, topology: str,
                         n_params: int = 0) -> dict:
    log("compiling (real XLA TPU pipeline)")
    return _lib_compile_and_analyze(lowered, name, topology, n_params)


def compile_llama7b_fsdp_tp(topo_name="v5e:4x4", fsdp=4, tp=4):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.trainer.step import data_sharding, make_train_step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topo_name)
    # build_mesh: the full axis set (size-1 dp/sp/... included) that the
    # preset rule tables reference.
    mesh = build_mesh(MeshConfig(fsdp=fsdp, tp=tp), list(topo.devices))
    cfg = LlamaConfig.llama2_7b(
        max_seq_len=2048,
        attention_impl="splash",
        scan_layers=True,  # production compile-time choice at depth 32
        # The compiler VERIFIES HBM: without these the program is
        # honestly rejected as OOM on a 16GB v5e chip (2GB materialized
        # logits + unremat'd activations; dots_saveable still keeps
        # 9.4GB of saved dot outputs across 32 layers).  This is the
        # memory-bound fit recipe at 7B-on-v5e-16: full remat + chunked
        # fused CE.
        remat_policy="full",
        fused_ce_chunks=8,
    )
    model = LlamaModel(cfg)
    rules = PRESET_RULES["fsdp_tp"]
    batch, seq = 8, 2048
    batch_abs = {
        "input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    opt = optax.chain(optax.clip_by_global_norm(1.0),
                      optax.adamw(3e-4, b2=0.95))
    log(f"llama-7B abstract state on {topo_name} mesh "
        f"fsdp={fsdp} tp={tp}")
    abs_state, shardings = _abstract_sharded_state(
        model, opt, mesh, rules, batch_abs
    )
    n_params = sum(
        int(np.prod(l.shape))
        for l in jax.tree.leaves(abs_state.params)
    )
    step = make_train_step(model, mesh, rules, shardings)
    dshard = data_sharding(mesh, rules)
    batch_abs = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=dshard)
        for k, v in batch_abs.items()
    }
    log(f"lowering 7B train step ({n_params / 1e9:.2f}B params)")
    from flax.linen import partitioning as nn_partitioning

    from dlrover_tpu.trainer.step import use_mesh

    # .jitted is the raw jit wrapper (the callable wraps it with the
    # rule-table context, which lowering needs in scope the same way).
    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
        lowered = step.jitted.lower(abs_state, batch_abs)
    return _compile_and_analyze(
        lowered, "llama7b_fsdp4_tp4_trainstep", topo_name, n_params
    )


def compile_llama7b_v6e():
    """Same flagship program, current-generation target: Trillium
    (v6e-16).  One GSPMD program, three TPU generations — the point of
    compiling against topologies instead of owned hardware."""
    r = compile_llama7b_fsdp_tp(topo_name="v6e:4x4", fsdp=4, tp=4)
    r["name"] = "llama7b_fsdp4_tp4_trainstep_v6e"
    return r


def compile_glm65b_v5p(topo_name="v5p:4x4x4", fsdp=8, tp=8):
    """BASELINE config #5's compile half: a 65B-class GLM (prefix-LM,
    GQA, hidden 8192 x 80 layers) sharded fsdp x tp over a 64-chip v5p
    topology.  v5p-256 is the production target; 4x4x4 is the largest
    topology that compiles in minutes on this 1-core host — the program
    is the same GSPMD program at a different axis size."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies

    from dlrover_tpu.models.glm import GLMConfig, GLMModel, glm_lm_loss
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.trainer.step import data_sharding, make_train_step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topo_name)
    mesh = build_mesh(MeshConfig(fsdp=fsdp, tp=tp), list(topo.devices))
    cfg = GLMConfig(
        vocab_size=65024,
        hidden_size=8192,
        intermediate_size=21760,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        max_seq_len=2048,
        param_dtype=jnp.bfloat16,  # 65B x f32 params would be 260GB
        logits_f32_output=False,
        scan_layers=True,
        # compiler-measured: without remat the saved prefix-LM scores
        # alone are 120GB/chip at this depth (see PERF.md)
        remat_policy="full",
    )
    model = GLMModel(cfg)
    rules = PRESET_RULES["fsdp_tp"]
    batch, seq = 8, 2048
    batch_abs = {
        "input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    opt = optax.chain(optax.clip_by_global_norm(1.0),
                      optax.adamw(1e-4, b2=0.95))
    log(f"GLM-65B abstract state on {topo_name} mesh fsdp={fsdp} tp={tp}")
    abs_state, shardings = _abstract_sharded_state(
        model, opt, mesh, rules, batch_abs
    )
    n_params = sum(
        int(np.prod(l.shape))
        for l in jax.tree.leaves(abs_state.params)
    )
    step = make_train_step(
        model, mesh, rules, shardings,
        loss_fn=lambda logits, b: glm_lm_loss(logits, b["labels"]),
    )
    dshard = data_sharding(mesh, rules)
    batch_abs = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=dshard)
        for k, v in batch_abs.items()
    }
    log(f"lowering GLM train step ({n_params / 1e9:.2f}B params)")
    from flax.linen import partitioning as nn_partitioning

    from dlrover_tpu.trainer.step import use_mesh

    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
        lowered = step.jitted.lower(abs_state, batch_abs)
    return _compile_and_analyze(
        lowered, "glm65b_fsdp8_tp8_trainstep", topo_name, n_params
    )


def compile_llama7b_ring_128k(topo_name="v5p:4x4x2", sp=8, fsdp=4):
    """The long-context compile half: llama-7B at a 131072-token context,
    ring attention over an 8-way sp axis (x fsdp=4 for the state) on a
    32-chip v5p topology.  Sequence-sharded activations + blockwise ring
    attention + full remat + chunked fused CE (the 128k-token logits
    tensor would be 8.4GB) — the whole long-context recipe, type-checked
    by the TPU compiler.  (The compiler rejected the first two drafts as
    real OOMs: full per-ring-step scores, then scan VJPs saving every
    tile's p matrix — both fixed in parallel/ring_attention.py.)"""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.trainer.step import data_sharding, make_train_step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topo_name)
    mesh = build_mesh(MeshConfig(fsdp=fsdp, sp=sp), list(topo.devices))
    seq = 131072
    cfg = LlamaConfig.llama2_7b(
        max_seq_len=seq,
        attention_impl="ring",
        scan_layers=True,
        remat_policy="full",
        fused_ce_chunks=16,
    )
    model = LlamaModel(cfg)
    rules = PRESET_RULES["fsdp_tp"]
    batch = fsdp  # ring shards batch over (dp, fsdp): one seq per group
    batch_abs = {
        "input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    opt = optax.chain(optax.clip_by_global_norm(1.0),
                      optax.adamw(3e-4, b2=0.95))
    log(f"llama-7B ring-128k abstract state on {topo_name} sp={sp}")
    abs_state, shardings = _abstract_sharded_state(
        model, opt, mesh, rules, batch_abs
    )
    step = make_train_step(model, mesh, rules, shardings)
    dshard = data_sharding(mesh, rules)
    batch_abs = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=dshard)
        for k, v in batch_abs.items()
    }
    log("lowering ring-128k train step")
    from flax.linen import partitioning as nn_partitioning

    from dlrover_tpu.trainer.step import use_mesh

    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
        lowered = step.jitted.lower(abs_state, batch_abs)
    return _compile_and_analyze(
        lowered, "llama7b_ring128k_sp8_trainstep", topo_name,
        sum(int(np.prod(l.shape))
            for l in jax.tree.leaves(abs_state.params)),
    )


def compile_local_sgd_sync(per_slice="v5e:4x4", n_slices=2):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dlrover_tpu.parallel.local_sgd import _int8_mean_over_dcn

    # A REAL multislice topology: num_slices slices of per_slice chips,
    # devices carrying slice_index — the dcn mesh axis maps to physical
    # slices, exactly the production (dcn, fsdp) layout.
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=per_slice, num_slices=n_slices
    )
    devs = sorted(
        topo.devices, key=lambda d: (getattr(d, "slice_index", 0), d.id)
    )
    multislice = len({getattr(d, "slice_index", 0) for d in devs}) > 1
    arr = np.array(devs).reshape(n_slices, -1)
    mesh = Mesh(arr, ("dcn", "fsdp"))
    fsdp = mesh.shape["fsdp"]

    # 7B-ish param tree sharded (dcn, fsdp): one big 2D leaf + a vector.
    deltas_abs = {
        "w": jax.ShapeDtypeStruct(
            (n_slices, 4096, 11008), jnp.float32,
            sharding=NamedSharding(mesh, P("dcn", "fsdp", None)),
        ),
        "b": jax.ShapeDtypeStruct(
            (n_slices, 4096), jnp.float32,
            sharding=NamedSharding(mesh, P("dcn", None)),
        ),
    }
    param_specs = {"w": P("fsdp", None), "b": P()}

    def sync(deltas):
        return _int8_mean_over_dcn(
            deltas, mesh, block_size=2048, param_specs=param_specs
        )

    log(f"lowering int8 DCN sync on ({n_slices}x{fsdp}) mesh "
        f"(multislice_topology={multislice})")
    lowered = jax.jit(sync).lower(deltas_abs)
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    txt = compiled.as_text()
    colls = sorted({op for op in _COLLECTIVE_OPS if op in txt})
    # The wire contract, as the multislice compiler actually lowers it:
    # cross-slice traffic becomes xla_megascale DCN send/recv pairs, and
    # the quantization promise is that their payloads are s8 (the f32
    # sends that remain are the per-block absmax scales).
    dcn_sends = [
        ln.strip()[:160] for ln in txt.splitlines()
        if "xla_megascale" in ln and ("send(" in ln or " recv(" in ln)
    ]
    int8_wire = any(
        ln.startswith(("%send", "%recv")) and "s8[" in ln.split("send(")[0]
        for ln in dcn_sends
    ) or any("s8[" in ln for ln in dcn_sends)
    return {
        "name": "local_sgd_int8_dcn_sync",
        "topology": f"{per_slice} x {n_slices} slices",
        "multislice_topology": multislice,
        "ok": True,
        "compile_s": round(compile_s, 1),
        "collectives": colls,
        "dcn_transport": "xla_megascale" if dcn_sends else "none-found",
        "dcn_transfers": dcn_sends[:8],
        "int8_on_wire": int8_wire,
    }


def compile_llama7b_wus(topo_name="v5p:4x4x4", dp=2, fsdp=8, tp=4):
    """The weight-update-sharding evidence pair: the SAME llama-7B
    int8-Adam train step compiled twice — replicated weight update vs
    ``weight_update_sharding="scatter"`` — so the collective-census
    delta and the per-chip HBM drop are compiler-verified, not modeled.

    Mesh dp=2 x fsdp=8 x tp=4 on a 64-chip v5p: the update scatters
    over both replica axes (N=16), and the int8 optimizer uses
    ``shards=16`` in BOTH variants so codes/absmax block boundaries
    align with partition boundaries and the HBM delta is pure layout,
    not padding.  v5p (95GB) rather than v5e: the int8 codec's
    codes/absmax strip their flax boxes, so the BASELINE keeps them
    fully replicated — ~13.4GB of moment codes per chip, an honest OOM
    on a 16GB v5e.  The pair needs the baseline to fit to measure the
    drop."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.optimizers.quantized import quantized_adamw
    from dlrover_tpu.parallel import wus
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.telemetry.costmodel import predict_wus_delta
    from dlrover_tpu.trainer.step import data_sharding, make_train_step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topo_name)
    mesh = build_mesh(MeshConfig(dp=dp, fsdp=fsdp, tp=tp),
                      list(topo.devices))
    n_replica = dp * fsdp
    cfg = LlamaConfig.llama2_7b(
        max_seq_len=2048,
        attention_impl="splash",
        scan_layers=True,
        remat_policy="full",
        fused_ce_chunks=8,
    )
    model = LlamaModel(cfg)
    rules = PRESET_RULES["fsdp_tp"]
    batch, seq = 8, 2048
    batch_abs = {
        "input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    opt = optax.chain(
        optax.clip_by_global_norm(1.0),
        quantized_adamw(3e-4, b2=0.95, shards=n_replica),
    )
    # Data shards over (dp, fsdp): batch dim must divide by N=16.
    batch = n_replica
    batch_abs = {
        k: jax.ShapeDtypeStruct((batch, seq), v.dtype)
        for k, v in batch_abs.items()
    }
    log(f"llama-7B int8 abstract state on {topo_name} mesh "
        f"dp={dp} fsdp={fsdp} tp={tp}")
    abs_state, shardings = _abstract_sharded_state(
        model, opt, mesh, rules, batch_abs
    )
    n_params = sum(
        int(np.prod(l.shape))
        for l in jax.tree.leaves(abs_state.params)
    )
    dshard = data_sharding(mesh, rules)
    batch_abs = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=dshard)
        for k, v in batch_abs.items()
    }
    from flax.linen import partitioning as nn_partitioning

    from dlrover_tpu.trainer.step import use_mesh

    log("lowering baseline (replicated weight update)")
    step_b = make_train_step(model, mesh, rules, shardings)
    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
        lowered = step_b.jitted.lower(abs_state, batch_abs)
    base = _compile_and_analyze(
        lowered, "llama7b_wus_baseline_int8", topo_name, n_params
    )

    plan = wus.make_plan(mesh, shardings, abs_state, mode="scatter")
    # Scatter mode stores params in the base layout; only the optimizer
    # state's input layout changes for the lowering.
    abs_wus = abs_state.replace(opt_state=jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abs_state.opt_state, plan.opt_shardings,
    ))
    log(f"lowering wus scatter step (N={plan.n_replica} over "
        f"{plan.axes})")
    step_w = make_train_step(model, mesh, rules, shardings,
                             weight_update_sharding=plan)
    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
        lowered = step_w.jitted.lower(abs_wus, batch_abs)
    wusr = _compile_and_analyze(
        lowered, "llama7b_wus_scatter_int8", topo_name, n_params
    )

    census_delta = {}
    for op in sorted(set(base.get("collective_census", {}))
                     | set(wusr.get("collective_census", {}))):
        b = base.get("collective_census", {}).get(op, {})
        w = wusr.get("collective_census", {}).get(op, {})
        census_delta[op] = {
            "count": w.get("count", 0) - b.get("count", 0),
            "bytes": w.get("bytes", 0) - b.get("bytes", 0),
        }
    hbm_b = base.get("hbm_bytes_per_chip")
    hbm_w = wusr.get("hbm_bytes_per_chip")
    return {
        "name": "llama7b_wus_int8_pair",
        "topology": topo_name,
        "mesh": {"dp": dp, "fsdp": fsdp, "tp": tp},
        "n_replica": n_replica,
        "ok": bool(base.get("ok") and wusr.get("ok")),
        "baseline": base,
        "wus": wusr,
        "hbm_drop_bytes_per_chip": (
            hbm_b - hbm_w if hbm_b and hbm_w else None
        ),
        "census_delta": census_delta,
        "predicted": predict_wus_delta(abs_state, plan),
    }


def _run_isolated(fn_name: str) -> dict:
    """Each program compiles in its own subprocess: an XLA CHECK failure
    SIGABRTs the whole process (seen with an invalid 3D v5e topology),
    and one program's crash must not cost the other's artifact.

    The libtpu compile-only client is PROCESS-EXCLUSIVE
    (/tmp/libtpu_lockfile): a concurrent libtpu user — e.g. the test
    suite's own tests/test_aot_topology.py — makes setup fail with
    UNAVAILABLE; that class retries after a wait."""
    import subprocess

    # jax_platforms=cpu BEFORE anything else: any stray concrete array
    # (an rng key, a module-level jnp constant) would otherwise
    # initialize (and take) an attached accelerator.  The topology
    # compile is unaffected — it builds an explicit platform="tpu"
    # compile-only client, not the default backend.
    code = (
        "import json, os, sys; sys.path.insert(0, {!r}); "
        # No GCP metadata server in this container: libtpu's MDS probe
        # retries for minutes per process before giving up.  Skip it.
        "os.environ.setdefault('TPU_SKIP_MDS_QUERY', '1'); "
        "import jax; jax.config.update('jax_platforms', 'cpu'); "
        "import importlib.util as iu; "
        "spec = iu.spec_from_file_location('aotmod', {!r}); "
        "m = iu.module_from_spec(spec); spec.loader.exec_module(m); "
        "print('\\n__RESULT__ ' + json.dumps(getattr(m, {!r})()))"
    ).format(REPO, os.path.abspath(__file__), fn_name)
    last = None
    for attempt in range(3):
        try:
            res = subprocess.run(
                [sys.executable, "-c", code], capture_output=True,
                text=True,
                timeout=2400,  # the 7B TPU-pipeline compile takes
                # ~15-20 min on this 1-core host; the compiler is
                # normally multi-threaded
            )
        except subprocess.TimeoutExpired:
            return {"name": fn_name, "ok": False, "error": "timeout 2400s"}
        with open(f"/tmp/aot_{fn_name}.err", "w") as f:
            f.write(res.stderr)  # full child stderr (OOM dumps are long)
        sys.stderr.write(res.stderr[-2000:])
        for line in reversed(res.stdout.splitlines()):
            if line.startswith("__RESULT__ "):
                return json.loads(line[len("__RESULT__ "):])
        last = {"name": fn_name, "ok": False,
                "error": f"rc={res.returncode}: {res.stderr[-300:]}"}
        blob = res.stdout + res.stderr
        if "UNAVAILABLE" in blob or "lockfile" in blob:
            log(f"{fn_name}: libtpu busy (attempt {attempt + 1}); "
                f"waiting 120s for the lock holder")
            time.sleep(120)
            continue
        break
    return last


def main():
    results = []
    for fn_name in ("compile_llama7b_fsdp_tp", "compile_llama7b_v6e",
                    "compile_glm65b_v5p", "compile_llama7b_ring_128k",
                    "compile_local_sgd_sync", "compile_llama7b_wus"):
        r = _run_isolated(fn_name)
        results.append(r)
        log(f"{r['name']}: ok={r['ok']}")
    print(json.dumps({"programs": results}))
    return 0 if all(r["ok"] for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
