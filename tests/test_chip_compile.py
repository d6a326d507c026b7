"""Compile the main path's kernels and its whole step for a DESCRIBED
TPU v5e — no chip attached, no chip time spent.

The TPU's compiler is installed wherever the tests run, and
``jax.experimental.topologies`` hands it a chip that is described, not
attached: what it refuses here, the chip would refuse.  Interpret mode
cannot show that — ``fused_adam8bit_update`` passed every interpret-mode
test for months while Mosaic had no lowering for its ``powf``; the splash
kernel passed them while a program over more than one device could not
contain it at all.  Widths are ``LlamaConfig.llama2_7b``'s.

Nothing runs, so nothing here is a result or a time.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

HBM_BYTES = 16e9  # one TPU v5e chip (Google Cloud documentation, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"the v5e topology cannot be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _grad_of(attn, **kw):
    def loss(q, k, v, *seg):
        out = attn(q, k, v, *seg, interpret=False, **kw)
        return out.astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


def _attention_case(impl, b, s, h, h_kv, d, segmented=False, **kw):
    def build(sds):
        if impl == "splash":
            from dlrover_tpu.ops.splash_attention import (
                splash_attention_gqa as attn,
            )
        else:
            from dlrover_tpu.ops.flash_attention import (
                flash_attention_gqa as attn,
            )
        args = [
            sds((b, s, h, d), jnp.bfloat16),
            sds((b, s, h_kv, d), jnp.bfloat16),
            sds((b, s, h_kv, d), jnp.bfloat16),
        ]
        if segmented:
            args.append(sds((b, s), jnp.int32))
        return _grad_of(attn, **kw), args

    return build


_LEAF = (4096, 11008)  # llama-7B gate/up/down projection
_N = _LEAF[0] * _LEAF[1]
_BLOCKS = _N // 256


def _quant(sds):
    from dlrover_tpu.ops.quantize_pallas import quantize_blockwise_pallas

    return quantize_blockwise_pallas, [sds(_LEAF, jnp.float32)]


def _dequant(sds):
    from dlrover_tpu.ops.quantize_pallas import dequantize_blockwise_pallas

    return (
        lambda codes, absmax: dequantize_blockwise_pallas(
            codes, absmax, _LEAF, mode="log"
        ),
        [sds((_N,), jnp.int8), sds((_BLOCKS,), jnp.float32)],
    )


def _grouped_matmul_case(m, k, n, groups):
    """fwd + both backward products of ``ops/grouped_matmul.py`` at one of
    lfm2moe.steady's shapes (the pairs buffer of 4 x 8192 tokens x top-4)."""
    def build(sds):
        from dlrover_tpu.ops.grouped_matmul import grouped_matmul

        def loss(lhs, rhs, sizes):
            out = grouped_matmul(lhs, rhs, sizes, interpret=False)
            return jnp.sum(out.astype(jnp.float32))

        return jax.grad(loss, (0, 1)), [
            sds((m, k), jnp.bfloat16), sds((groups, k, n), jnp.bfloat16),
            sds((groups,), jnp.int32)]
    return build


def _fused_adam(sds):
    from dlrover_tpu.ops.quantize_pallas import fused_adam8bit_update

    return fused_adam8bit_update, [
        sds(_LEAF, jnp.float32),
        sds((_N,), jnp.int8), sds((_BLOCKS,), jnp.float32),
        sds((_N,), jnp.int8), sds((_BLOCKS,), jnp.float32),
        sds((), jnp.int32),
    ]


KERNELS = {
    "splash_mha_s2048_d128": _attention_case("splash", 2, 2048, 32, 32, 128),
    "splash_gqa_32_8_s4096": _attention_case("splash", 1, 4096, 32, 8, 128),
    "splash_segmented_banded": _attention_case(
        "splash", 2, 2048, 32, 32, 128, segmented=True, max_segment_len=512
    ),
    # The head dim the library kernel was once gated off for.
    "splash_mha_d64": _attention_case("splash", 2, 2048, 12, 12, 64),
    "flash_blocks512_d128": _attention_case(
        "flash", 2, 2048, 32, 32, 128, block_q=512, block_kv=512
    ),
    "flash_segmented_d64": _attention_case(
        "flash", 2, 2048, 12, 12, 64, segmented=True,
        block_q=512, block_kv=512,
    ),
    # trinitymini.steady's two masks at its heads (32 / 4 of 128, s8192):
    # a window is part of the kernel's static mask, so each is a kernel.
    "splash_window2048_32_4_s8192": _attention_case(
        "splash", 1, 8192, 32, 4, 128, window=2048),
    "splash_causal_32_4_s8192": _attention_case(
        "splash", 1, 8192, 32, 4, 128),
    "flash_window512_d128": _attention_case(
        "flash", 2, 2048, 32, 4, 128, window=512, block_q=512,
        block_kv=512),
    "grouped_matmul_gate_up": _grouped_matmul_case(131072, 2048, 3584, 8),
    "grouped_matmul_down": _grouped_matmul_case(131072, 1792, 2048, 8),
    "quantize_blockwise": _quant,
    "dequantize_blockwise_log": _dequant,
    "fused_adam8bit_update": _fused_adam,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, topo, monkeypatch):
    """fwd + bwd where there is one; the compiled text must hold the
    Mosaic call — an interpret-mode lowering would compile too."""
    from jax.sharding import SingleDeviceSharding

    from dlrover_tpu.ops import quantize_pallas

    # The codec kernels pick interpret mode from the backend, which here
    # is the CPU: steer them as the attention cases steer theirs.
    monkeypatch.setattr(quantize_pallas, "pallas_interpret", lambda: False)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = KERNELS[name](sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ulysses_compiles_for_four_v5e_chips(topo, monkeypatch):
    """``attention_impl="ulysses"`` calls the flash kernel from inside its
    own shard_map, where every axis is already manual: the kernel's wrapper
    must not open a second region there.  sp=2 x tp=2, GQA 32/8, fwd+bwd."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlrover_tpu.ops import flash_attention
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh
    from dlrover_tpu.parallel.ulysses import ulysses_attention

    monkeypatch.setattr(flash_attention, "pallas_interpret", lambda: False)
    mesh = build_mesh(MeshConfig(dp=1, sp=2, tp=2), topo.devices[:4])
    sharding = NamedSharding(mesh, P(("dp", "fsdp"), "sp", "tp", None))

    def sds(heads):
        return jax.ShapeDtypeStruct(
            (2, 4096, heads, 128), jnp.bfloat16, sharding=sharding
        )

    def loss(q, k, v):
        return ulysses_attention(q, k, v).astype(jnp.float32).sum()

    with use_mesh(mesh):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            sds(32), sds(8), sds(8)
        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-to-all" in text


# Codestral-22B's widths (benchmarks/configs/codestral-22b.json).
_CODESTRAL = dict(
    vocab_size=32768, hidden_size=6144, intermediate_size=16384,
    num_heads=48, num_kv_heads=8, head_dim=128, rope_theta=1e6,
)


def _compile_step(topo, monkeypatch, chips, layers, seq=2048, **widths):
    """The step ``scripts/chip_smoke_worker.py`` builds, lowered for
    ``chips`` described devices."""
    import optax
    from flax.linen import partitioning as nn_partitioning

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.telemetry.costmodel import abstract_sharded_state
    from dlrover_tpu.trainer.step import data_sharding, make_train_step

    # The program asks the backend whether to donate and whether kernels
    # compile; the backend here is the CPU, the target is not.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if chips == 1:
        mesh_cfg, rules = MeshConfig(dp=-1), PRESET_RULES["dp"]
    else:
        mesh_cfg = MeshConfig(dp=1, fsdp=2, tp=2)
        rules = PRESET_RULES["fsdp_tp"]
    mesh = build_mesh(mesh_cfg, topo.devices[:chips])
    model = LlamaModel(LlamaConfig.llama2_7b(
        num_layers=layers, max_seq_len=seq, attention_impl="splash",
        scan_layers=False, logits_f32_output=False, **widths,
    ))
    batch = {
        k: jax.ShapeDtypeStruct(
            (4, seq), jnp.int32, sharding=data_sharding(mesh, rules)
        )
        for k in ("input_ids", "labels")
    }
    state, shardings = abstract_sharded_state(
        model, optax.adamw(3e-4, b2=0.95), mesh, rules, batch
    )
    step = make_train_step(model, mesh, rules, shardings)
    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
        return step.jitted.lower(state, batch).compile()


def _kernel_calls(text, name):
    """The custom-call instructions of a compiled program's text that run
    the Pallas kernel ``name`` (an instruction's line holds its target; the
    lines of metadata after it name the kernel several times more)."""
    return sum(
        'custom_call_target="tpu_custom_call"' in line and name in line
        for line in text.splitlines())


@pytest.mark.parametrize("config, params, attention_layers", [
    ("trinity-mini", 705_474_304, 5),
    ("lfm2-8b-a1b", 507_820_288, 1),
    ("granite-4.0-h-micro", 797_850_560, 1),
])
def test_a_hybrid_cells_step_fits_one_v5e_chip(
        topo, monkeypatch, config, params, attention_layers):
    """``trinitymini.steady``'s, ``lfm2moe.steady``'s and
    ``granite4h.steady``'s whole steps as the benchmark's worker builds
    them (the configuration's file, b2 / b4 / b1 x s8192, every layer
    recomputed): 12 B a parameter of arguments, the splash kernels
    (Trinity's two masks) and the routed cells' grouped products in one
    program, inside 15.75 GiB, with no conditional (a conditional's two
    branches count double in the compiler's own estimate of the memory),
    nothing that the compiler computes a second time for want of room,
    and one forward and one backward attention kernel a layer: the
    recomputation reads the forward pass's output and log-sum-exp
    (models/hybrid.py::recompute_policy) where it once ran the forward
    kernel again."""
    import json
    import os

    import optax
    from flax.linen import partitioning as nn_partitioning

    from dlrover_tpu.models.hybrid import HybridConfig, HybridModel
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh
    from dlrover_tpu.parallel.sharding import PRESET_RULES
    from dlrover_tpu.telemetry.costmodel import abstract_sharded_state
    from dlrover_tpu.trainer.step import data_sharding, make_train_step

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        f"{config}.json")
    with open(path) as f:
        cfg = json.load(f)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = HybridModel(HybridConfig(
        **{ours: cfg[theirs]
           for ours, theirs in cfg["model"]["from_source"].items()},
        **cfg["model"]["kwargs"]))
    mesh = build_mesh(MeshConfig(**cfg["mesh"]), topo.devices[:1])
    rules = PRESET_RULES[cfg["rules"]]
    batch = {
        k: jax.ShapeDtypeStruct(
            (cfg["batch"], cfg["seq"]), jnp.int32,
            sharding=data_sharding(mesh, rules))
        for k in ("input_ids", "labels")
    }
    opt = cfg["optimizer"]
    state, shardings = abstract_sharded_state(
        model, optax.adamw(opt["learning_rate"], b2=opt["b2"]), mesh, rules,
        batch)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    assert n_params == params
    step = make_train_step(model, mesh, rules, shardings)
    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
        compiled = step.jitted.lower(state, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert " conditional(" not in text and ".remat" not in text
    assert (_kernel_calls(text, "splash_mha_fwd"),
            _kernel_calls(text, "splash_mha_dkv")) == (
        attention_layers, attention_layers)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 12 * n_params  # + the batch
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{config} b{cfg['batch']} x s{cfg['seq']}: arguments "
          f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.3f} GiB")
    assert total < 15.75 * 2**30, f"{total / 2**30:.2f} GiB"
    # the fullest device holds over a quarter of the chip before a step
    assert mem.argument_size_in_bytes > 0.25 * HBM_BYTES


def test_train_step_fits_one_v5e_chip_with_its_snapshot(topo, monkeypatch):
    """Phase ``train`` of chip_smoke.py, at its widths and depth: state,
    temporaries and the Flash Checkpoint's transient device copy of the
    state (checkpoint/engine.py::_DeviceSnapshot) fit 16 GB together."""
    import chip_smoke

    spec = chip_smoke.TRAIN_SPECS[(1, False)]
    assert (spec["seq"], spec["batch"], spec["widths"]) == (2048, 4, {})
    compiled = _compile_step(topo, monkeypatch, 1, spec["layers"])
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # Donated: the new state lands in the old one's buffers.
    assert mem.alias_size_in_bytes > 0.99 * mem.argument_size_in_bytes
    snapshot = mem.argument_size_in_bytes
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + snapshot
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB"
    # ...and the cut is as shallow as the chip forces: one more layer
    # (202 M parameters, f32 + two Adam moments, twice) would not fit.
    per_layer = 12 * (4 * 4096 * 4096 + 3 * 4096 * 11008) * 2
    assert total + per_layer > HBM_BYTES


def test_sharded_train_step_compiles_for_four_v5e_chips(topo, monkeypatch):
    """A Mosaic kernel cannot be partitioned by GSPMD: over fsdp=2 x tp=2
    the step compiles only because the kernel runs under shard_map
    (ops/flash_attention.py::shard_kernel_over_mesh)."""
    compiled = _compile_step(topo, monkeypatch, 4, layers=1)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert any(
        op in text for op in ("all-gather", "reduce-scatter", "all-reduce")
    )
    # A quarter of the one-chip state per device, not all of it on one.
    mem = compiled.memory_analysis()
    n_params = 2 * 32000 * 4096 + 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert mem.argument_size_in_bytes < 12 * n_params / 4 * 1.05


def test_sharded_step_at_codestral_widths_keeps_its_activations_in_place(
        topo, monkeypatch):
    """``codestral22b.fsdp2tp2``'s step (depth 4, b4 x s4096): with the
    models' activation constraints in the program, GSPMD gathers weights
    and does not exchange activations.  Without them this compile held 50
    all-to-all (8.9 GiB a chip a step) and 9.11 GiB of temporaries."""
    import re

    compiled = _compile_step(
        topo, monkeypatch, 4, layers=4, seq=4096, **_CODESTRAL)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert len(re.findall(r"= \S+ all-to-all(?:-start)?\(", text)) <= 2
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 6 * 2**30, (
        f"{mem.temp_size_in_bytes / 2**30:.3f} GiB")
    # A quarter of the state per device.
    n_params = (2 * 32768 * 6144
                + 4 * (2 * 6144 * 6144 + 2 * 6144 * 1024 + 3 * 6144 * 16384))
    assert mem.argument_size_in_bytes < 12 * n_params / 4 * 1.05
