"""Weight-update sharding (``parallel/wus.py``): plan construction,
CPU-mesh numerical equivalence against the replicated update (f32 and
int8 blockwise Adam), HLO layout evidence, and reform -> flash-restore
with the 1/N-sharded optimizer state.

Lowering honesty (see the wus module docstring): this jaxlib's GSPMD
pipeline materializes "partial gradient -> scattered layout" as
``all-reduce + dynamic-slice`` rather than a literal ``reduce-scatter``
op, so the HLO assertions here check for the param all-gather plus a
grad reduction in either form — asserting a literal reduce-scatter
would test the toolchain, not the plan.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.optimizers.quantized import (
    dequantize_blockwise,
    quantize_blockwise,
    quantized_adamw,
)
from dlrover_tpu.parallel import wus
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import PRESET_RULES
from dlrover_tpu.trainer.step import (
    create_sharded_state,
    data_sharding,
    make_train_step,
)

pytestmark = pytest.mark.wus

TINY = LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=32,
)


@pytest.fixture(scope="module")
def mesh22():
    devs = jax.devices()
    assert len(devs) >= 4
    return build_mesh(MeshConfig(dp=2, fsdp=2), devs[:4])


def _batch():
    ids = jnp.tile(jnp.arange(32, dtype=jnp.int32)[None], (8, 1))
    return {"input_ids": ids, "labels": ids}


def _fit(model, tx, mesh, rules, batch, wus_mode=None):
    """State + jitted step, with or without a WUS plan."""
    rng = jax.random.PRNGKey(0)
    if wus_mode:
        state, sh, plan = create_sharded_state(
            model, tx, mesh, rules, rng, batch,
            weight_update_sharding=wus_mode,
        )
        step = make_train_step(model, mesh, rules, sh,
                               weight_update_sharding=plan)
        return state, step, plan
    state, sh = create_sharded_state(model, tx, mesh, rules, rng, batch)
    return state, make_train_step(model, mesh, rules, sh), None


class TestShardedCodec:
    """int8 blockwise codec with per-shard padding (optimizers/quantized.py):
    each of the N segments pads independently so block boundaries align
    with partition boundaries when the state is scattered over N."""

    def test_round_trip_and_idempotence(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
        for shards in (1, 2, 4):
            codes, scales = quantize_blockwise(x, 256, "linear", shards)
            assert codes.size % shards == 0
            assert scales.size % shards == 0
            back = dequantize_blockwise(
                codes, scales, x.shape, 256, "linear", shards
            )
            assert float(jnp.max(jnp.abs(back - x))) < 0.05
            codes2, scales2 = quantize_blockwise(back, 256, "linear", shards)
            assert jnp.array_equal(codes, codes2)
            assert jnp.array_equal(scales, scales2)

    def test_shard_segments_decode_independently(self):
        """Partition boundary = segment boundary: each 1/N slice of the
        codes+scales decodes its own 1/N slice of the value, which is
        what lets a scattered replica touch only its shard."""
        n = 512
        shards = 4
        x = jax.random.normal(jax.random.PRNGKey(1), (n,))
        codes, scales = quantize_blockwise(x, 64, "linear", shards)
        per_codes = codes.size // shards
        per_scales = scales.size // shards
        full = dequantize_blockwise(codes, scales, x.shape, 64, "linear",
                                    shards)
        for k in range(shards):
            seg_codes = codes[k * per_codes:(k + 1) * per_codes]
            seg_scales = scales[k * per_scales:(k + 1) * per_scales]
            seg = dequantize_blockwise(
                seg_codes, seg_scales, (n // shards,), 64, "linear", 1
            )
            np.testing.assert_array_equal(
                np.asarray(seg),
                np.asarray(full[k * (n // shards):(k + 1) * (n // shards)]),
            )


class TestScatterSpec:
    def test_appends_free_axes_to_first_divisible_dim(self, mesh22):
        spec = wus.scatter_spec(P(), (8, 3), mesh22, ("dp", "fsdp"))
        assert spec == P(("dp", "fsdp"), None)

    def test_keeps_existing_axes_and_adds_free_one(self, mesh22):
        spec = wus.scatter_spec(P("fsdp"), (8, 4), mesh22, ("dp", "fsdp"))
        assert spec == P(("fsdp", "dp"), None)

    def test_none_when_no_dim_divides(self, mesh22):
        assert wus.scatter_spec(P(), (3, 5), mesh22, ("dp", "fsdp")) is None
        assert wus.scatter_spec(P(), (), mesh22, ("dp", "fsdp")) is None

    def test_skips_undivisible_leading_dim(self, mesh22):
        spec = wus.scatter_spec(P(), (3, 8), mesh22, ("dp", "fsdp"))
        assert spec == P(None, ("dp", "fsdp"))

    def test_make_plan_none_without_replica_axes(self):
        mesh = build_mesh(MeshConfig(tp=4), jax.devices()[:4])
        assert wus.replica_axes(mesh) == ()
        # Trees are never touched when there is nothing to scatter over.
        assert wus.make_plan(mesh, None, None) is None


class TestEquivalence:
    """The WUS step must compute the SAME training trajectory as the
    replicated update — the plan changes layout, never math."""

    def test_f32_scatter_and_gather_match_baseline(self, mesh22):
        model = LlamaModel(TINY)
        rules = PRESET_RULES["fsdp"]
        batch = _batch()
        tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-2))
        s0, step0, _ = _fit(model, tx, mesh22, rules, batch)
        s1, step1, p1 = _fit(model, tx, mesh22, rules, batch, "scatter")
        s2, step2, p2 = _fit(model, tx, mesh22, rules, batch, "gather")
        assert p1.axes == ("dp", "fsdp") and p1.n_replica == 4
        assert p2.mode == "gather"
        # Gather mode stores params scattered between steps: the big
        # leaves' storage shardings gained a replica axis.
        stored = [
            sh.spec for sh in jax.tree.leaves(p2.stored_params)
            if isinstance(sh, NamedSharding)
        ]
        assert any("dp" in str(spec) for spec in stored)
        db = jax.device_put(batch, data_sharding(mesh22, rules))

        def hold(loss_tol, param_atol):
            for m in (m1, m2):
                np.testing.assert_allclose(
                    float(m["loss"]), float(m0["loss"]), **loss_tol
                )
            for a, b, c in zip(jax.tree.leaves(s0.params),
                               jax.tree.leaves(s1.params),
                               jax.tree.leaves(s2.params)):
                for other in (b, c):
                    np.testing.assert_allclose(
                        np.asarray(other), np.asarray(a),
                        rtol=0, atol=param_atol,
                    )

        for k in range(5):
            s0, m0 = step0(s0, db)
            s1, m1 = step1(s1, db)
            s2, m2 = step2(s2, db)
            if k == 0:
                # One update from one state: a mode that computed
                # something else would show here.  Observed: loss
                # bit-equal, grad norm and params one f32 ulp apart
                # (1.2e-7), the scattered reduction's order.
                hold(dict(rtol=0, atol=1e-6), 1e-6)
        # Five updates: that ulp goes through Adam's m / sqrt(v), which
        # turns a relative difference in a small gradient into an
        # absolute one in the parameter (with SGD the same five steps
        # stay within 1.3e-6; the losses are bit-equal through step 4).
        # Observed at step 5: loss 1.7e-5 apart at 0.86, parameters
        # 2.4e-4.  Held to 1e-4 relative in the loss and, in the
        # parameters, a tenth of one update at this learning rate.
        hold(dict(rtol=1e-4, atol=0), 1e-3)

    def test_int8_scatter_matches_replicated_int8(self, mesh22):
        """int8 blockwise Adam under WUS: codes/absmax are scattered 1/N
        (shards=4 aligns their block boundaries with the partition), and
        the trajectory matches the replicated int8 run to quantization
        precision."""
        model = LlamaModel(TINY)
        rules = PRESET_RULES["fsdp"]
        batch = _batch()
        s0, step0, _ = _fit(model, quantized_adamw(1e-2, shards=4),
                            mesh22, rules, batch)
        s1, step1, plan = _fit(model, quantized_adamw(1e-2, shards=4),
                               mesh22, rules, batch, "scatter")
        # The codec's codes/scales leaves (unconstrained before the plan)
        # must have been scattered over a replica axis.
        opt_specs = [
            sh.spec for sh in jax.tree.leaves(plan.opt_shardings)
            if isinstance(sh, NamedSharding)
        ]
        assert any("dp" in str(spec) for spec in opt_specs)
        db = jax.device_put(batch, data_sharding(mesh22, rules))
        for _ in range(5):
            s0, m0 = step0(s0, db)
            s1, m1 = step1(s1, db)
        # Quantization is discontinuous: a ~1e-7 layout-induced float
        # difference that crosses a bucket edge becomes one code step in
        # the moments.  Measured over 5 steps: params within 2.4e-4; the
        # loss (evaluated near convergence, where it is very sensitive)
        # within 1.8e-3.
        np.testing.assert_allclose(
            float(m1["loss"]), float(m0["loss"]), rtol=0, atol=5e-3
        )
        for a, b in zip(jax.tree.leaves(s0.params),
                        jax.tree.leaves(s1.params)):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=0, atol=1e-3
            )


class TestHLOEvidence:
    def test_scatter_step_emits_gather_and_reduction(self, mesh22):
        model = LlamaModel(TINY)
        rules = PRESET_RULES["fsdp"]
        batch = _batch()
        tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-2))
        state, step, _ = _fit(model, tx, mesh22, rules, batch, "scatter")
        db = jax.device_put(batch, data_sharding(mesh22, rules))
        hlo = step.jitted.lower(state, db).compile().as_text()
        from dlrover_tpu.telemetry.costmodel import collective_census

        census = collective_census(hlo)
        # The param re-gather at the end of the sharded update.
        assert census.get("all-gather", {}).get("count", 0) > 0
        assert census.get("all-gather", {}).get("bytes", 0) > 0
        # The grad reduction, in whichever form this toolchain lowers it
        # (literal reduce-scatter, or all-reduce + dynamic-slice — see
        # module docstring).
        assert (
            census.get("reduce-scatter", {}).get("count", 0) > 0
            or census.get("all-reduce", {}).get("count", 0) > 0
        )

    def test_opt_state_is_one_over_n_per_chip(self, mesh22):
        """Compiler-independent layout check: a scattered moment leaf's
        addressable shard is 1/n_replica of the global element count
        (times any base sharding it already had)."""
        model = LlamaModel(TINY)
        rules = PRESET_RULES["fsdp"]
        batch = _batch()
        tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-2))
        state, _, plan = _fit(model, tx, mesh22, rules, batch, "scatter")
        checked = 0
        for leaf, sh in zip(jax.tree.leaves(state.opt_state),
                            jax.tree.leaves(plan.opt_shardings)):
            if not (hasattr(leaf, "addressable_shards")
                    and isinstance(sh, NamedSharding)):
                continue
            if "dp" not in str(sh.spec):
                continue
            local = leaf.addressable_shards[0].data.size
            assert local * plan.n_replica <= leaf.size
            checked += 1
        assert checked > 0


@pytest.fixture(autouse=True)
def _isolated_ipc(request):
    """Checkpoint-IPC isolation only for the restore tests (module-scoped
    meshes above must not pay the saver reset)."""
    if "restore" in request.node.name:
        request.getfixturevalue("isolated_ipc")
    yield


class TestReformFlashRestore:
    def test_restore_into_scattered_opt_state(self, tmp_path, mesh22):
        """Reform drill: train 2 steps under the scatter plan, flash-save
        to shm, rebuild the world (fresh state, same plan), restore — the
        restored optimizer state must land back in its 1/N-scattered
        shardings with identical bytes."""
        from dlrover_tpu.checkpoint import Checkpointer, StorageType

        model = LlamaModel(TINY)
        rules = PRESET_RULES["fsdp"]
        batch = _batch()
        rng = jax.random.PRNGKey(0)
        tx = quantized_adamw(1e-2, shards=4)
        state, sh, plan = create_sharded_state(
            model, tx, mesh22, rules, rng, batch,
            weight_update_sharding="scatter",
        )
        step = make_train_step(model, mesh22, rules, sh,
                               weight_update_sharding=plan)
        db = jax.device_put(batch, data_sharding(mesh22, rules))
        for _ in range(2):
            state, _ = step(state, db)
        ckpt = Checkpointer(str(tmp_path / "ckpt"), start_saver=True)
        try:
            assert ckpt.save_checkpoint(2, state, StorageType.MEMORY)
            # "Reform": a fresh train state born from a different seed —
            # the shm-first restore must overwrite every leaf.
            # Same tx object: the TrainState's static metadata (the
            # optimizer's update fn) must match the jitted step's.
            state2, sh2, plan2 = create_sharded_state(
                model, tx, mesh22, rules,
                jax.random.PRNGKey(7), batch,
                weight_update_sharding="scatter",
            )
            loaded_step, restored = ckpt.load_checkpoint(state2, sh2)
            assert loaded_step == 2
            for a, b in zip(jax.tree.leaves(state.params),
                            jax.tree.leaves(restored.params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            for a, b, want in zip(jax.tree.leaves(state.opt_state),
                                  jax.tree.leaves(restored.opt_state),
                                  jax.tree.leaves(plan2.opt_shardings)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
                if isinstance(want, NamedSharding) and hasattr(b, "sharding"):
                    assert b.sharding.is_equivalent_to(want, b.ndim)
            # Restored state trains: one more step under the same plan.
            restored, metrics = step(restored, db)
            assert np.isfinite(float(metrics["loss"]))
        finally:
            ckpt.close()


@pytest.mark.slow
def test_wus_equivalence_fresh_4proc_world():
    """The same scatter-vs-baseline equivalence in a pristine 4-device
    process (no inherited 8-device harness state) — the smallest honest
    stand-in for a 4-host world.  Marked slow: a cold jax import + two
    jit compiles in a subprocess."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np, optax
from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import PRESET_RULES
from dlrover_tpu.trainer.step import (
    create_sharded_state, data_sharding, make_train_step)
cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=32)
model = LlamaModel(cfg)
mesh = build_mesh(MeshConfig(dp=2, fsdp=2), jax.devices())
rules = PRESET_RULES["fsdp"]
rng = jax.random.PRNGKey(0)
ids = jnp.tile(jnp.arange(32, dtype=jnp.int32)[None], (8, 1))
batch = {"input_ids": ids, "labels": ids}
tx = optax.adamw(1e-2)
s0, sh0 = create_sharded_state(model, tx, mesh, rules, rng, batch)
step0 = make_train_step(model, mesh, rules, sh0)
s1, sh1, plan = create_sharded_state(
    model, tx, mesh, rules, rng, batch, weight_update_sharding="scatter")
step1 = make_train_step(model, mesh, rules, sh1,
                        weight_update_sharding=plan)
db = jax.device_put(batch, data_sharding(mesh, rules))
for _ in range(2):
    s0, m0 = step0(s0, db)
    s1, m1 = step1(s1, db)
np.testing.assert_allclose(float(m1["loss"]), float(m0["loss"]),
                           rtol=0, atol=1e-6)
for a, b in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params)):
    np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                               rtol=0, atol=1e-6)
print("WUS_4PROC_OK")
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    res = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "WUS_4PROC_OK" in res.stdout
