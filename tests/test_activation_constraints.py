"""The models' activation constraints reach the compiler.

``parallel/sharding.py::constrain`` is the one call every model makes on
its activations.  It reads the rule table and the mesh the step sets at
trace time; over more than one device it puts a ``sharding_constraint``
into the program, so that GSPMD gathers the weights and leaves the
activations where they are; on one device, or with no mesh or rules in
scope, it returns its input and the program is what it would be without
the call.  (Before it, the models called flax's
``nn.with_logical_constraint``, which dropped every constraint unless
jax's own ambient mesh was set, and nothing set it.)
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.linen import partitioning as nn_partitioning
from jax.sharding import NamedSharding, PartitionSpec

from dlrover_tpu.models import hybrid, llama, moe
from dlrover_tpu.models.hybrid import HybridConfig, HybridModel
from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh
from dlrover_tpu.parallel.sharding import (
    PRESET_RULES,
    constrain,
    count_constraints,
)
from dlrover_tpu.telemetry import events as tevents
from dlrover_tpu.telemetry.costmodel import abstract_sharded_state
from dlrover_tpu.trainer.step import (
    create_sharded_state,
    data_sharding,
    make_train_step,
)

MODELS = {
    "llama": lambda **kw: LlamaModel(
        LlamaConfig.tiny(scan_layers=False, **kw)),
    "granite": lambda **kw: HybridModel(HybridConfig.tiny(**kw)),
    "lfm2": lambda **kw: HybridModel(HybridConfig.tiny_lfm2(**kw)),
}
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


def _mesh(devices, sharded):
    if sharded:
        return (build_mesh(MeshConfig(dp=1, fsdp=2, tp=2), devices[:4]),
                PRESET_RULES["fsdp_tp"])
    return build_mesh(MeshConfig(dp=-1), devices[:1]), PRESET_RULES["dp"]


def _lower(model, mesh, rules):
    """The train step lowered as ``make_train_step`` traces it, and how
    many constraints the helper applied meanwhile."""
    batch = {
        k: jax.ShapeDtypeStruct(
            (4, 32), jnp.int32, sharding=data_sharding(mesh, rules))
        for k in ("input_ids", "labels")
    }
    state, shardings = abstract_sharded_state(
        model, optax.adamw(1e-3), mesh, rules, batch)
    step = make_train_step(model, mesh, rules, shardings)
    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh), \
            count_constraints() as applied:
        lowered = step.jitted.lower(state, batch)
    return lowered, applied[0]


def _constraints_in(text):
    return len(re.findall(r"\bsharding_constraint\b", text))


def _collectives(compiled_text, op):
    return len(re.findall(rf"= \S+ {op}(?:-start)?\(", compiled_text))


@pytest.mark.parametrize("name", MODELS)
def test_sharded_step_holds_every_constraint_the_model_wrote(devices8, name):
    lowered, applied = _lower(MODELS[name](), *_mesh(devices8, True))
    assert applied > 0
    # Each constraint is in the program twice: where the model wrote it,
    # and on its cotangent (the transpose of a constraint is the same
    # constraint), so the backward pass keeps activations in place too.
    assert _constraints_in(lowered.as_text()) == 2 * applied
    # With the activations held, what is left to exchange is not them
    # (the tree before: 21 all-to-all for the tiny Llama).
    assert _collectives(lowered.compile().as_text(), "all-to-all") <= 2


@pytest.mark.parametrize("name", MODELS)
def test_one_device_program_is_the_program_without_the_helper(
        devices8, monkeypatch, name):
    mesh, rules = _mesh(devices8, False)
    lowered, applied = _lower(MODELS[name](), mesh, rules)
    text = lowered.as_text()
    assert applied == 0 and _constraints_in(text) == 0
    for module in (llama, hybrid, moe):
        monkeypatch.setattr(module, "constrain", lambda x, axes: x)
    assert _lower(MODELS[name](), mesh, rules)[0].as_text() == text


@pytest.mark.parametrize("sharded", [True, False], ids=["4dev", "1dev"])
def test_compile_span_reports_the_count(devices8, tmp_path, monkeypatch,
                                        sharded):
    monkeypatch.setenv(tevents.ENV_TELEMETRY_DIR, str(tmp_path))
    tevents.reset()
    try:
        mesh, rules = _mesh(devices8, sharded)
        model = MODELS["llama"]()
        _, applied = _lower(model, mesh, rules)
        ids = jax.random.randint(jax.random.key(0), (4, 33), 0, 256)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        state, shardings = create_sharded_state(
            model, optax.adamw(1e-3), mesh, rules, jax.random.key(1), batch)
        step = make_train_step(model, mesh, rules, shardings)
        batch = jax.device_put(batch, data_sharding(mesh, rules))
        for _ in range(2):  # the second call is not a compile: no span
            state, _ = step(state, batch)
        ends = [e for e in tevents.read_dir(str(tmp_path))
                if e["ev"] == "compile_end" and e["what"] == "train_step"]
    finally:
        tevents.reset()
    assert [e["activation_constraints"] for e in ends] == [applied]
    assert (applied > 0) == sharded


def test_without_a_mesh_or_rules_the_argument_comes_back(devices8):
    x = jnp.ones((4, 8, 64))
    axes = ("batch", "seq", "act_embed")
    mesh, rules = _mesh(devices8, True)
    assert constrain(x, axes) is x
    with nn_partitioning.axis_rules(list(rules)):
        assert constrain(x, axes) is x  # rules, no mesh
    with use_mesh(mesh):
        assert constrain(x, axes) is x  # mesh, no rules
    one, _ = _mesh(devices8, False)
    with nn_partitioning.axis_rules(list(rules)), use_mesh(one):
        assert constrain(x, axes) is x  # one device


def test_constraint_lands_where_the_rules_say(devices8):
    mesh, rules = _mesh(devices8, True)
    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh), \
            count_constraints() as applied:
        out = jax.jit(
            lambda x: constrain(x, ("batch", "seq", "act_mlp"))
        )(jnp.ones((4, 8, 64)))
    assert applied[0] == 1
    want = NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), "sp", "tp"))
    assert out.sharding.is_equivalent_to(want, out.ndim)
    assert out.addressable_shards[0].data.shape == (2, 8, 32)


@pytest.mark.parametrize("shape, axes, named", [
    ((3, 8, 64), ("batch", "seq", "act_embed"), "('dp', 'fsdp')"),
    ((4, 8, 63), ("batch", "seq", "act_mlp"), "('tp',)"),
    ((4, 8), ("batch", "seq", "act_embed"), "3 dimensions"),
])
def test_an_axis_that_does_not_divide_raises(devices8, shape, axes, named):
    mesh, rules = _mesh(devices8, True)
    with nn_partitioning.axis_rules(list(rules)), use_mesh(mesh):
        with pytest.raises(ValueError) as err:
            jax.jit(lambda x: constrain(x, axes))(jnp.ones(shape))
    message = str(err.value)
    assert named in message and str(axes) in message
    assert str(shape) in message


@pytest.mark.parametrize("name", MODELS)
def test_sharding_moves_places_not_values(devices8, name):
    """float32 all through: one step on fsdp=2 x tp=2 and on one device
    from the same seed give the same loss and the same gradients."""
    kw = dict(F32, attention_impl="dot") if name == "llama" else F32
    ids = jax.random.randint(jax.random.key(0), (4, 33), 0, 256)
    host_batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    got = {}
    for sharded in (False, True):
        mesh, rules = _mesh(devices8, sharded)
        model = MODELS[name](**kw)
        # A momentum trace with no decay is the gradient itself, kept in
        # the optimizer's state where the test can read it.
        state, shardings = create_sharded_state(
            model, optax.trace(decay=0.0), mesh, rules, jax.random.key(1),
            host_batch)
        step = make_train_step(model, mesh, rules, shardings)
        state, metrics = step(
            state, jax.device_put(host_batch, data_sharding(mesh, rules)))
        grads = jax.tree.map(np.asarray, state.opt_state.trace)
        got[sharded] = (float(metrics["loss"]), grads)
    (loss1, grads1), (loss4, grads4) = got[False], got[True]
    assert abs(loss4 - loss1) < 1e-6 * max(1.0, abs(loss1))
    flat1 = jax.tree_util.tree_leaves_with_path(grads1)
    flat4 = jax.tree.leaves(grads4)
    assert len(flat1) == len(flat4) > 0
    for (path, g1), g4 in zip(flat1, flat4):
        where = jax.tree_util.keystr(path)
        # No gradient reaches LFM2's selection bias: 0 on both meshes.
        assert np.any(g1) or "expert_bias" in where, where
        assert np.abs(g4 - g1).max() <= 1e-5 * np.abs(g1).max(), where
