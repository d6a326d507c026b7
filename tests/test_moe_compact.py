"""The pairs buffer of ``models/moe.py::RoutedExperts`` by the load: the
layer in passes over a buffer of ``cap`` sorted slots (twice the pairs
expected on the held experts) and a tile of zeros, against the layer over
all the slots at once, at sizes where the small buffer is built.  On the
CPU; the library kernel in Pallas interpret mode where it says so."""

import functools
import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import moe
from dlrover_tpu.models.hybrid import HybridConfig, HybridModel
from dlrover_tpu.ops import grouped_matmul as gm
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import PRESET_RULES
from dlrover_tpu.trainer.step import create_sharded_state, make_train_step

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 512 tokens, hidden 256, experts of width 128.  top-4 of 32 with 8 held:
# 2,048 pairs, 512 expected here, cap 1,024; top-8 of 128 with 16 held and
# a shared expert: 4,096 pairs, 512 expected here, cap 1,024.
_LAYERS = {
    "top4_of_32": dict(num_experts=32, num_experts_per_token=4,
                       experts_held=8),
    "top8_of_128_shared": dict(
        num_experts=128, num_experts_per_token=8, experts_held=16,
        routed_scaling_factor=2.826, route_norm_eps=1e-20,
        num_shared_experts=1),
}
_EXPERT_WEIGHTS = ("gate_proj", "up_proj", "down_proj")


def _reference():
    path = os.path.join(CHECKOUT, "benchmarks", "ref", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("lfm2_moe_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _layer_case(name, dtype, seed=0, t=512):
    layer = moe.RoutedExperts(256, 128, dtype=dtype, **_LAYERS[name])
    x = jax.random.normal(jax.random.key(seed), (1, t, 256)).astype(dtype)
    params = nn.unbox(layer.init(jax.random.key(seed + 1), x))["params"]
    return layer, params, x


def _run(layer, params, x):
    """-> (output, the flag, the load), gradients for the parameters and x."""

    def loss(params, x):
        out, sown = layer.apply({"params": params}, x,
                                mutable=["intermediates"])
        sown = sown["intermediates"]
        return jnp.sum(out.astype(jnp.float32) ** 2), (
            out, sown["moe_compact"][0], sown["moe_load"][0])

    (_, aux), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(params, x)
    return aux, grads


def _worst_case_only(monkeypatch):
    """The formulation before the small buffer: every slot in one pass."""
    monkeypatch.setattr(
        moe, "small_buffer", lambda pairs, held, experts: (pairs, pairs))


@pytest.mark.parametrize("pairs, held, experts, want", [
    (131072, 8, 32, (65536, 66048)),  # lfm2moe.steady
    (131072, 16, 128, (32768, 33280)),  # trinitymini.steady
    (2048, 8, 32, (1024, 1536)),
    (2048, 5, 32, (1024, 1536)),  # 640 expected: up to the row tile
    (1536, 8, 32, (1536, 1536)),  # 1,024 and its tile of zeros: no gain
    (131072, 32, 32, (131072, 131072)),  # every expert held
    (256, 4, 8, (256, 256)),  # a handful of tokens
])
def test_the_small_buffer_is_twice_the_expected_pairs_and_a_tile(
        pairs, held, experts, want):
    cap, rows = moe.small_buffer(pairs, held, experts)
    assert (cap, rows) == want
    assert rows % 512 == 0 or rows == pairs


@pytest.mark.parametrize("kernel", ["ragged_dot", "megablox_interpret"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(_LAYERS))
def test_the_small_body_gives_the_worst_case_bodys_numbers(
        name, dtype, kernel, monkeypatch):
    """Outputs and gradients bit for bit, with one exception that is the
    CPU product's and not the layer's: ``ragged_dot``'s gradient for the
    stacked weights contracts over all the buffer's rows at once, so a
    shorter buffer adds the same terms (and fewer zeros) in another order;
    the library kernel sums a group's rows tile by tile, the same tiles in
    both, and is bit-equal there too."""
    if kernel == "megablox_interpret":
        monkeypatch.setattr(moe, "grouped_matmul", functools.partial(
            gm.grouped_matmul, interpret=True))
    layer, params, x = _layer_case(name, dtype)
    (out, flag, load), grads = _run(layer, params, x)
    held = layer.held
    assert int(flag) == 1 and int(load[:held].sum()) <= 1024
    assert int(load.sum()) == 512 * layer.num_experts_per_token
    _worst_case_only(monkeypatch)
    (worst_out, worst_flag, worst_load), worst_grads = _run(layer, params, x)
    assert int(worst_flag) == 0
    np.testing.assert_array_equal(load, worst_load)
    np.testing.assert_array_equal(out, worst_out)
    np.testing.assert_array_equal(grads[1], worst_grads[1])
    assert np.asarray(grads[1], np.float32).any()
    got = dict(jax.tree_util.tree_flatten_with_path(grads[0])[0])
    want = dict(jax.tree_util.tree_flatten_with_path(worst_grads[0])[0])
    assert sorted(map(str, got)) == sorted(map(str, want))
    for path, leaf in got.items():
        if kernel == "ragged_dot" and path[-1].key in _EXPERT_WEIGHTS:
            scale = float(jnp.abs(want[path]).max())
            np.testing.assert_allclose(
                leaf, want[path], rtol=0,
                atol=scale * (2 ** -7 if dtype == jnp.bfloat16 else 1e-5))
        else:
            np.testing.assert_array_equal(leaf, want[path], str(path))


def _close(got, want, tolerance=2e-5):
    """Float32 sums taken in another order: a token's picks, an expert's
    rows and a weight's gradient are added up pass by pass."""
    scale = float(jnp.abs(want).max()) or 1.0
    np.testing.assert_allclose(got, want, rtol=0, atol=tolerance * scale)


@pytest.mark.parametrize("name", sorted(_LAYERS))
def test_a_load_past_the_cap_takes_more_passes_and_loses_nothing(
        name, monkeypatch):
    """A bias of 10 on the held block sends every pick of every token
    there: all the pairs, two or four times ``cap``; the flag reads 0, and
    output and gradients are the one-pass formulation's (to float32
    rounding: the sums are taken pass by pass) and the plain reference's."""
    layer, params, x = _layer_case(name, jnp.float32, seed=2)
    held, k = layer.held, layer.num_experts_per_token
    params = dict(params, expert_bias=jnp.zeros(
        layer.num_experts).at[:held].set(10.0))
    (out, flag, load), grads = _run(layer, params, x)
    assert int(flag) == 0
    assert int(load[:held].sum()) == 512 * k and int(load[held]) == 0
    assert 512 * k // moe.small_buffer(512 * k, held, layer.num_experts)[
        0] == {4: 2, 8: 4}[k]
    _worst_case_only(monkeypatch)
    (worst_out, _, _), worst_grads = _run(layer, params, x)
    _close(out, worst_out)
    jax.tree.map(_close, grads, worst_grads)
    assert np.asarray(grads[0]["router"]).any()
    ref = _reference()
    cfg = dict(num_experts_per_tok=k,
               routed_scaling_factor=layer.routed_scaling_factor)
    w = {"router": params["router"], "bias": params["expert_bias"],
         "gate": params["gate_proj"], "up": params["up_proj"],
         "down": params["down_proj"]}
    with jax.default_matmul_precision("highest"):
        want = ref.experts_of_block(cfg, w, x[0], 0, held)[0]
        if layer.num_shared_experts:
            shared = {n: params["shared"][f"{n}_proj"]["kernel"]
                      for n in ("gate", "up", "down")}
            want = want + ref._swiglu(x[0], *shared.values())
    np.testing.assert_allclose(out[0], want, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("sizes", [
    [512, 512, 0, 0, 0, 0, 0, 0, 1024],  # exactly cap here
    [300, 0, 900, 1, 0, 0, 47, 0, 800],  # a group across two windows
    [0, 0, 0, 0, 0, 0, 0, 2048, 0],  # one expert takes every pair
    [0, 0, 0, 0, 0, 0, 0, 0, 2048],  # nothing here
])
def test_the_windows_hold_every_pair_of_the_held_experts_once(sizes):
    """2,048 pairs over a buffer of 1,024 and a tile: the groups' parts in
    the two windows add up to the groups, a pair of a held expert lies
    inside exactly one window, at the slot its window's ``order`` names it
    in, and every other pair is sent to the last slot, past the groups."""
    sizes = jnp.asarray(sizes, jnp.int32)
    key = jnp.repeat(jnp.arange(9), sizes, total_repeat_length=2048)
    key = jax.random.permutation(jax.random.key(0), key)
    picks = key.reshape(4, 512).T  # pair p * t + i is token i's pick p
    sort = moe.sort_pairs(picks, 0, 8)
    np.testing.assert_array_equal(sort[2], sizes)
    cap, rows = 1024, 1536
    position = np.asarray(sort[1])
    total, seen = 0, np.zeros(2048, int)
    for start in (0, jnp.int32(1024)):
        order, slot, here = moe._window(sort, start, cap, rows)
        assert order.shape == (rows,) and slot.shape == (2048,)
        assert int(here.sum()) <= cap
        total = total + here
        inside = np.asarray(slot) < int(here.sum())
        seen += inside
        pairs = np.flatnonzero(inside)
        np.testing.assert_array_equal(np.asarray(order)[slot[pairs]], pairs)
        np.testing.assert_array_equal(
            position[pairs], np.asarray(slot)[pairs] + int(start))
        assert (np.asarray(slot)[~inside] >= int(here.sum())).all()
        assert (np.asarray(slot)[
            (position < int(start)) | (position >= int(start) + cap)
        ] == rows - 1).all()
    np.testing.assert_array_equal(total, sizes[:8])
    np.testing.assert_array_equal(
        seen, np.asarray(picks.T.reshape(-1) < 8).astype(int))


def test_a_load_of_exactly_the_cap_takes_one_pass(monkeypatch):
    """Every token picks experts 0 and 1 (held) and 8 and 9 (elsewhere):
    1,024 pairs here, which is ``cap``.  One pass, the pair sorted to slot
    ``cap`` reads zeros, and the numbers are the one-pass formulation's
    over all the slots, bit for bit."""
    layer, params, x = _layer_case("top4_of_32", jnp.float32, seed=3)
    params = dict(params, expert_bias=jnp.zeros(32).at[
        jnp.array([0, 1, 8, 9])].set(10.0))
    (out, flag, load), grads = _run(layer, params, x)
    np.testing.assert_array_equal(load, [512, 512] + [0] * 6 + [1024])
    assert int(flag) == 1
    cap, rows = moe.small_buffer(2048, 8, 32)
    assert int(load[:8].sum()) == cap

    # the body alone, eagerly, with the unsorting gather's result kept
    tokens = x[0]
    picks, pick_weights = moe.route(
        moe.router_scores(tokens, params["router"]), params["expert_bias"],
        4)
    sort = moe.sort_pairs(picks, 0, 8)
    inputs = (tokens, params["gate_proj"], params["up_proj"],
              params["down_proj"], pick_weights)
    seen = []
    unsort = moe._unsort

    def keeping(rows, order, position):
        seen.append(unsort(rows, order, position))
        return seen[-1]

    monkeypatch.setattr(moe, "_unsort", keeping)
    small = moe._experts_over(
        jnp.float32, inputs, moe._window(sort, 0, cap, rows))
    worst = moe._experts_over(jnp.float32, inputs, sort)
    np.testing.assert_array_equal(small, worst)
    by_pair, by_pair_worst = seen
    assert by_pair.shape == by_pair_worst.shape == (2048, 256)
    np.testing.assert_array_equal(by_pair, by_pair_worst)
    order = np.asarray(sort[0])
    assert not np.asarray(by_pair[order[cap:]]).any()  # slot cap and on
    assert np.asarray(by_pair[order[cap - 1]]).any()  # the last pair here
    # and the second window is empty
    assert not np.asarray(moe._window(sort, cap, cap, rows)[2]).any()

    monkeypatch.setattr(moe, "_unsort", unsort)
    _worst_case_only(monkeypatch)
    (worst_out, worst_flag, _), worst_grads = _run(layer, params, x)
    assert int(worst_flag) == 0
    np.testing.assert_array_equal(out, worst_out)
    np.testing.assert_array_equal(grads[1], worst_grads[1])
    np.testing.assert_array_equal(
        grads[0]["router"], worst_grads[0]["router"])


def _lower_spans(cfg, ids, tmp_path, monkeypatch):
    from dlrover_tpu.telemetry import events

    log = events.EventLog(directory=str(tmp_path))
    monkeypatch.setattr(events, "emit", log.emit)
    jax.eval_shape(HybridModel(cfg).init, jax.random.key(0), ids)
    return [e for e in events.read_dir(str(tmp_path))
            if e["ev"] == "span_end" and e.get("name") == "lower"]


# 8 rows of 64 tokens, top-4 of 8: 2,048 pairs; 2 held: cap 1,024.
_IDS = (8, 65)


@pytest.mark.parametrize("held, flagged, cap", [
    (2, 2, 1024),  # both routed layers run over the small buffer
    (None, 0, 2048),  # every expert held: no conditional, the flag is 0
])
def test_the_step_counts_the_layers_that_ran_over_the_small_buffer(
        devices8, tmp_path, monkeypatch, held, flagged, cap):
    cfg = HybridConfig.tiny_lfm2(dtype=jnp.float32, experts_held=held)
    model = HybridModel(cfg)
    mesh = build_mesh(MeshConfig(dp=1), devices8[:1])
    rules = PRESET_RULES["dp"]
    ids = jax.random.randint(jax.random.key(0), _IDS, 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    state, shardings = create_sharded_state(
        model, optax.adamw(1e-3), mesh, rules, jax.random.key(1), batch)
    step = make_train_step(model, mesh, rules, shardings)
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert sorted(metrics) == [
        "grad_norm", "loss", "moe_compact", "moe_load", "step"]
    assert metrics["moe_compact"].dtype == jnp.int32
    assert int(metrics["moe_compact"]) == flagged
    assert sorted(metrics["moe_load"]) == [
        "layers_1/experts", "layers_2/experts"]
    for load in metrics["moe_load"].values():
        assert int(load.sum()) == 2048 and int(load[:-1].sum()) <= cap

    end, = _lower_spans(cfg, batch["input_ids"], tmp_path, monkeypatch)
    assert (end["pairs_rows"], end["pairs_cap"]) == (2048, cap)
    assert end["routed_layers"] == 2
    plan = {"path": "ragged_dot", "tiling": None}
    assert end["gmm_gate_up"] == end["gmm_down"] == plan
    if held:
        assert end["gmm_gate_up_at_cap"] == end["gmm_down_at_cap"] == plan
    else:
        assert "gmm_gate_up_at_cap" not in end
        assert "gmm_down_at_cap" not in end


def test_the_lower_span_plans_the_kernels_at_both_row_counts(
        tmp_path, monkeypatch):
    """On a TPU (here: the rule that says so, patched) the span names the
    tilings of the products over the worst case and over the small
    buffer: the row tile is the same, as are the other two."""
    monkeypatch.setattr(gm, "pallas_interpret", lambda: False)
    cfg = HybridConfig.tiny_lfm2(experts_held=2)
    end, = _lower_spans(
        cfg, jnp.zeros((8, 64), jnp.int32), tmp_path, monkeypatch)
    assert end["gmm_gate_up"]["path"] == "megablox"
    assert end["gmm_gate_up"] == end["gmm_gate_up_at_cap"]
    assert end["gmm_down"] == end["gmm_down_at_cap"]
    assert end["gmm_down"]["tiling"][0][0] == 512


@pytest.mark.parametrize("held, loops", [(2, 2), (None, 0)])
def test_the_loop_of_passes_is_lowered_only_where_a_small_buffer_is_built(
        held, loops):
    """One loop a routed layer in the forward program, none where every
    expert is held; a conditional in neither."""
    cfg = HybridConfig.tiny_lfm2(dtype=jnp.float32, experts_held=held)
    model = HybridModel(cfg)
    ids = jnp.zeros((8, 64), jnp.int32)
    params = nn.unbox(jax.eval_shape(
        model.init, jax.random.key(0), ids))["params"]
    text = jax.jit(model.apply).lower({"params": params}, ids).as_text()
    assert text.count("stablehlo.while") == loops
    assert "stablehlo.case" not in text and "stablehlo.if" not in text


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for sub in value if isinstance(value, (list, tuple)) else [value]:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _products(jaxpr, in_loop=False):
    """(outside any loop, inside one): the grouped products (``ragged_dot``
    and its transposes) a jaxpr holds, those of its sub-jaxprs with them."""
    outside = inside = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("ragged_dot"):
            outside, inside = outside + (not in_loop), inside + in_loop
        for sub in _subjaxprs(eqn):
            o, i = _products(sub, in_loop or eqn.primitive.name == "while")
            outside, inside = outside + o, inside + i
    return outside, inside


@pytest.mark.parametrize("family", ["tiny_lfm2", "tiny_afmoe"])
def test_a_recomputed_layer_runs_the_products_the_layer_always_ran(family):
    """Under a policy that recomputes the layer, a routed layer is two
    loops: the forward one with its two products, and the backward one,
    which runs them again and then the four gradients: eight a pass, as
    before the small buffer.  No third loop recomputes the forward pass,
    whether a gradient outside reads the layer's output (a norm after it:
    AFMoE) or not: the policy keeps that output."""
    cfg = getattr(HybridConfig, family)(
        dtype=jnp.float32, experts_held=2, remat_policy="full")
    model = HybridModel(cfg)
    ids = jnp.zeros((8, 64), jnp.int32)
    params = nn.unbox(jax.eval_shape(
        model.init, jax.random.key(0), ids))["params"]

    def loss(p, ids):
        return jnp.sum(model.apply({"params": p}, ids) ** 2)

    routed = sum(cfg.routed(i) for i in range(len(cfg.layer_types)))
    assert _products(
        jax.make_jaxpr(jax.value_and_grad(loss))(params, ids).jaxpr) == (
            0, 8 * routed)


def test_a_model_under_recomputation_trains_through_several_passes(
        monkeypatch):
    """The loops with something to do, under ``jit``, a recomputation
    policy and a gradient: top-4 of 16 with 4 held and a bias of 10 on
    those four sends every pair there, 2,048 where a pass takes 1,024; loss
    and gradients are the one-pass formulation's to float32 rounding."""
    cfg = HybridConfig.tiny_lfm2(
        dtype=jnp.float32, num_experts=16, experts_held=4,
        remat_policy="full")
    model = HybridModel(cfg)
    ids = jax.random.randint(jax.random.key(0), (8, 64), 0, cfg.vocab_size)
    params = nn.unbox(model.init(jax.random.key(1), ids))["params"]
    for name in ("layers_1", "layers_2"):
        params[name]["experts"]["expert_bias"] = jnp.zeros(16).at[:4].set(10.0)

    def loss(p, ids):
        logits, sown = model.apply({"params": p}, ids,
                                   mutable=["intermediates"])
        flags = [int_ for path, int_ in jax.tree_util.tree_flatten_with_path(
            sown)[0] if "moe_compact" in jax.tree_util.keystr(path)]
        return jnp.mean(logits ** 2), sum(flags)

    run = lambda: jax.jit(jax.value_and_grad(loss, has_aux=True))(params, ids)
    (got, flags), grads = run()
    assert int(flags) == 0
    _worst_case_only(monkeypatch)
    (want, _), worst_grads = run()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    jax.tree.map(lambda a, b: _close(a, b, 1e-4), grads, worst_grads)
    assert np.asarray(grads["layers_1"]["experts"]["gate_proj"]).any()
