"""The yardstick's own arithmetic: the trace reduction, the operation
counts, and the plain reference against the program at a tiny size."""

import gzip
import json
import os
import re

import pytest

import flops
from reduce import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# -- interval arithmetic, by hand --------------------------------------------


def test_union_subtract_and_gaps_by_hand():
    busy = xplane.union([(0, 10), (5, 12), (20, 30), (30, 31), (40, 40)])
    assert busy == [(0, 12), (20, 31)]
    assert xplane.measure(busy) == 23
    assert xplane.gaps(busy, (0, 50)) == [(12, 20), (31, 50)]
    assert xplane.subtract([(0, 100)], [(10, 20), (15, 30), (90, 120)]) == [
        (0, 10), (30, 90)]
    assert xplane.subtract([(0, 5), (10, 15)], []) == [(0, 5), (10, 15)]


def test_self_time_takes_the_children_out_of_a_loop():
    events = [("while", 0, 100), ("fusion", 10, 30), ("kernel", 30, 70),
              ("fusion", 120, 130)]
    total = {}
    for name, ns in xplane.self_times(events):
        total[name] = total.get(name, 0) + ns
    assert total == {"while": 40, "fusion": 30, "kernel": 40}


def test_an_exposed_collective_by_hand():
    lines = {
        xplane.OP_LINE: [("fusion.1", 0, 50), ("all-reduce.2", 50, 80),
                         ("fusion.3", 100, 150)],
        # an asynchronous collective on a line of its own, half hidden
        "XLA Async Ops": [("all-gather-start.4", 120, 180)],
        "XLA Modules": [("jit_step", 0, 180)],
    }
    host = [("bench/fetch", 75, 105), ("bench/dispatch", 0, 10)]
    r = xplane.reduce_device(lines, host)
    assert r["span_s"] == pytest.approx(150e-9)
    assert r["busy_s"] == pytest.approx(130e-9)
    assert r["collective_s"] == pytest.approx(90e-9)
    # all-reduce 30 wholly exposed; all-gather exposed after fusion.3 ends
    assert r["collective_exposed_s"] == pytest.approx(60e-9)
    assert r["ops"]["all-reduce.2"] == [1, pytest.approx(30e-9)]
    assert r["gaps"] == [["bench/fetch", pytest.approx(20e-9)]]


# -- the recorded trace ------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    """Four v5e chips, fsdp=2 x tp=2, the tests' ``small4.record`` cell."""
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(HERE, "small4.xplane.pb.gz")) as f:
        return ProfileData.from_serialized_xspace(f.read())


def brute_force(intervals, inside=None):
    """Measure of a union (minus ``inside``'s complement) the slow way:
    every elementary segment between two endpoints is covered or not."""
    points = sorted({p for iv in intervals for p in iv}
                    | {p for iv in (inside or []) for p in iv})
    total = 0
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        covered = any(s <= mid < e for s, e in intervals)
        if covered and not any(s <= mid < e for s, e in (inside or [])):
            total += b - a
    return total


def test_recorded_trace_reduces_to_what_brute_force_counts(recorded):
    devices, annotations, _labels = xplane.read_planes(recorded)
    assert sorted(devices) == [0, 1, 2, 3]
    assert any(name == "bench/dispatch" for name, _s, _e in annotations)
    reduced = xplane.reduce_profile(recorded)
    for index, lines in devices.items():
        ops = lines[xplane.OP_LINE][:1500]  # the slow way is quadratic
        r = xplane.reduce_device({xplane.OP_LINE: ops}, annotations)
        spans = [(s, e) for _n, s, e in ops]
        assert r["busy_s"] * 1e9 == pytest.approx(brute_force(spans))
        collective = [(s, e) for n, s, e in ops if xplane.COLLECTIVE.search(n)]
        compute = [(s, e) for n, s, e in ops
                   if not xplane.COLLECTIVE.search(n)]
        assert collective, "a sharded step has collectives"
        assert r["collective_exposed_s"] * 1e9 == pytest.approx(
            brute_force(collective, compute))
        whole = reduced["devices"][str(index)]
        assert 0 < whole["busy_s"] <= whole["span_s"]
        assert 0 < whole["collective_exposed_s"] <= whole["collective_s"]


def test_recorded_trace_holds_the_attention_kernel(recorded):
    from metrics import attn_kernel_ms

    reduced = xplane.reduce_profile(recorded)
    seconds = xplane.op_seconds(reduced, attn_kernel_ms.KERNEL)
    lines = xplane.read_planes(recorded)[0][0]
    by_hand = sum(e - s for n, s, e in lines[xplane.OP_LINE]
                  if re.search(attn_kernel_ms.KERNEL, n))
    assert by_hand > 0
    # one device's sum is near the mean over the four
    assert seconds * 1e9 == pytest.approx(by_hand, rel=0.25)
    assert xplane.top_ops(reduced, 10)[0][1] > 0
    assert len(xplane.longest_gaps(reduced, 5)) == 5


# -- operations a token, by hand ---------------------------------------------


def test_flops_a_token_against_hand_counts():
    m, c = config("mistral-7b-v0.3"), config("codestral-22b")
    # q 33,554,432 + k,v 16,777,216 + o 33,554,432 + MLP 352,321,536
    assert flops.layer_matmul_flops_per_token(m) == 436_207_616
    assert flops.attention_flops_per_token(m, 4096) == 33_554_432
    assert flops.head_flops_per_token(m) == 268_435_456
    assert flops.train_flops_per_token(m, 4096) == 2_214_592_512
    assert flops.n_params(m) == 486_551_552
    assert flops.head_share_of_matmul_flops(m) == pytest.approx(0.381, abs=1e-3)
    # q 75,497,472 + k,v 25,165,824 + o 75,497,472 + MLP 603,979,776
    assert flops.layer_matmul_flops_per_token(c) == 780_140_544
    assert flops.train_flops_per_token(c, 4096) == 11_173_625_856
    assert flops.n_params(c) == 1_962_989_568
    assert flops.head_share_of_matmul_flops(c) == pytest.approx(0.114, abs=1e-3)


def test_attention_kernel_cost_and_its_roofline():
    m = config("mistral-7b-v0.3")
    need, moved = flops.attention_kernel_cost(m, rows=2, seq=4096)
    # six causal multiplications of 4096 x 4096 x (32 heads x 128) a row
    assert need == 2 * 6 * 4096 * 4096 * 4096
    # q, o (4096 x 4096) and k, v (4096 x 1024) in bf16: 2+2 forward, 4+4 back
    assert moved == 2 * 2 * (6 * 4096 * 4096 + 6 * 4096 * 1024)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    seconds, bound = flops.least_seconds(need, moved, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(need / 197e12)


# -- the plain reference against the program ---------------------------------


def test_reference_agrees_with_the_program_at_a_tiny_size():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.llama import (
        LlamaConfig, LlamaModel, cross_entropy_loss)
    from ref import plain_lm

    with open(os.path.join(HERE, "cells", "configs", "tiny.json")) as f:
        cfg = json.load(f)
    model = LlamaModel(LlamaConfig(
        **{ours: cfg[theirs]
           for ours, theirs in cfg["model"]["from_source"].items()},
        scan_layers=False, dtype=jnp.float32, attention_impl="dot"))
    ids = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], size=(2, cfg["seq"] + 1), dtype=np.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    import flax

    params = flax.core.meta.unbox(
        model.init(jax.random.key(0), x)["params"])
    logits = model.apply({"params": params}, x)
    # float32 on both sides: only the order of the sums differs
    np.testing.assert_allclose(
        plain_lm.logits_of_row(cfg, params, x[0]), logits[0],
        rtol=2e-4, atol=2e-4)
    want = float(cross_entropy_loss(logits, y))
    got = sum(float(plain_lm.loss_of_row(cfg, params, a, b))
              for a, b in zip(x, y)) / y.size
    assert got == pytest.approx(want, rel=1e-5)
