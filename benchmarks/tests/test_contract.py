"""``BENCHMARK.json`` against the contract's rules of form, and every name
in it against the files the harness finds by that name."""

import importlib.util
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(CHECKOUT, "BENCHMARK.json")


def reader(name, root=BENCH):
    path = os.path.join(root, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) < 65536
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        layers.add(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 1
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(map(NAME.match, c["reduced"]))
        assert c["name"] in {w["config"] for w in bench["workloads"]}


WIDTH = re.compile(
    r"hidden_size|intermediate_size|head_dim|_dim$|_rank$|latent|state_size"
    r"|experts_per_tok")


def test_every_name_resolves_to_files_that_exist(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("benchmarks/")
        cfg = load(CHECKOUT, c["file"])
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == c["reduced"]
        assert not any(WIDTH.search(k) for k in c["reduced"])
        assert os.path.exists(os.path.join(CHECKOUT, cfg["reference"]))
    for w in bench["workloads"]:
        cell = load(BENCH, "workloads", w["name"] + ".json")
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        assert os.path.exists(
            os.path.join(BENCH, "configs", cell["config"] + ".json"))
        traffic = load(BENCH, "traffic", cell["traffic"] + ".json")
        assert os.path.exists(
            os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
        # the cell's lists and BENCHMARK.json's say the same
        for kind in ("end_to_end", "per_layer"):
            listed = {m["name"] for m in bench[kind]
                      if w["name"] in m.get("workloads", [w["name"]])}
            assert set(cell[kind]) == listed, (w["name"], kind)
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) > 1
        assert cell["per_layer"]
        moved = {m["name"]: m["moves"] for m in bench["per_layer"]}
        for name in cell["per_layer"]:
            assert moved[name] in cell["end_to_end"], (w["name"], name)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert reader(m["name"]).UNIT == m["unit"], m["name"]
    peaks = load(BENCH, "peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert all("source" in row for row in peaks.values())


def test_the_tests_own_cell_resolves_too():
    cells = os.path.join(HERE, "cells")
    for name in os.listdir(os.path.join(cells, "workloads")):
        cell = load(cells, "workloads", name)
        assert os.path.exists(
            os.path.join(cells, "configs", cell["config"] + ".json"))
        assert os.path.exists(
            os.path.join(cells, "traffic", cell["traffic"] + ".json"))
        for metric in cell["end_to_end"] + cell["per_layer"]:
            assert any(os.path.exists(os.path.join(root, "metrics",
                                                   metric + ".py"))
                       for root in (cells, BENCH)), metric
