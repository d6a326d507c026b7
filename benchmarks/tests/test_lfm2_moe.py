"""What the ``lfm2-8b-a1b`` configuration added by files alone: its file
against the published ``config.json`` and the contract, its arithmetic
against hand counts, its three metric readers on a run written out by hand,
the reference against the program at a tiny size through the harness's own
loader, and its toy twin (``cells/configs/tiny-lfm2moe.json``) rehearsed on
the CPU."""

import json
import os

import pytest
from test_contract import BENCH, CHECKOUT, reader
from test_rehearsal import EXPECTED, rehearse

import flops_lfm2_moe as flops

# https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json as the
# catalog of architectures holds it (the keys that shape the model).
PERIOD = ["conv", "conv", "full_attention", "conv"]
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": (
        ["conv", "conv", "full_attention"] + ["conv", "conv", "conv",
                                              "full_attention"] * 4
        + ["conv", "conv", "full_attention", "conv", "conv"]),
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
        return json.load(f)


def test_every_published_key_is_kept_but_for_the_cut(cfg):
    assert len(PUBLISHED["layer_types"]) == 24
    assert PUBLISHED["layer_types"].count("full_attention") == 6
    reduced = cfg["reduced"]
    assert sorted(reduced) == ["layer_types", "num_dense_layers",
                               "num_experts", "num_hidden_layers",
                               "vocab_size"]
    for key, entry in reduced.items():
        assert {"source", "here"} <= set(entry), key
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == value, key
    # the cut: the last leading dense layer and one whole period of the
    # expert layers, in the published order; 8 of 32 experts; a quarter of
    # the vocabulary's rows (the floors: four expert layers, 8 experts, an
    # eighth)
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:6]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 5
    routed = cfg["layer_types"][cfg["num_dense_layers"]:]
    assert routed.count("conv") == 3 * routed.count("full_attention") == 3
    assert reduced["num_hidden_layers"]["source"] == 24
    assert (reduced["num_experts"]["source"], cfg["num_experts"]) == (32, 8)
    assert cfg["router_width"] == PUBLISHED["num_experts"]
    assert (cfg["expert_block"] + 1) * cfg["num_experts"] <= cfg[
        "router_width"]
    assert cfg["num_experts_per_tok"] == PUBLISHED["num_experts_per_tok"]
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert (reduced["num_dense_layers"]["source"], cfg["num_dense_layers"]
            ) == (2, 1)
    # four chips a layer, and the batch that gives a held expert the
    # deployment's 4096 tokens a layer
    assert "four chips share each layer" in cfg["stands_for"]
    assert (cfg["batch"], cfg["seq"]) == (4, 8192)
    per_expert = (cfg["batch"] * cfg["seq"] * cfg["num_experts_per_tok"]
                  / cfg["router_width"])
    assert per_expert == 4 * 8192 * 4 / 32 == 4096
    for key in ("seq", "head_dim", "tied_head", "expert_bias", "state",
                "init", "recompute", "batch"):
        assert key in cfg["assumed"], key


def test_parameters_against_hand_counts(cfg):
    assert flops.conv_op_params(cfg) == 4 * 2048 * 2048 + 3 * 2048 == 16_783_360
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert flops.attention_op_params(cfg) == attention == 10_485_888
    assert flops.dense_mlp_params(cfg) == 3 * 2048 * 7168 == 44_040_192
    assert flops.expert_params(cfg) == 3 * 2048 * 1792 == 11_010_048
    assert flops.router_params(cfg) == 2048 * 32 + 32 == 65_568
    dense_layer = 16_783_360 + 44_040_192 + 2 * 2048
    routed_ffn = 65_568 + 8 * 11_010_048
    ops = 3 * 16_783_360 + 10_485_888 + 4 * 2 * 2048
    held = dense_layer + ops + 4 * routed_ffn + 2048 + 16_384 * 2048
    assert flops.n_params(cfg) == held == 507_820_288
    assert round(held * 12 / 1e9, 2) == 6.09  # f32 weights + two moments


def test_operations_against_hand_counts(cfg):
    conv = 2 * 4 * 2048 * 2048
    attention = 2 * 2048 * (2048 + 1024) + 2 * 2048 * 2048
    dense = 6 * 2048 * 7168
    routers = 4 * 2 * 2048 * 32
    assert flops.non_expert_matmul_flops_per_token(cfg) == (
        4 * conv + attention + dense + routers)
    assert round(flops.non_expert_matmul_flops_per_token(cfg) / 1e6, 1) == 243.8
    # top-4 of 32 over 8 held: one pick a token lands here
    assert flops.expected_picks_here(cfg) == 1.0
    assert flops.expert_flops_per_token(cfg) == 4 * 6 * 2048 * 1792
    assert round(flops.expert_flops_per_token(cfg) / 1e6, 1) == 88.1
    assert flops.head_flops_per_token(cfg) == 2 * 2048 * 16_384
    matmuls = flops.forward_matmul_flops_per_token(cfg)
    forward = matmuls + 2 * 8192 * 2048
    assert round(forward / 1e6, 1) == 432.5
    assert flops.train_flops_per_token(cfg, 8192) == 3 * forward
    assert round(3 * forward * 4 * 8192 / 1e12, 1) == 42.5
    assert round(100 * flops.expert_flops_per_token(cfg) / forward) == 20
    assert round(100 * flops.head_share_of_matmul_flops(cfg), 1) == 16.8
    # the grouped products: nine multiplications a routed layer over the
    # 32,768 pairs expected here
    need, moved = flops.grouped_matmul_cost(cfg, 4, 8192)
    assert need == 4 * 9 * 2 * 32_768 * 2048 * 1792
    x, gu, act = 32_768 * 2048, 32_768 * 3584, 32_768 * 1792
    w_gu, w_down = 8 * 2048 * 3584, 8 * 1792 * 2048
    assert moved == 4 * 2 * 3 * (x + w_gu + gu + act + w_down + x)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    least, bound = flops.least_seconds(need, moved, peak)
    assert bound == "compute" and round(least * 1e3, 2) == 43.95


def _run(cfg):
    """A run as the driver hands it to a reader: two blocks of ten steps,
    0.6 s a step, the second shared with the profiler; 90 ms of grouped
    products a step in a three-step trace."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    return {
        "config": cfg, "peak": peak,
        "cell": {"chips": 1},
        "events": [
            {"ev": "window_open", "t": 100.0, "step": 3},
            {"ev": "fetch", "t": 106.0, "step": 13, "loss": 9.7},
            {"ev": "fetch", "t": 113.0, "step": 23, "loss": 9.7,
             "traced": True},
            {"ev": "trace", "step_from": 14, "step_to": 17},
        ],
        # reduce/xplane.py's form: name -> [calls, seconds of self time]
        "reduced": {"devices": {"0": {"ops": {
            "gmm": [3, 0.030], "gmm.23": [3, 0.150], "tgmm.7": [3, 0.090],
            "splash_mha_dkv_no_residuals.1": [3, 0.024],
            "fusion.12": [3, 1.0], "gmm_like_fusion": [3, 5.0],
        }}}},
    }


def test_the_three_readers_on_a_run_written_by_hand(cfg):
    run = _run(cfg)
    # 10 steps x 32768 tokens in the 6 s the profiler did not share
    mfu = reader("mfu_lfm2_pct").read(run)
    assert mfu == pytest.approx(
        100 * flops.train_flops_per_token(cfg, 8192) * 32768 / 0.6 / 197e12)
    assert 35 < mfu < 37
    # gmm + gmm.23 + tgmm.7 and nothing else: (30 + 150 + 90) / 3 ms a step
    assert reader("moe_gmm_ms").read(run) == pytest.approx(90.0)
    assert reader("moe_gmm_roofline_pct").read(run) == pytest.approx(
        100 * 43.952 / 90, rel=1e-3)


def test_a_program_without_the_kernels_gives_no_reading(cfg):
    """The parent commit's traces hold no such operation: the readers
    return nothing and raise nothing."""
    run = _run(cfg)
    run["reduced"]["devices"]["0"]["ops"] = {"fusion.12": [3, 1.0]}
    assert reader("moe_gmm_ms").read(run) is None
    assert reader("moe_gmm_roofline_pct").read(run) is None
    run["reduced"] = None
    assert reader("moe_gmm_ms").read(run) is None
    assert reader("moe_gmm_roofline_pct").read(run) is None


def test_the_reference_reads_the_programs_tree_through_the_harness_loader(
        tmp_path, monkeypatch):
    """As ``workers/train_worker.py`` does it: the model from the file's
    ``model`` block, the reference from its ``reference`` path, the loss of
    a batch row by row on the program's own parameters."""
    import importlib

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    # the worker reads its spec as it is imported
    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps({"events": str(tmp_path / "events.jsonl")}))
    monkeypatch.setenv("BENCH_SPEC", str(spec))
    train_worker = importlib.import_module("workers.train_worker")
    from workers.batches import host_batch

    with open(os.path.join(BENCH, "tests", "cells", "configs",
                           "tiny-lfm2moe.json")) as f:
        tiny = json.load(f)
    model_cfg = train_worker.load_object(tiny["model"]["config_class"])(
        **{ours: tiny[theirs]
           for ours, theirs in tiny["model"]["from_source"].items()},
        **dict(tiny["model"]["kwargs"], attention_impl="dot",
               dtype=jnp.float32))
    assert (model_cfg.num_experts, model_cfg.experts_held,
            model_cfg.expert_block) == (8, 4, 1)
    model = train_worker.load_object(tiny["model"]["class"])(model_cfg)
    batch = host_batch(7, 1, 2, 32, tiny["vocab_size"])
    params = nn.unbox(model.init(
        jax.random.key(0), batch["input_ids"]))["params"]
    want = train_worker._reference_loss(jax, tiny, params, batch)
    from dlrover_tpu.models.llama import cross_entropy_loss

    got = float(cross_entropy_loss(
        model.apply({"params": params}, batch["input_ids"]),
        batch["labels"]))
    assert got == pytest.approx(want, rel=1e-5)
    assert os.path.exists(os.path.join(CHECKOUT, tiny["reference"]))


@pytest.mark.parametrize("trace, metrics", [
    (0, {"train_tokens_per_s", "setup_s"}),
    (1, {"compile_misses", "step_ms_p50", "window_tokens_per_s"}),
])
def test_rehearsal_of_the_toy_twin(trace, metrics):
    proc, result = rehearse("tiny-lfm2moe.steady", trace)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu"
    unexpected = [p for p in result["problems"]
                  if not any(e in p for e in EXPECTED)]
    assert not unexpected, unexpected  # step 1 held to the reference
    assert metrics <= set(result["metrics"]), result["metrics"]
    # no Mosaic call runs off the TPU and no peak is known for a CPU: the
    # new readers find nothing and the lines leave them out
    assert not {"moe_gmm_ms", "moe_gmm_roofline_pct", "mfu_lfm2_pct"} & set(
        result["metrics"])
    assert result["attempted"] > 0 and result["failed"] == 0
