"""What the ``trinity-mini`` configuration added by files alone: its file
against the published ``config.json`` and the contract, its arithmetic
against hand counts, its three metric readers on a run written out by hand,
the reference against the program at a tiny size through the harness's own
loader, and its toy twin (``cells/configs/tiny-afmoe.json``) rehearsed on
the CPU."""

import json
import math
import os

import pytest
from test_contract import BENCH, CHECKOUT, reader
from test_rehearsal import EXPECTED, rehearse

import flops_afmoe as flops

# https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json as the
# catalog of architectures holds it.
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": PERIOD * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "trinity-mini.json")) as f:
        return json.load(f)


def test_every_published_key_is_kept_but_for_the_cut(cfg):
    reduced = cfg["reduced"]
    assert sorted(reduced) == ["layer_types", "num_dense_layers",
                               "num_experts", "num_hidden_layers",
                               "vocab_size"]
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "trinity-mini"][0]
    assert entry["reduced"] == sorted(reduced)
    assert entry["source"] == cfg["source"]
    for key, note in reduced.items():
        assert {"source", "here"} <= set(note), key
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert key in cfg and cfg[key] == value, key
    # the cut: the last leading dense layer and one whole period of the
    # expert layers, in the published order; 16 of 128 experts; an eighth
    # of the vocabulary's rows (the floors: four expert layers, 8 experts,
    # an eighth)
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:6]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 5
    routed = cfg["layer_types"][cfg["num_dense_layers"]:]
    assert sorted(routed) == sorted(PERIOD)
    assert reduced["num_hidden_layers"]["source"] == 32
    assert (reduced["num_experts"]["source"], cfg["num_experts"]) == (128, 16)
    assert cfg["router_width"] == PUBLISHED["num_experts"]
    assert (cfg["expert_block"] + 1) * cfg["num_experts"] <= cfg[
        "router_width"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert (reduced["num_dense_layers"]["source"], cfg["num_dense_layers"]
            ) == (2, 1)
    # eight chips a layer; the batch gives a held expert a quarter of the
    # deployment's 4096 tokens a layer, and the file says so
    assert "eight chips share each layer" in cfg["stands_for"]
    assert (cfg["batch"], cfg["seq"]) == (2, 8192)
    per_expert = (cfg["batch"] * cfg["seq"] * cfg["num_experts_per_tok"]
                  / cfg["router_width"])
    assert per_expert == 1024 and "a quarter of the 4,096" in cfg[
        "assumed"]["batch"]
    for key in ("embedding_scale", "four_norms", "attention_gate", "qk_norm",
                "window", "global_layers", "selection_bias",
                "route_norm_eps", "expert_bias", "shared_expert", "seq",
                "state", "init", "recompute", "batch"):
        assert key in cfg["assumed"], key
    # what the program is told beyond the published keys
    kwargs = cfg["model"]["kwargs"]
    assert kwargs["embedding_multiplier"] == math.sqrt(cfg["hidden_size"])
    assert kwargs["rope_layers"] == ["sliding_attention"]
    assert kwargs["route_norm_eps"] == 1e-20
    # the optimizer is lfm2-8b-a1b's, and the selection bias a leaf of zeros
    # that load_balance_coeff does not move
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
        lfm2 = json.load(f)
    assert cfg["optimizer"] == lfm2["optimizer"]
    assert "128 zeros" in cfg["assumed"]["expert_bias"]
    assert "used by nothing" in cfg["assumed"]["expert_bias"]
    mapped = cfg["model"]["from_source"]
    assert mapped["routed_scaling_factor"] == "route_scale"
    assert mapped["head_dim"] == "head_dim"


def test_parameters_against_hand_counts(cfg):
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    assert flops.attention_params(cfg) == attention == 27_263_232
    assert flops.dense_mlp_params(cfg) == 3 * 2048 * 6144 == 37_748_736
    assert flops.expert_params(cfg) == 3 * 2048 * 1024 == 6_291_456
    assert flops.shared_expert_params(cfg) == 6_291_456
    assert flops.router_params(cfg) == 2048 * 128 + 128 == 262_272
    dense_layer = attention + 4 * 2048 + 37_748_736
    assert dense_layer == 65_020_160
    expert_layer = attention + 4 * 2048 + 262_272 + 17 * 6_291_456
    assert expert_layer == 134_488_448
    held = dense_layer + 4 * expert_layer + 2 * 25_024 * 2048 + 2048
    assert flops.n_params(cfg) == held == 705_474_304
    assert round(held * 12 / 1e9, 2) == 8.47  # f32 weights + two moments
    assert round(held * 16 / 1e9, 2) == 11.29  # + f32 gradients


def test_operations_against_hand_counts(cfg):
    projections = 2 * 2048 * (2 * 4096 + 2 * 512) + 2 * 4096 * 2048
    assert flops.attention_projection_flops_per_token(cfg) == projections
    dense = 6 * 2048 * 6144
    assert round((projections + dense) / 1e6, 1) == 130.0
    router, shared = 2 * 2048 * 128, 6 * 2048 * 1024
    assert flops.non_expert_matmul_flops_per_token(cfg) == (
        5 * projections + dense + 4 * (router + shared))
    # top-8 of 128 over 16 held: one pick a token lands here
    assert flops.expected_picks_here(cfg) == 1.0
    assert flops.expert_flops_per_token(cfg) == 4 * 6 * 2048 * 1024
    assert round((projections + router + 2 * shared) / 1e6, 1) == 80.2
    assert flops.head_flops_per_token(cfg) == 2 * 2048 * 25_024
    matmuls = flops.forward_matmul_flops_per_token(cfg)
    assert round(matmuls / 1e6, 1) == 553.4
    assert round(100 * flops.head_share_of_matmul_flops(cfg), 1) == 18.5
    # the pairs a mask keeps, exactly
    assert flops.kept_pairs(8192) == 8192 * 8193 // 2
    windowed = sum(min(t + 1, 2048) for t in range(8192))
    assert flops.kept_pairs(8192, 2048) == windowed == 14_681_088
    assert flops.kept_pairs(1024, 2048) == 1024 * 1025 // 2
    sliding = flops.attention_flops_per_token(cfg, 8192, "sliding_attention")
    full = flops.attention_flops_per_token(cfg, 8192, "full_attention")
    assert sliding == 4 * 4096 * windowed / 8192
    assert (round(sliding / 1e6, 1), round(full / 1e6, 1)) == (29.4, 67.1)
    attention = flops.forward_attention_flops_per_token(cfg, 8192)
    assert attention == 4 * sliding + full
    assert round(attention / 1e6, 1) == 184.6
    assert round((matmuls + attention) / 1e6, 1) == 738.0
    assert flops.train_flops_per_token(cfg, 8192) == 3 * (matmuls + attention)
    step = 3 * (matmuls + attention) * 2 * 8192
    assert round(step / 1e12, 1) == 36.3
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    # the attention kernels: six multiplications over the kept pairs
    need, moved = flops.attention_kernel_cost(cfg, 2, 8192)
    assert need == 2 * 12 * 4096 * (4 * windowed + 8192 * 8193 // 2)
    assert moved == 2 * 5 * 2 * 6 * (8192 * 4096 + 8192 * 512)
    least, bound = flops.least_seconds(need, moved, peak)
    assert bound == "compute" and round(least * 1e3, 2) == 46.05
    # the grouped products: nine multiplications a routed layer over the
    # 16,384 pairs expected here
    need, moved = flops.grouped_matmul_cost(cfg, 2, 8192)
    assert need == 4 * 9 * 2 * 16_384 * 2048 * 1024
    x, gu, act = 16_384 * 2048, 16_384 * 2048, 16_384 * 1024
    w_gu, w_down = 16 * 2048 * 2048, 16 * 1024 * 2048
    assert moved == 4 * 2 * 3 * (x + w_gu + gu + act + w_down + x)
    least, bound = flops.least_seconds(need, moved, peak)
    assert bound == "compute" and round(least * 1e3, 2) == 12.56


def _run(cfg):
    """A run as the driver hands it to a reader: two blocks of ten steps,
    0.7 s a step, the second shared with the profiler; 30 ms of grouped
    products and 120 ms of attention kernels a step in a three-step
    trace."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    return {
        "config": cfg, "peak": peak,
        "cell": {"chips": 1},
        "events": [
            {"ev": "window_open", "t": 100.0, "step": 3},
            {"ev": "fetch", "t": 107.0, "step": 13, "loss": 10.1},
            {"ev": "fetch", "t": 115.0, "step": 23, "loss": 10.1,
             "traced": True},
            {"ev": "trace", "step_from": 14, "step_to": 17},
        ],
        # reduce/xplane.py's form: name -> [calls, seconds of self time]
        "reduced": {"devices": {"0": {"ops": {
            "gmm": [3, 0.010], "gmm.23": [3, 0.050], "tgmm.7": [3, 0.030],
            "splash_mha_fwd_residuals": [12, 0.100],
            "splash_mha_dkv_no_residuals.1": [12, 0.260],
            "fusion.12": [3, 1.0], "gmm_like_fusion": [3, 5.0],
        }}}},
    }


def test_the_three_readers_on_a_run_written_by_hand(cfg):
    run = _run(cfg)
    # 10 steps x 16384 tokens in the 7 s the profiler did not share
    mfu = reader("mfu_afmoe_pct").read(run)
    assert mfu == pytest.approx(
        100 * flops.train_flops_per_token(cfg, 8192) * 16384 / 0.7 / 197e12)
    assert 26 < mfu < 27
    assert reader("attn_kernel_ms").read(run) == pytest.approx(120.0)
    assert reader("swa_attn_roofline_pct").read(run) == pytest.approx(
        100 * 46.048 / 120, rel=1e-3)
    assert reader("moe_gmm_ms").read(run) == pytest.approx(30.0)
    assert reader("afmoe_gmm_roofline_pct").read(run) == pytest.approx(
        100 * 12.557 / 30, rel=1e-3)


def test_a_program_without_the_kernels_gives_no_reading(cfg):
    """The parent commit's traces of this cell do not exist, and a trace
    without the kernels reads as nothing: the readers return None and
    raise nothing."""
    run = _run(cfg)
    run["reduced"]["devices"]["0"]["ops"] = {"fusion.12": [3, 1.0]}
    assert reader("swa_attn_roofline_pct").read(run) is None
    assert reader("afmoe_gmm_roofline_pct").read(run) is None
    run["reduced"] = None
    assert reader("swa_attn_roofline_pct").read(run) is None
    assert reader("afmoe_gmm_roofline_pct").read(run) is None
    run["peak"] = None
    assert reader("mfu_afmoe_pct").read(run) is None


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
                           "tiny-afmoe.json")) as f:
        tiny = json.load(f)
    model_cfg = train_worker.load_object(tiny["model"]["config_class"])(
        **{ours: tiny[theirs]
           for ours, theirs in tiny["model"]["from_source"].items()},
        **dict(tiny["model"]["kwargs"], attention_impl="dot",
               dtype=jnp.float32))
    assert (model_cfg.num_experts, model_cfg.experts_held,
            model_cfg.expert_block) == (16, 4, 1)
    assert (model_cfg.resolved_head_dim, model_cfg.sliding_window,
            model_cfg.tie_word_embeddings) == (32, 16, False)
    model = train_worker.load_object(tiny["model"]["class"])(model_cfg)
    batch = host_batch(7, 1, 2, 64, tiny["vocab_size"])
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
    proc, result = rehearse("tiny-afmoe.steady", trace)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu"
    unexpected = [p for p in result["problems"]
                  if not any(e in p for e in EXPECTED)]
    assert not unexpected, unexpected  # step 1 held to the reference
    assert metrics <= set(result["metrics"]), result["metrics"]
    # no Mosaic call runs off the TPU and no peak is known for a CPU: the
    # new readers find nothing and the lines leave them out
    assert not {"moe_gmm_ms", "afmoe_gmm_roofline_pct", "mfu_afmoe_pct",
                "swa_attn_roofline_pct"} & set(result["metrics"])
    assert result["attempted"] > 0 and result["failed"] == 0
