"""What the ``granite-4.0-h-micro`` configuration added by files alone: its
arithmetic against hand counts, its two metric readers on a run written
out by hand, its file against the published ``config.json``, and its toy
twin (``cells/configs/tiny-hybrid.json``) rehearsed on the CPU."""

import json
import os

import pytest
from test_contract import BENCH, reader
from test_rehearsal import EXPECTED, rehearse

import flops_granite_hybrid as flops

# https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json
# as the catalog of architectures holds it (the keys that shape the model).
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": [
        "attention" if i % 10 == 5 else "mamba" for i in range(40)],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def test_every_published_key_is_kept_but_for_the_cut(cfg):
    reduced = cfg["reduced"]
    assert sorted(reduced) == ["layer_types", "num_hidden_layers",
                               "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == value, key
    # the cut: one whole period of the published pattern, in its order, and
    # a quarter of the vocabulary's rows (the floor is an eighth)
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:10]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 10
    assert cfg["layer_types"].count("attention") * 9 == cfg[
        "layer_types"].count("mamba")
    assert reduced["num_hidden_layers"] == {"source": 40, "here": 10}
    assert reduced["vocab_size"] == {"source": 100352, "here": 25088}
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    # the mixer's inner width is the published expansion of the hidden size
    assert cfg["mamba_n_heads"] * cfg["mamba_d_head"] == (
        cfg["mamba_expand"] * cfg["hidden_size"])
    assert (cfg["batch"], cfg["seq"]) == (1, 8192)
    assert cfg["seq"] % cfg["mamba_chunk_size"] == 0


def test_parameters_against_hand_counts(cfg):
    in_proj = 2048 * (2 * 4096 + 2 * 128 + 64)
    assert in_proj == 17_432_576
    mixer = in_proj + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    assert flops.mamba_mixer_params(cfg) == mixer == 25_847_232
    mlp = 2048 * 16384 + 8192 * 2048
    assert flops.mamba_layer_params(cfg) == mixer + mlp + 2 * 2048 == 76_182_976
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert flops.attention_layer_params(cfg) == (
        attention + mlp + 2 * 2048) == 60_821_504
    held = 9 * 76_182_976 + 60_821_504 + 2048 + 25_088 * 2048
    assert flops.n_params(cfg) == held == 797_850_560


def test_operations_against_hand_counts(cfg):
    mamba = 2 * 17_432_576 + 2 * 4096 * 2048 + 6 * 2048 * 8192
    assert flops.mamba_layer_matmul_flops_per_token(cfg) == mamba
    attention = 2 * 2048 * (2048 + 1024) + 2 * 2048 * 2048 + 6 * 2048 * 8192
    assert flops.attention_layer_matmul_flops_per_token(cfg) == attention
    assert flops.scan_flops_per_token(cfg) == 4 * 128 * 64 * 64
    head = 2 * 2048 * 25_088
    assert flops.head_flops_per_token(cfg) == head
    matmuls = 9 * mamba + attention + head
    assert flops.forward_matmul_flops_per_token(cfg) == matmuls
    assert round(matmuls / 1e6) == 1595 and round(head / 1e6, 1) == 102.8
    assert round(100 * flops.head_share_of_matmul_flops(cfg), 1) == 6.4
    forward = matmuls + 9 * 4 * 128 * 64 * 64 + 2 * 8192 * 2048
    assert flops.train_flops_per_token(cfg, 8192) == 3 * forward
    assert round(3 * forward / 1e9, 2) == 4.94
    # attention: six causal multiplications in the one attention layer
    need, moved = flops.attention_kernel_cost(cfg, 1, 8192)
    assert need == 6 * 8192 * 8192 * 2048 and round(need / 1e9, 1) == 824.6
    assert moved == 2 * 6 * 8192 * (2048 + 512)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    least, bound = flops.least_seconds(need, moved, peak)
    assert bound == "compute" and round(least * 1e3, 2) == 4.19


def _run(cfg):
    """A run as the driver hands it to a reader: two blocks of ten steps,
    0.5 s a step, the second shared with the profiler; 12 ms of attention
    kernels a step in a three-step trace."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    return {
        "config": cfg, "peak": peak,
        "cell": {"chips": 1},
        "events": [
            {"ev": "window_open", "t": 100.0, "step": 3},
            {"ev": "fetch", "t": 105.0, "step": 13, "loss": 10.1},
            {"ev": "fetch", "t": 111.0, "step": 23, "loss": 10.1,
             "traced": True},
            {"ev": "trace", "step_from": 14, "step_to": 17},
        ],
        # reduce/xplane.py's form: name -> [calls, seconds of self time]
        "reduced": {"devices": {"0": {"ops": {
            "splash_mha_fwd_residuals": [3, 0.012],
            "splash_mha_dkv": [3, 0.024],
            "fusion.12": [3, 1.0],
        }}}},
    }


def test_the_two_readers_on_a_run_written_by_hand(cfg):
    run = _run(cfg)
    # 10 steps x 8192 tokens in the 5 s the profiler did not share
    mfu = reader("mfu_hybrid_pct").read(run)
    assert mfu == pytest.approx(
        100 * flops.train_flops_per_token(cfg, 8192) * 16384 / 197e12)
    assert 40 < mfu < 42
    # 4.19 ms least over (12 + 24) / 3 = 12 ms measured a step
    roofline = reader("hybrid_attn_roofline_pct").read(run)
    assert roofline == pytest.approx(100 * 4.186 / 12, rel=1e-3)


def test_a_run_without_a_device_trace_gives_no_roofline(cfg):
    run = _run(cfg)
    run["reduced"] = None
    assert reader("hybrid_attn_roofline_pct").read(run) is None


@pytest.mark.parametrize("trace, metrics", [
    (0, {"train_tokens_per_s", "setup_s"}),
    (1, {"compile_misses", "step_ms_p50", "window_tokens_per_s"}),
])
def test_rehearsal_of_the_toy_twin(trace, metrics):
    proc, result = rehearse("tiny-hybrid.steady", trace)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu"
    unexpected = [p for p in result["problems"]
                  if not any(e in p for e in EXPECTED)]
    assert not unexpected, unexpected  # step 1 held to the reference
    assert metrics <= set(result["metrics"]), result["metrics"]
    assert result["attempted"] > 0 and result["failed"] == 0
