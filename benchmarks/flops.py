"""Operations and bytes a training step needs, from the configuration's sizes.

The yardstick's arithmetic: nothing here reads the program.  A dense
decoder-only LM with grouped-query attention and a gated MLP, as every
configuration under ``benchmarks/configs/`` is today (keys as in the
model's published ``config.json``).

Counted: the matrix multiplications of the layers and of the output head,
and causal attention (half of the full score matrix).  Not counted: the
embedding lookup (a gather), norms, rotary embeddings, activations, the
softmax, the loss, the optimizer, and anything recomputed in the backward
pass.  The backward pass needs twice the forward's multiplications.
"""


def _sizes(cfg):
    d = cfg["head_dim"]
    return dict(
        h=cfg["hidden_size"],
        q=cfg["num_attention_heads"] * d,
        kv=cfg["num_key_value_heads"] * d,
        m=cfg["intermediate_size"],
        v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"],
    )


def layer_matmul_flops_per_token(cfg):
    """Forward multiply-adds x 2 of one layer's seven projections."""
    z = _sizes(cfg)
    attn_proj = 2 * z["h"] * (z["q"] + 2 * z["kv"]) + 2 * z["q"] * z["h"]
    mlp = 3 * 2 * z["h"] * z["m"]
    return attn_proj + mlp


def head_flops_per_token(cfg):
    z = _sizes(cfg)
    return 2 * z["h"] * z["v"]


def attention_flops_per_token(cfg, seq):
    """Forward QK^T and PV of one layer under a causal mask: each is
    ``2 * seq * q`` a token over the full square, half of it causal."""
    return 2 * seq * _sizes(cfg)["q"]


def train_flops_per_token(cfg, seq):
    """Forward + backward operations one token of a dense causal row needs."""
    z = _sizes(cfg)
    forward = z["layers"] * (
        layer_matmul_flops_per_token(cfg) + attention_flops_per_token(cfg, seq)
    ) + head_flops_per_token(cfg)
    return 3 * forward


def head_share_of_matmul_flops(cfg):
    layers = _sizes(cfg)["layers"] * layer_matmul_flops_per_token(cfg)
    head = head_flops_per_token(cfg)
    return head / (layers + head)


def n_params(cfg):
    z = _sizes(cfg)
    layer = (
        z["h"] * (z["q"] + 2 * z["kv"]) + z["q"] * z["h"]
        + 3 * z["h"] * z["m"] + 2 * z["h"]
    )
    tied = cfg.get("tie_word_embeddings", False)
    return (
        z["layers"] * layer + z["h"]
        + z["v"] * z["h"] * (1 if tied else 2)
    )


def attention_kernel_cost(cfg, rows, seq, itemsize=2):
    """What the attention kernels of one step (all layers, forward and
    backward) must do for ``rows`` dense causal rows: ``(flops, bytes)``.

    Forward is two multiplications (QK^T, PV), backward four (dV, dP, dQ,
    dK); the backward's recomputation of the scores is not needed work.
    Bytes: q, k, v and the output read or written once forward; q, k, v,
    the output, its gradient read and dq, dk, dv written once backward.
    """
    z = _sizes(cfg)
    one_matmul = seq * seq * z["q"]  # 2 * s * s * q, halved by the mask
    flops = rows * z["layers"] * 6 * one_matmul
    qo, kv = seq * z["q"], seq * z["kv"]
    forward_bytes = 2 * qo + 2 * kv
    backward_bytes = 4 * qo + 4 * kv
    bytes_moved = rows * z["layers"] * itemsize * (
        forward_bytes + backward_bytes
    )
    return flops, bytes_moved


def least_seconds(flops, bytes_moved, peak):
    """The roofline: the larger of operations over the chip's peak rate and
    bytes over its peak bandwidth.  Returns ``(seconds, bound_by)``."""
    by_compute = flops / peak["bf16_flops_per_s"]
    by_memory = bytes_moved / peak["hbm_bytes_per_s"]
    if by_compute >= by_memory:
        return by_compute, "compute"
    return by_memory, "memory"
