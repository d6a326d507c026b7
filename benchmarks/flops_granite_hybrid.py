"""Operations and bytes a training step of a Granite-4.0-H configuration
needs, from the file's published keys (``flops.py``'s sibling for
``model_type: granitemoehybrid``, dense).  Nothing here reads the program.

A decoder laid out by ``layer_types``: every layer has the gated MLP of
``shared_intermediate_size``; a ``mamba`` layer has the Mamba-2 mixer, an
``attention`` layer grouped-query attention; the output head is the tied
embedding over the rows of the vocabulary held here.

Counted: the matrix multiplications of the layers by kind and of the head,
causal attention (half of the full score matrix) in the attention layers
only, and the state-space scan **as the recurrence itself**: a token
updates a (d_state x d_head) state a head and reads it out, two
multiply-adds an element, whatever computes it (a chunked form does other
arithmetic; the count reads the same work).  Not counted: the embedding
lookup, the convolution's four taps, norms, gates, activations, the softmax,
the loss, the optimizer, and anything recomputed in the backward pass.  The
backward pass needs twice the forward's operations.
"""

from flops import least_seconds  # noqa: F401  (the roofline, shared)


def _sizes(cfg):
    heads = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // heads
    kinds = cfg["layer_types"]
    return dict(
        h=cfg["hidden_size"],
        q=heads * d,
        kv=cfg["num_key_value_heads"] * d,
        m=cfg["shared_intermediate_size"],
        v=cfg["vocab_size"],
        inner=cfg["mamba_n_heads"] * cfg["mamba_d_head"],
        ssm_heads=cfg["mamba_n_heads"],
        d_head=cfg["mamba_d_head"],
        d_state=cfg["mamba_d_state"],
        groups=cfg["mamba_n_groups"],
        conv=cfg["mamba_d_conv"],
        n_mamba=kinds.count("mamba"),
        n_attention=kinds.count("attention"),
    )


def _mlp_params(z):
    return 3 * z["h"] * z["m"]


def _in_proj_width(z):
    """z and x (the inner width each), B and C (the state a group), dt."""
    return 2 * z["inner"] + 2 * z["groups"] * z["d_state"] + z["ssm_heads"]


def mamba_mixer_params(cfg):
    z = _sizes(cfg)
    conv_channels = z["inner"] + 2 * z["groups"] * z["d_state"]
    return (
        z["h"] * _in_proj_width(z)
        + conv_channels * z["conv"] + conv_channels  # taps and bias
        + 3 * z["ssm_heads"]                          # dt_bias, A_log, D
        + z["inner"]                                  # the gated norm
        + z["inner"] * z["h"]                         # out_proj
    )


def mamba_layer_params(cfg):
    z = _sizes(cfg)
    return mamba_mixer_params(cfg) + _mlp_params(z) + 2 * z["h"]


def attention_layer_params(cfg):
    z = _sizes(cfg)
    mixer = z["h"] * (z["q"] + 2 * z["kv"]) + z["q"] * z["h"]
    return mixer + _mlp_params(z) + 2 * z["h"]


def n_params(cfg):
    """Held here: the layers, the final norm and the tied embedding."""
    z = _sizes(cfg)
    tied = cfg.get("tie_word_embeddings", False)
    return (
        z["n_mamba"] * mamba_layer_params(cfg)
        + z["n_attention"] * attention_layer_params(cfg)
        + z["h"] + z["v"] * z["h"] * (1 if tied else 2)
    )


def mamba_layer_matmul_flops_per_token(cfg):
    """Forward multiply-adds x 2: in_proj, out_proj and the MLP."""
    z = _sizes(cfg)
    mixer = 2 * z["h"] * _in_proj_width(z) + 2 * z["inner"] * z["h"]
    return mixer + 2 * _mlp_params(z)


def attention_layer_matmul_flops_per_token(cfg):
    z = _sizes(cfg)
    mixer = 2 * z["h"] * (z["q"] + 2 * z["kv"]) + 2 * z["q"] * z["h"]
    return mixer + 2 * _mlp_params(z)


def scan_flops_per_token(cfg):
    """Forward: the state's update and its read-out, a multiply-add each
    an element of (d_state x d_head) a head."""
    z = _sizes(cfg)
    return 4 * z["d_state"] * z["d_head"] * z["ssm_heads"]


def head_flops_per_token(cfg):
    z = _sizes(cfg)
    return 2 * z["h"] * z["v"]


def attention_flops_per_token(cfg, seq):
    """Forward QK^T and PV of one attention layer under a causal mask."""
    return 2 * seq * _sizes(cfg)["q"]


def forward_matmul_flops_per_token(cfg):
    z = _sizes(cfg)
    return (
        z["n_mamba"] * mamba_layer_matmul_flops_per_token(cfg)
        + z["n_attention"] * attention_layer_matmul_flops_per_token(cfg)
        + head_flops_per_token(cfg)
    )


def train_flops_per_token(cfg, seq):
    """Forward + backward operations one token of a dense causal row needs."""
    z = _sizes(cfg)
    forward = (
        forward_matmul_flops_per_token(cfg)
        + z["n_mamba"] * scan_flops_per_token(cfg)
        + z["n_attention"] * attention_flops_per_token(cfg, seq)
    )
    return 3 * forward


def head_share_of_matmul_flops(cfg):
    return head_flops_per_token(cfg) / forward_matmul_flops_per_token(cfg)


def attention_kernel_cost(cfg, rows, seq, itemsize=2):
    """What the attention kernels of one step (the attention layers only,
    forward and backward) must do for ``rows`` dense causal rows, at the
    configuration's true head dim: ``(flops, bytes)``, counted as
    ``flops.attention_kernel_cost`` counts them."""
    z = _sizes(cfg)
    one_matmul = seq * seq * z["q"]  # 2 * s * s * q, halved by the mask
    flops = rows * z["n_attention"] * 6 * one_matmul
    qo, kv = seq * z["q"], seq * z["kv"]
    bytes_moved = rows * z["n_attention"] * itemsize * (6 * qo + 6 * kv)
    return flops, bytes_moved
