"""Operations and bytes a training step of an LFM2 mixture-of-experts
configuration needs, from the file's published keys (``flops.py``'s sibling
for ``model_type: lfm2_moe``).  Nothing here reads the program.

A decoder laid out by ``layer_types``: a ``conv`` layer has the gated short
convolution (three projections in, one out), a ``full_attention`` layer
grouped-query attention; the first ``num_dense_layers`` layers have the
gated MLP of ``intermediate_size``, the later ones a router over all the
model's experts (``reduced.num_experts.source``) and the ``num_experts``
experts held here, ``moe_intermediate_size`` wide; the output head is the
tied embedding over the rows of the vocabulary held here.

**The experts are counted at the expected picks**: a token makes
``num_experts_per_tok`` picks over all the router's outputs, and under the
uniform ids and random weights of the benchmark's traffic ``held / outputs``
of them land on an expert held here (one pick a token at 8 of 32 and
top-4).  What a run's routing really sent here is the step metric
``moe_load``, which no benchmark reader sees yet (PERF.md, section 7).

Counted: the matrix multiplications of the layers by kind, of the routers,
of the held experts at the expected picks and of the head, and causal
attention (half of the full score matrix) in the attention layers.  Not
counted: the embedding lookup, the convolution's taps, norms, gates,
rotary positions, activations, the softmax, the sort and the gathers around
the experts, the loss, the optimizer, and anything recomputed in the
backward pass.  The backward pass needs twice the forward's operations.
"""

from flops import least_seconds  # noqa: F401  (the roofline, shared)


def _sizes(cfg):
    heads = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // heads
    kinds = cfg["layer_types"]
    n_layers = len(kinds)
    return dict(
        h=cfg["hidden_size"],
        d=d,
        q=heads * d,
        kv=cfg["num_key_value_heads"] * d,
        m=cfg["intermediate_size"],
        m_expert=cfg["moe_intermediate_size"],
        v=cfg["vocab_size"],
        taps=cfg["conv_L_cache"],
        held=cfg["num_experts"],
        outputs=cfg["reduced"]["num_experts"]["source"]
        if "num_experts" in cfg.get("reduced", {}) else cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        n_conv=kinds.count("conv"),
        n_attention=kinds.count("full_attention"),
        n_dense=min(cfg["num_dense_layers"], n_layers),
        n_routed=max(n_layers - cfg["num_dense_layers"], 0),
    )


def conv_op_params(cfg):
    """B, C, x and the output projection, and the taps."""
    z = _sizes(cfg)
    return 4 * z["h"] * z["h"] + z["taps"] * z["h"]


def attention_op_params(cfg):
    """q, k, v, o and the two norms' scales over the head dim."""
    z = _sizes(cfg)
    return z["h"] * (z["q"] + 2 * z["kv"]) + z["q"] * z["h"] + 2 * z["d"]


def dense_mlp_params(cfg):
    z = _sizes(cfg)
    return 3 * z["h"] * z["m"]


def expert_params(cfg):
    z = _sizes(cfg)
    return 3 * z["h"] * z["m_expert"]


def router_params(cfg):
    """The router's matrix and the selection bias."""
    z = _sizes(cfg)
    return z["h"] * z["outputs"] + z["outputs"]


def n_params(cfg):
    """Held here: the layers, the final norm and the tied embedding."""
    z = _sizes(cfg)
    ops = (z["n_conv"] * conv_op_params(cfg)
           + z["n_attention"] * attention_op_params(cfg))
    ffns = (z["n_dense"] * dense_mlp_params(cfg)
            + z["n_routed"] * (
                router_params(cfg) + z["held"] * expert_params(cfg)))
    norms = 2 * z["h"] * (z["n_conv"] + z["n_attention"]) + z["h"]
    return ops + ffns + norms + z["v"] * z["h"]


def expected_picks_here(cfg):
    """Picks a token makes on the experts held here, under uniform
    routing."""
    z = _sizes(cfg)
    return z["top_k"] * z["held"] / z["outputs"]


def non_expert_matmul_flops_per_token(cfg):
    """Forward multiply-adds x 2: the ops' projections, the dense MLPs and
    the routers."""
    z = _sizes(cfg)
    conv = 2 * 4 * z["h"] * z["h"]
    attention = 2 * z["h"] * (z["q"] + 2 * z["kv"]) + 2 * z["q"] * z["h"]
    return (
        z["n_conv"] * conv + z["n_attention"] * attention
        + z["n_dense"] * 2 * dense_mlp_params(cfg)
        + z["n_routed"] * 2 * z["h"] * z["outputs"]
    )


def expert_flops_per_token(cfg):
    """Forward, all routed layers, at the expected picks."""
    z = _sizes(cfg)
    return z["n_routed"] * expected_picks_here(cfg) * 2 * expert_params(cfg)


def head_flops_per_token(cfg):
    z = _sizes(cfg)
    return 2 * z["h"] * z["v"]


def attention_flops_per_token(cfg, seq):
    """Forward QK^T and PV of one attention layer under a causal mask."""
    return 2 * seq * _sizes(cfg)["q"]


def forward_matmul_flops_per_token(cfg):
    return (non_expert_matmul_flops_per_token(cfg)
            + expert_flops_per_token(cfg) + head_flops_per_token(cfg))


def train_flops_per_token(cfg, seq):
    """Forward + backward operations one token of a dense causal row needs."""
    forward = (
        forward_matmul_flops_per_token(cfg)
        + _sizes(cfg)["n_attention"] * attention_flops_per_token(cfg, seq)
    )
    return 3 * forward


def head_share_of_matmul_flops(cfg):
    return head_flops_per_token(cfg) / forward_matmul_flops_per_token(cfg)


def grouped_matmul_cost(cfg, rows, seq, itemsize=2):
    """What the grouped-product kernels of one step must do for ``rows``
    rows at the expected picks: ``(flops, bytes)``.  A routed layer runs
    the gate-and-up product and the down product forward, and for each the
    rows' gradient and the weights' gradient backward: nine multiplications
    of ``pairs x hidden x moe_intermediate_size``, two operations each.
    Bytes: each product reads its two operands and writes its result once
    (the weights of all the held experts: every one is visited)."""
    z = _sizes(cfg)
    pairs = rows * seq * expected_picks_here(cfg)
    h, m, held = z["h"], z["m_expert"], z["held"]
    flops = z["n_routed"] * 9 * 2 * pairs * h * m
    x, gu, act = pairs * h, pairs * 2 * m, pairs * m
    w_gu, w_down = held * h * 2 * m, held * m * h
    forward = (x + w_gu + gu) + (act + w_down + x)
    rows_grad = (gu + w_gu + x) + (x + w_down + act)
    weights_grad = (x + gu + w_gu) + (act + x + w_down)
    bytes_moved = z["n_routed"] * itemsize * (
        forward + rows_grad + weights_grad)
    return flops, bytes_moved
