"""Operations and bytes a training step of an AFMoE configuration (Trinity)
needs, from the file's published keys (``flops.py``'s sibling for
``model_type: afmoe``).  Nothing here reads the program.

A decoder of attention layers laid out by ``layer_types``: every layer has
grouped-query attention at ``head_dim`` with a gate projection as wide as
q; a ``sliding_attention`` layer sees the last ``sliding_window`` keys, a
``full_attention`` layer all the earlier ones.  The first
``num_dense_layers`` layers have the gated MLP of ``intermediate_size``,
the later ones a router over all the model's experts
(``reduced.num_experts.source``), the ``num_experts`` experts held here,
``moe_intermediate_size`` wide, and ``num_shared_experts`` shared experts of
that width, which every token passes; embedding and head are two matrices
over the rows of the vocabulary held here.

**Attention is counted over the pairs the mask keeps**, exactly: a row of
``seq`` tokens has ``sum_t min(t + 1, window)`` (query, key) pairs under a
window and ``seq (seq + 1) / 2`` without one.  **The routed experts are
counted at the expected picks**: a token makes ``num_experts_per_tok`` picks
over all the router's outputs, and under the uniform ids and random weights
of the benchmark's traffic ``held / outputs`` of them land on an expert held
here (one pick a token at 16 of 128 and top-8); the shared expert is
counted for every token.  What a run's routing really sent here is the step
metric ``moe_load``, which no benchmark reader sees yet (PERF.md, section 7).

Counted: the matrix multiplications of the layers (q, k, v, gate, o; the
dense MLP; the router, the held experts at the expected picks, the shared
expert) and of the head, and attention's two products over the kept pairs.
Not counted: the embedding lookup, norms, the gate's sigmoid, rotary
positions, activations, the softmax, the sort and the gathers around the
experts, the loss, the optimizer, and anything recomputed in the backward
pass.  The backward pass needs twice the forward's operations.
"""

from flops import least_seconds  # noqa: F401  (the roofline, shared)

WINDOWED = "sliding_attention"


def _sizes(cfg):
    d = cfg["head_dim"]
    kinds = cfg["layer_types"]
    n_layers = len(kinds)
    return dict(
        h=cfg["hidden_size"],
        d=d,
        q=cfg["num_attention_heads"] * d,
        kv=cfg["num_key_value_heads"] * d,
        m=cfg["intermediate_size"],
        m_expert=cfg["moe_intermediate_size"],
        m_shared=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        v=cfg["vocab_size"],
        window=cfg["sliding_window"],
        held=cfg["num_experts"],
        outputs=cfg["reduced"]["num_experts"]["source"]
        if "num_experts" in cfg.get("reduced", {}) else cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        n_layers=n_layers,
        n_sliding=kinds.count(WINDOWED),
        n_full=n_layers - kinds.count(WINDOWED),
        n_dense=min(cfg["num_dense_layers"], n_layers),
        n_routed=max(n_layers - cfg["num_dense_layers"], 0),
    )


def attention_params(cfg):
    """q, gate and o, k and v, and the two norms' scales over the head
    dim."""
    z = _sizes(cfg)
    return 3 * z["h"] * z["q"] + 2 * z["h"] * z["kv"] + 2 * z["d"]


def dense_mlp_params(cfg):
    z = _sizes(cfg)
    return 3 * z["h"] * z["m"]


def expert_params(cfg):
    z = _sizes(cfg)
    return 3 * z["h"] * z["m_expert"]


def shared_expert_params(cfg):
    z = _sizes(cfg)
    return 3 * z["h"] * z["m_shared"]


def router_params(cfg):
    """The router's matrix and the selection bias."""
    z = _sizes(cfg)
    return z["h"] * z["outputs"] + z["outputs"]


def n_params(cfg):
    """Held here: the layers (four norms each), the final norm, the
    embedding and the head."""
    z = _sizes(cfg)
    layers = z["n_layers"] * (attention_params(cfg) + 4 * z["h"])
    ffns = (z["n_dense"] * dense_mlp_params(cfg)
            + z["n_routed"] * (
                router_params(cfg) + shared_expert_params(cfg)
                + z["held"] * expert_params(cfg)))
    return layers + ffns + z["h"] + 2 * z["v"] * z["h"]


def expected_picks_here(cfg):
    """Picks a token makes on the experts held here, under uniform
    routing."""
    z = _sizes(cfg)
    return z["top_k"] * z["held"] / z["outputs"]


def attention_projection_flops_per_token(cfg):
    """Forward multiply-adds x 2 of q, k, v, the gate and o, one layer."""
    z = _sizes(cfg)
    return 2 * z["h"] * (2 * z["q"] + 2 * z["kv"]) + 2 * z["q"] * z["h"]


def non_expert_matmul_flops_per_token(cfg):
    """Forward: the attention projections of every layer, the dense MLPs,
    the routers and the shared experts."""
    z = _sizes(cfg)
    return (
        z["n_layers"] * attention_projection_flops_per_token(cfg)
        + z["n_dense"] * 2 * dense_mlp_params(cfg)
        + z["n_routed"] * (
            2 * z["h"] * z["outputs"] + 2 * shared_expert_params(cfg))
    )


def expert_flops_per_token(cfg):
    """Forward, all routed layers, the routed experts at the expected
    picks."""
    z = _sizes(cfg)
    return z["n_routed"] * expected_picks_here(cfg) * 2 * expert_params(cfg)


def head_flops_per_token(cfg):
    z = _sizes(cfg)
    return 2 * z["h"] * z["v"]


def kept_pairs(seq, window=None):
    """(query, key) pairs of one dense causal row: ``sum_t min(t + 1,
    window)``, all ``seq (seq + 1) / 2`` without a window."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops_per_token(cfg, seq, kind):
    """Forward QK^T and PV of one layer of ``kind`` over the pairs its
    mask keeps: two operations a pair a q column, twice."""
    z = _sizes(cfg)
    pairs = kept_pairs(seq, z["window"] if kind == WINDOWED else None)
    return 4 * z["q"] * pairs / seq


def forward_matmul_flops_per_token(cfg):
    return (non_expert_matmul_flops_per_token(cfg)
            + expert_flops_per_token(cfg) + head_flops_per_token(cfg))


def forward_attention_flops_per_token(cfg, seq):
    return sum(attention_flops_per_token(cfg, seq, kind)
               for kind in cfg["layer_types"])


def train_flops_per_token(cfg, seq):
    """Forward + backward operations one token of a dense causal row needs."""
    return 3 * (forward_matmul_flops_per_token(cfg)
                + forward_attention_flops_per_token(cfg, seq))


def head_share_of_matmul_flops(cfg):
    return head_flops_per_token(cfg) / forward_matmul_flops_per_token(cfg)


def attention_kernel_cost(cfg, rows, seq, itemsize=2):
    """What the attention kernels of one step (all layers, forward and
    backward) must do for ``rows`` dense causal rows: ``(flops, bytes)``.
    Forward is two multiplications (QK^T, PV), backward four (dV, dP, dQ,
    dK), each over the pairs the layer's mask keeps; a kernel that visits
    a whole block the window half covers spends that time and earns
    nothing.  Bytes as ``flops.py`` counts them: q, k, v and the output
    once forward; q, k, v, the output and its gradient read and dq, dk, dv
    written once backward."""
    z = _sizes(cfg)
    pairs = (z["n_sliding"] * kept_pairs(seq, z["window"])
             + z["n_full"] * kept_pairs(seq))
    flops = rows * 6 * 2 * pairs * z["q"]
    qo, kv = seq * z["q"], seq * z["kv"]
    bytes_moved = rows * z["n_layers"] * itemsize * (6 * qo + 6 * kv)
    return flops, bytes_moved


def grouped_matmul_cost(cfg, rows, seq, itemsize=2):
    """What the grouped-product kernels of one step must do for ``rows``
    rows at the expected picks: ``(flops, bytes)``, counted as
    ``flops_lfm2_moe.py`` counts them: nine multiplications of ``pairs x
    hidden x moe_intermediate_size`` a routed layer, each operand and
    result moved once (the weights of all the held experts: every one is
    visited).  The shared expert is no grouped product."""
    z = _sizes(cfg)
    pairs = rows * seq * expected_picks_here(cfg)
    h, m, held = z["h"], z["m_expert"], z["held"]
    flops = z["n_routed"] * 9 * 2 * pairs * h * m
    x, gu, act = pairs * h, pairs * 2 * m, pairs * m
    w_gu, w_down = held * h * 2 * m, held * m * h
    one_pass = (x + w_gu + gu) + (act + w_down + x)
    return flops, z["n_routed"] * itemsize * 3 * one_pass
