"""Plain reference of ``benchmarks/configs/trinity-mini.json``.

The forward pass and the loss of an AFMoE decoder (``model_type: afmoe``,
arcee-ai's Trinity) from the keys of its published ``config.json``.  What
is not a key of that file is from the family's published modelling code
and description (gated attention, QK-norm, 3 : 1 local/global attention
with no position term on the global layers, sandwich norm, sigmoid routing
with a selection bias) and is marked **(A)** here and listed under
``assumed`` in the configuration's file.  No bias anywhere;
``rms_norm_eps`` 1e-5; ``hidden_act`` silu.

* ``h0 = sqrt(hidden_size) * E[ids]`` (``mup_enabled``; that the switch
  scales the embedding by sqrt(hidden_size): **(A)**).
* Layer ``i``: ``h += N2(attn_i(N1(h)))`` then ``h += N4(ffn_i(N3(h)))``,
  four RMSNorms with their own scales **(A)**.  ``logits = Nf(h) W_head``,
  ``W_head`` its own matrix (``tie_word_embeddings`` false).
* ``attn_i`` with ``n = N1(h)``: ``q = W_q n`` (``num_attention_heads`` x
  ``head_dim``), ``k = W_k n``, ``v = W_v n`` (``num_key_value_heads`` x
  ``head_dim``), ``g = W_g n`` (as wide as q) **(A)**; RMSNorm over the
  head dim on q and on k, each its own scale **(A)**.  If ``layer_types[i]``
  is ``sliding_attention``: half-split rotary positions over the whole head
  dim (``rope_theta``, no scaling), and key ``j`` is seen by query ``t``
  iff ``0 <= t - j < sliding_window`` (the window counts the query's own
  position: **(A)**).  If ``full_attention``: no position term at all
  **(A)** and ``0 <= t - j``.  Scores over ``sqrt(head_dim)``, softmax,
  grouped-query; ``out = W_o (softmax(...) v * sigmoid(g))``.
* ``ffn_i``, ``i < num_dense_layers``: SwiGLU at ``intermediate_size``.
* ``ffn_i`` of the later layers, with ``n = N3(h)``: ``s = sigmoid(W_r n)``
  over all the router's outputs (``score_func`` sigmoid; ``n_group`` and
  ``topk_group`` 1: no grouped selection); the picks are the
  ``num_experts_per_tok`` largest of ``s + b``, ``b`` a per-expert vector
  that enters the selection only **(A)**; ``w = s[picks] / (sum s[picks] +
  1e-20)`` (``route_norm``; the epsilon **(A)**) times ``route_scale``;
  ``out = SwiGLU_shared(n) + sum_picks w_e SwiGLU_e(n)``, the routed
  experts at ``moe_intermediate_size``, the shared one at
  ``moe_intermediate_size x num_shared_experts``, unweighted.
  ``load_balance_coeff`` is read by nothing: ``b`` is a leaf of the
  parameter tree that nothing updates (the program starts it where its
  first batch routes evenly; the reference takes it as it finds it).

**The share.**  The file's ``num_experts`` is the number of experts held
here, the block ``expert_block`` of the router's outputs (the router's
width is read off its weight: 128 as published).  Only the held experts'
terms are added; what the absent experts would have given is left out,
here as in the program, and the shared expert, whole on every chip that
shares the layer, is added once.  The experts are a plain loop over the
held ids with 0/1 masks over all the tokens: no sort, no grouped product.

Plain ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``;
attention is explicit scores under an explicit ``(t - j)`` mask, in blocks
of query rows so that a row's (32, 8192, 8192) scores never exist at once.
It shares no code with the program.  What it has to know of the program is
where each weight sits in the parameter tree.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
ROUTE_NORM_EPS = 1e-20

_EMBED = ("embed_tokens",)
_HEAD = ("lm_head", "kernel")  # (hidden, vocab)
_FINAL_NORM = ("final_norm", "scale")
# N1, N2, N3, N4.
_NORMS = {
    "input_norm": ("input_norm", "scale"),
    "mixer_out_norm": ("mixer_out_norm", "scale"),
    "post_norm": ("post_norm", "scale"),
    "ffn_out_norm": ("ffn_out_norm", "scale"),
}
# Kernels: q and the gate (hidden, heads, d), k/v (hidden, kv_heads, d),
# o (heads, d, hidden); the head norms' scales (d,).
_ATTENTION = {
    **{name: ("attention", f"{name}_proj", "kernel") for name in "qkvo"},
    "g": ("attention", "gate_proj", "kernel"),
    "q_norm": ("attention", "q_norm"),
    "k_norm": ("attention", "k_norm"),
}
_MLP = {name: ("mlp", f"{name}_proj", "kernel")
        for name in ("gate", "up", "down")}
# router (hidden, outputs); bias (outputs,); gate/up (held, hidden, m);
# down (held, m, hidden); the shared expert's three (hidden, m), (m, hidden).
_EXPERTS = {
    "router": ("experts", "router"),
    "bias": ("experts", "expert_bias"),
    **{name: ("experts", f"{name}_proj") for name in ("gate", "up", "down")},
    **{f"shared_{name}": ("experts", "shared", f"{name}_proj", "kernel")
       for name in ("gate", "up", "down")},
}


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return jnp.asarray(tree, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """Half-split rotary positions 0..s-1.  x: (s, heads, d)."""
    s, _, d = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    sin, cos = jnp.sin(angle)[:, None], jnp.cos(angle)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _masked_attention(q, k, v, window):
    """q: (s, heads, d); k, v: (s, kv_heads, d); scores over sqrt(d).  Key
    ``j`` is seen by query ``t`` iff ``0 <= t - j`` and, with a window,
    ``t - j < window``."""
    s, heads, d = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of {block}")
    key_pos = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(float(d))
        back = (start + jnp.arange(block))[:, None] - key_pos[None, :]
        mask = back >= 0
        if window is not None:
            mask = mask & (back < window)
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    return jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, heads, d)


def _attention(cfg, w, n, kind):
    eps = cfg["rms_norm_eps"]
    q = _rms_norm(jnp.einsum("se,ehd->shd", n, w["q"]), w["q_norm"], eps)
    k = _rms_norm(jnp.einsum("se,ehd->shd", n, w["k"]), w["k_norm"], eps)
    v = jnp.einsum("se,ehd->shd", n, w["v"])
    gate = jax.nn.sigmoid(jnp.einsum("se,ehd->shd", n, w["g"]))
    window = None
    if kind == "sliding_attention":
        q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
        window = cfg["sliding_window"]
    elif kind != "full_attention":
        raise ValueError(f"no layer kind {kind!r} in this family")
    out = _masked_attention(q, k, v, window) * gate
    return jnp.einsum("shd,hde->se", out, w["o"])


def _swiglu(n, gate, up, down):
    return (jax.nn.silu(n @ gate) * (n @ up)) @ down


def pick_weights(cfg, router, bias, n):
    """(s, outputs): each token's weight on every expert of the whole
    model, zero off its picks.  The picks are the largest of ``s + b``; the
    weights are the scores alone, normalised over the picks."""
    scores = jax.nn.sigmoid(n @ router)
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[:, -cfg["num_experts_per_tok"]]
    chosen = scores * (biased >= kth[:, None])
    if cfg["route_norm"]:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + ROUTE_NORM_EPS)
    return chosen * cfg["route_scale"]


def experts_of_block(cfg, w, n, block, held):
    """The held experts' part of the routed sum: experts ``block * held ..
    (block + 1) * held - 1`` of the router's outputs, whose weights are
    ``w["gate"][j]``, ``w["up"][j]``, ``w["down"][j]``.  n: (s, hidden).
    Returns the partial sum (without the shared expert) and every token's
    weights, (s, outputs)."""
    weights = pick_weights(cfg, w["router"], w["bias"], n)
    out = jnp.zeros_like(n)
    for j in range(held):
        share = weights[:, block * held + j]  # 0 where the token went elsewhere
        out = out + share[:, None] * _swiglu(
            n, w["gate"][j], w["up"][j], w["down"][j])
    return out, weights


def shared_expert(cfg, w, n):
    """What every chip that shares the layer computes alike: the shared
    expert of every token, unweighted (zero where the family has none)."""
    if not cfg["num_shared_experts"]:
        return jnp.zeros_like(n)
    return _swiglu(n, w["shared_gate"], w["shared_up"], w["shared_down"])


def _routed_ffn(cfg, w, n):
    routed, weights = experts_of_block(
        cfg, w, n, cfg.get("expert_block", 0), cfg["num_experts"])
    return routed + shared_expert(cfg, w, n), weights


def _expert_names(cfg):
    return {name: path for name, path in _EXPERTS.items()
            if cfg["num_shared_experts"] or not name.startswith("shared_")}


def _subtree(tree, names):
    """``names``' weights from a layer's ``attention`` or ``experts``
    subtree (the paths without their first key)."""
    return {name: _get(tree, path[1:]) for name, path in names.items()}


def _layer_weights(cfg, layer, i):
    names = dict(_NORMS, **_ATTENTION)
    names.update(_MLP if i < cfg["num_dense_layers"] else _expert_names(cfg))
    return {name: _get(layer, path) for name, path in names.items()}


def _forward(cfg, params, ids):
    """-> the final norm's output, each attention layer's input, and each
    routed layer's input and weights."""
    eps, attended, routed = cfg["rms_norm_eps"], [], []
    x = _get(params, _EMBED)[ids]
    if cfg["mup_enabled"]:
        x = x * jnp.sqrt(float(cfg["hidden_size"]))
    for i, kind in enumerate(cfg["layer_types"]):
        w = _layer_weights(cfg, params[f"layers_{i}"], i)
        n = _rms_norm(x, w["input_norm"], eps)
        attended.append(n)
        x = x + _rms_norm(_attention(cfg, w, n, kind), w["mixer_out_norm"], eps)
        n = _rms_norm(x, w["post_norm"], eps)
        if i < cfg["num_dense_layers"]:
            ffn = _swiglu(n, w["gate"], w["up"], w["down"])
        else:
            ffn, weights = _routed_ffn(cfg, w, n)
            routed.append((n, weights))
        x = x + _rms_norm(ffn, w["ffn_out_norm"], eps)
    return _rms_norm(x, _get(params, _FINAL_NORM), eps), attended, routed


def hidden_of_row(cfg, params, ids):
    """ids: (s,) int32 -> the final norm's output, (s, hidden) float32.
    ``cfg`` holds the published keys as the configuration's file has them."""
    return _forward(cfg, params, ids)[0]


def picks_of_row(cfg, params, ids):
    """The picks the reference makes, for a comparison of routing: one
    (s, outputs) 0/1 mask a routed layer (a sigmoid is never 0, so a weight
    is 0 only off the picks)."""
    with jax.default_matmul_precision("highest"):
        return [weights > 0 for _n, weights in _forward(cfg, params, ids)[2]]


def routed_inputs_of_row(cfg, params, ids):
    """What each routed layer's FFN is fed (N3's output), (s, hidden)
    float32 a routed layer: for a check of that layer alone."""
    with jax.default_matmul_precision("highest"):
        return [n for n, _weights in _forward(cfg, params, ids)[2]]


def attention_inputs_of_row(cfg, params, ids):
    """What each layer's attention is fed (N1's output), (s, hidden)
    float32 a layer, in the order of ``layer_types``."""
    with jax.default_matmul_precision("highest"):
        return _forward(cfg, params, ids)[1]


def routed_layer(cfg, experts, n):
    """One routed layer's FFN on a given input (the held experts' part and
    the shared expert), from the layer's own subtree of the parameters
    (``params[layer]["experts"]``): what ``hidden_of_row`` hands N4 there."""
    with jax.default_matmul_precision("highest"):
        return _routed_ffn(
            cfg, _subtree(experts, _expert_names(cfg)),
            jnp.asarray(n, jnp.float32))[0]


def attention_layer(cfg, attention, n, kind):
    """One attention layer of ``kind`` on a given input, from the layer's
    own subtree (``params[layer]["attention"]``): what ``hidden_of_row``
    hands N2 there."""
    with jax.default_matmul_precision("highest"):
        return _attention(
            cfg, _subtree(attention, _ATTENTION),
            jnp.asarray(n, jnp.float32), kind)


def logits_of_row(cfg, params, ids):
    """ids: (s,) int32 -> (s, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        return hidden_of_row(cfg, params, ids) @ _get(params, _HEAD)


def loss_of_row(cfg, params, ids, labels):
    """Summed token cross-entropy of one row (the caller divides by the
    number of tokens of the whole batch).  The head and the softmax run in
    blocks of positions, so a row's logits never exist at once."""
    s = ids.shape[0]
    block = min(QUERY_BLOCK, s)
    with jax.default_matmul_precision("highest"):
        x = hidden_of_row(cfg, params, ids)
        head = _get(params, _HEAD)

        def one_block(start):
            xb = jax.lax.dynamic_slice_in_dim(x, start, block, 0)
            lb = jax.lax.dynamic_slice_in_dim(labels, start, block, 0)
            logp = jax.nn.log_softmax(xb @ head, axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

        return jnp.sum(jax.lax.map(one_block, jnp.arange(0, s, block)))
