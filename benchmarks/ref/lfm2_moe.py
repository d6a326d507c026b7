"""Plain reference of ``benchmarks/configs/lfm2-8b-a1b.json``.

The forward pass and the loss of an LFM2 mixture-of-experts decoder
(``model_type: lfm2_moe``) as its published ``config.json`` and the
catalog's description give it: ``h = E[ids]``; per layer ``h += op(RMSNorm
(h))`` then ``h += ffn(RMSNorm(h))``; a final RMSNorm; ``logits = h E^T``;
mean token cross-entropy.  ``norm_eps`` 1e-5, no bias anywhere.

* ``op = conv`` (gated short convolution, ``conv_L_cache`` taps,
  ``conv_bias`` false): ``B, C, x`` projected from the normed state, ``u = B
  * x``, ``c_t = sum_j w_j * u_{t - L + 1 + j}`` (depthwise, causal, no bias,
  no activation), ``out = W_out (C * c)``.
* ``op = full_attention``: grouped-query attention; RMSNorm over the head
  dim on q and on k, each with its own scale, before half-split rotary
  positions (``rope_theta``) over the whole head dim; causal softmax of the
  scores over ``sqrt(head_dim)``; output projection.
* ``ffn`` of the first ``num_dense_layers`` layers: SwiGLU at
  ``intermediate_size``.
* ``ffn`` of the later layers: ``s = sigmoid(W_g n)`` over all the router's
  outputs; the picks are the ``num_experts_per_tok`` largest of ``s + b``
  (``use_expert_bias``: ``b`` enters the selection only); weights ``s[picks]
  / (sum s[picks] + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``out = sum_picks w_e SwiGLU_e(n)`` at
  ``moe_intermediate_size``.  No shared expert.

**The share.**  The file's ``num_experts`` is the number of experts held
here, the block ``expert_block`` of the router's outputs (the router's
width is read off its weight: 32 as published).  Only the held experts'
terms are added: what the absent experts would have given is left out, here
as in the program, and the partial sum is what goes on to the next layer.
The experts are a plain loop over the held ids with 0/1 masks over all the
tokens: no sort, no grouped product.

Plain ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``;
the convolution is its taps written out as shifted products; attention
runs in blocks of query rows so a row's (32, 8192, 8192) scores never exist
at once.  It shares no code with the program.  What it has to know of the
program is where each weight sits in the parameter tree.  Departures from
the published description, each also under ``assumed`` in the file: the
head is the embedding (tied: the family's convention, not a key of the
config); ``head_dim`` = hidden / heads (64: no such key); the published
``in_proj`` is one matrix, the program keeps it cut at its own boundaries
(B | C | x), which is the same map; ``b`` is zeros and stays so (the config
gives no rule to update it).
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512

_EMBED = ("embed_tokens",)
_FINAL_NORM = ("final_norm", "scale")
_NORMS = {
    "input_norm": ("input_norm", "scale"),
    "post_norm": ("post_norm", "scale"),
}
# Projections (hidden, hidden); taps (L, hidden), the last on the token itself.
_CONV = {
    **{name: ("conv", f"{name}_proj", "kernel")
       for name in ("b", "c", "x", "out")},
    "taps": ("conv", "conv"),
}
# Kernels: q (hidden, heads, d), k/v (hidden, kv_heads, d), o (heads, d,
# hidden); the norms' scales (d,).
_ATTENTION = {
    **{name: ("attention", f"{name}_proj", "kernel") for name in "qkvo"},
    "q_norm": ("attention", "q_norm"),
    "k_norm": ("attention", "k_norm"),
}
_MLP = {name: ("mlp", f"{name}_proj", "kernel")
        for name in ("gate", "up", "down")}
# router (hidden, outputs); bias (outputs,); gate/up (held, hidden, m);
# down (held, m, hidden).
_EXPERTS = {
    "router": ("experts", "router"),
    "bias": ("experts", "expert_bias"),
    **{name: ("experts", f"{name}_proj") for name in ("gate", "up", "down")},
}


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return jnp.asarray(tree, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _short_conv(w, n):
    b, c, x = n @ w["b"], n @ w["c"], n @ w["x"]
    u = b * x
    taps, s = w["taps"], n.shape[0]
    width = taps.shape[0]
    conv = jnp.zeros_like(u)
    for back in range(width):
        shifted = jnp.concatenate(
            [jnp.zeros((back, u.shape[1]), u.dtype), u[: s - back]], 0)
        conv = conv + shifted * taps[width - 1 - back]
    return (c * conv) @ w["out"]


def _rotary(x, theta):
    """Half-split rotary positions 0..s-1.  x: (s, heads, d)."""
    s, _, d = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    sin, cos = jnp.sin(angle)[:, None], jnp.cos(angle)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_attention(q, k, v):
    """q: (s, heads, d); k, v: (s, kv_heads, d); scores over sqrt(d)."""
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
        mask = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    return jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, heads, d)


def _attention(cfg, w, n):
    eps = cfg["norm_eps"]
    q = jnp.einsum("se,ehd->shd", n, w["q"])
    k = jnp.einsum("se,ehd->shd", n, w["k"])
    v = jnp.einsum("se,ehd->shd", n, w["v"])
    q = _rotary(_rms_norm(q, w["q_norm"], eps), cfg["rope_theta"])
    k = _rotary(_rms_norm(k, w["k_norm"], eps), cfg["rope_theta"])
    return jnp.einsum("shd,hde->se", _causal_attention(q, k, v), w["o"])


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
    weights = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6)
    return weights * cfg["routed_scaling_factor"]


def experts_of_block(cfg, w, n, block, held):
    """The held experts' part of the routed FFN: experts ``block * held ..
    (block + 1) * held - 1`` of the router's outputs, whose weights are
    ``w["gate"][j]``, ``w["up"][j]``, ``w["down"][j]``.  n: (s, hidden).
    Returns the partial sum and every token's weights, (s, outputs)."""
    weights = pick_weights(cfg, w["router"], w["bias"], n)
    out = jnp.zeros_like(n)
    for j in range(held):
        share = weights[:, block * held + j]  # 0 where the token went elsewhere
        out = out + share[:, None] * _swiglu(
            n, w["gate"][j], w["up"][j], w["down"][j])
    return out, weights


def _layer_weights(cfg, layer, i):
    kind = cfg["layer_types"][i]
    names = dict(_NORMS, **(_CONV if kind == "conv" else _ATTENTION))
    names.update(_MLP if i < cfg["num_dense_layers"] else _EXPERTS)
    return {name: _get(layer, path) for name, path in names.items()}


def _forward(cfg, params, ids):
    """-> the final norm's output, and each routed layer's input and
    weights."""
    eps, routed = cfg["norm_eps"], []
    x = _get(params, _EMBED)[ids]
    for i, kind in enumerate(cfg["layer_types"]):
        w = _layer_weights(cfg, params[f"layers_{i}"], i)
        n = _rms_norm(x, w["input_norm"], eps)
        x = x + (_short_conv(w, n) if kind == "conv" else _attention(cfg, w, n))
        n = _rms_norm(x, w["post_norm"], eps)
        if i < cfg["num_dense_layers"]:
            x = x + _swiglu(n, w["gate"], w["up"], w["down"])
        else:
            ffn, weights = experts_of_block(
                cfg, w, n, cfg.get("expert_block", 0), cfg["num_experts"])
            x = x + ffn
            routed.append((n, weights))
    return _rms_norm(x, _get(params, _FINAL_NORM), eps), routed


def hidden_of_row(cfg, params, ids):
    """ids: (s,) int32 -> the final norm's output, (s, hidden) float32.
    ``cfg`` holds the published keys as the configuration's file has them."""
    return _forward(cfg, params, ids)[0]


def picks_of_row(cfg, params, ids):
    """The picks the reference makes, for a comparison of routing: one
    (s, outputs) 0/1 mask a routed layer (a sigmoid is never 0, so a weight
    is 0 only off the picks)."""
    with jax.default_matmul_precision("highest"):
        return [weights > 0 for _n, weights in _forward(cfg, params, ids)[1]]


def routed_inputs_of_row(cfg, params, ids):
    """What each routed layer's experts are fed (the post-norm state),
    (s, hidden) float32 a routed layer: for a check of that layer alone."""
    with jax.default_matmul_precision("highest"):
        return [n for n, _weights in _forward(cfg, params, ids)[1]]


def routed_layer(cfg, experts, n):
    """The held experts' part of one routed layer on a given input, from
    the layer's own subtree of the parameters (``params[layer]["experts"]``
    ): what ``hidden_of_row`` adds to the stream there."""
    w = {name: _get(experts, path[1:]) for name, path in _EXPERTS.items()}
    with jax.default_matmul_precision("highest"):
        return experts_of_block(
            cfg, w, jnp.asarray(n, jnp.float32), cfg.get("expert_block", 0),
            cfg["num_experts"])[0]


def logits_of_row(cfg, params, ids):
    """ids: (s,) int32 -> (s, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        return hidden_of_row(cfg, params, ids) @ _get(params, _EMBED).T


def loss_of_row(cfg, params, ids, labels):
    """Summed token cross-entropy of one row (the caller divides by the
    number of tokens of the whole batch).  The head and the softmax run in
    blocks of positions, so a row's logits never exist at once."""
    s = ids.shape[0]
    block = min(QUERY_BLOCK, s)
    with jax.default_matmul_precision("highest"):
        x = hidden_of_row(cfg, params, ids)
        head = _get(params, _EMBED).T

        def one_block(start):
            xb = jax.lax.dynamic_slice_in_dim(x, start, block, 0)
            lb = jax.lax.dynamic_slice_in_dim(labels, start, block, 0)
            logp = jax.nn.log_softmax(xb @ head, axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

        return jnp.sum(jax.lax.map(one_block, jnp.arange(0, s, block)))
