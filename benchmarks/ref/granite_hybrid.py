"""Plain reference of ``benchmarks/configs/granite-4.0-h-micro.json``.

The forward pass and the loss of a Granite-4.0-H decoder as published
(``model_type: granitemoehybrid`` with ``num_local_experts`` 0, so dense):
``embedding_multiplier`` times the token embedding; per layer RMSNorm ->
mixer -> residual, RMSNorm -> gated MLP of ``shared_intermediate_size`` ->
residual, both residual branches times ``residual_multiplier``; a final
RMSNorm; the tied output head divided by ``logits_scaling``; mean token
cross-entropy.  ``layer_types`` names each layer's mixer:

* ``attention``: grouped-query, no bias, no rotary or other position term
  (``position_embedding_type: nope``), scores times ``attention_multiplier``
  (1/64 here, not ``1/sqrt(head_dim)``), causal softmax.
* ``mamba`` (Mamba-2): ``z, x, B, C, dt`` projected without bias; a
  depthwise causal convolution of ``mamba_d_conv`` taps with bias, then
  SiLU, over ``x, B, C``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x)
  X_t``, ``y_t = C_t . S_t + D X_t`` with ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; ``RMSNorm(y * silu(z))`` over the whole inner width
  (the gate comes before the norm); output projection.

Plain ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``.
The scan is the recurrence itself, token by token (``lax.scan`` over the
sequence on a (heads, d_state, d_head) state); the convolution is its taps
written out as shifted multiply-adds; attention runs in blocks of query rows
so the (32, 8192, 8192) scores never exist at once.

It shares no code with the program.  What it has to know of the program is
only where each weight sits in the parameter tree (``_WEIGHTS``).
Departures from the published description: the published ``in_proj`` and
``conv1d`` are one matrix each, the program keeps them cut at their own
boundaries (z | x | B | C | dt; x | B | C), which is the same map; one group
of B and C only (``mamba_n_groups`` 1, as published).
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512

_WEIGHTS = {
    "embed": ("embed_tokens",),
    "final_norm": ("final_norm", "scale"),
}
_LAYER_WEIGHTS = {
    "input_norm": ("input_norm", "scale"),
    "post_norm": ("post_norm", "scale"),
    "gate": ("mlp", "gate_proj", "kernel"),
    "up": ("mlp", "up_proj", "kernel"),
    "down": ("mlp", "down_proj", "kernel"),
}
# Kernels: q (h, heads, d), k/v (h, kv_heads, d), o (heads, d, h).
_ATTENTION_WEIGHTS = {
    name: ("attention", f"{name}_proj", "kernel") for name in "qkvo"
}
# Projections (h, width); convolutions (taps, channels), the last tap on the
# current token; dt_bias, A_log, D (heads,); norm (inner,); out (inner, h).
_MAMBA_WEIGHTS = {
    **{name: ("mamba", f"{name}_proj", "kernel")
       for name in ("z", "x", "b", "c", "dt", "out")},
    **{f"conv_{name}": ("mamba", f"conv_{name}") for name in "xbc"},
    **{f"conv_{name}_bias": ("mamba", f"conv_{name}_bias") for name in "xbc"},
    "dt_bias": ("mamba", "dt_bias"),
    "A_log": ("mamba", "A_log"),
    "D": ("mamba", "D"),
    "norm": ("mamba", "norm"),
}


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return jnp.asarray(tree, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _attention(q, k, v, scale):
    """Causal grouped-query attention of one row.  q: (s, heads, d);
    k, v: (s, kv_heads, d)."""
    s, heads, d = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of {block}")
    key_pos = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        query_pos = start + jnp.arange(block)
        mask = key_pos[None, :] <= query_pos[:, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))
    return out.reshape(s, heads, d)


def _causal_conv(x, taps, bias):
    """x: (s, channels); taps: (width, channels).  Token t reads tokens
    t - width + 1 .. t, the last tap on t itself."""
    width, s = taps.shape[0], x.shape[0]
    out = bias
    for back in range(width):
        shifted = jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[: s - back]], 0)
        out = out + shifted * taps[width - 1 - back]
    return out


def _selective_scan(x, dt, a, b, c):
    """The recurrence, one token a step.  x: (s, heads, d_head); dt: (s,
    heads); a: (heads,); b, c: (s, d_state) -> y: (s, heads, d_head)."""
    heads, d_head = x.shape[1:]

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        decay = jnp.exp(dt_t * a)[:, None, None]
        state = decay * state + (
            dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None, :])
        return state, jnp.einsum("n,hnp->hp", c_t, state)

    state = jnp.zeros((heads, b.shape[1], d_head), jnp.float32)
    return jax.lax.scan(step, state, (x, dt, b, c))[1]


def _mamba(cfg, w, h):
    heads, d_head = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    z = h @ w["z"]
    x = jax.nn.silu(_causal_conv(h @ w["x"], w["conv_x"], w["conv_x_bias"]))
    b = jax.nn.silu(_causal_conv(h @ w["b"], w["conv_b"], w["conv_b_bias"]))
    c = jax.nn.silu(_causal_conv(h @ w["c"], w["conv_c"], w["conv_c_bias"]))
    dt = jax.nn.softplus(h @ w["dt"] + w["dt_bias"])
    x = x.reshape(-1, heads, d_head)
    y = _selective_scan(x, dt, -jnp.exp(w["A_log"]), b, c)
    y = (y + w["D"][:, None] * x).reshape(-1, heads * d_head)
    y = _rms_norm(y * jax.nn.silu(z), w["norm"], cfg["rms_norm_eps"])
    return y @ w["out"]


def hidden_of_row(cfg, params, ids):
    """ids: (s,) int32 -> the final norm's output, (s, hidden) float32.
    ``cfg`` holds the published keys."""
    eps, residual = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = cfg["embedding_multiplier"] * _get(params, _WEIGHTS["embed"])[ids]
    for i, kind in enumerate(cfg["layer_types"]):
        layer = params[f"layers_{i}"]
        names = dict(_LAYER_WEIGHTS, **(
            _MAMBA_WEIGHTS if kind == "mamba" else _ATTENTION_WEIGHTS))
        w = {name: _get(layer, path) for name, path in names.items()}
        h = _rms_norm(x, w["input_norm"], eps)
        if kind == "mamba":
            mixed = _mamba(cfg, w, h)
        else:
            q = jnp.einsum("se,ehd->shd", h, w["q"])
            k = jnp.einsum("se,ehd->shd", h, w["k"])
            v = jnp.einsum("se,ehd->shd", h, w["v"])
            mixed = jnp.einsum(
                "shd,hde->se",
                _attention(q, k, v, cfg["attention_multiplier"]), w["o"])
        x = x + residual * mixed
        h = _rms_norm(x, w["post_norm"], eps)
        x = x + residual * (
            (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"])
    return _rms_norm(x, _get(params, _WEIGHTS["final_norm"]), eps)


def logits_of_row(cfg, params, ids):
    """ids: (s,) int32 -> (s, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        head = _get(params, _WEIGHTS["embed"]).T
        return hidden_of_row(cfg, params, ids) @ head / cfg["logits_scaling"]


def loss_of_row(cfg, params, ids, labels):
    """Summed token cross-entropy of one row (the caller divides by the
    number of tokens of the whole batch).  The head and the softmax run in
    blocks of positions, so a row's logits never exist at once."""
    s = ids.shape[0]
    block = min(QUERY_BLOCK, s)
    with jax.default_matmul_precision("highest"):
        x = hidden_of_row(cfg, params, ids)
        head = _get(params, _WEIGHTS["embed"]).T / cfg["logits_scaling"]

        def one_block(start):
            xb = jax.lax.dynamic_slice_in_dim(x, start, block, 0)
            lb = jax.lax.dynamic_slice_in_dim(labels, start, block, 0)
            logp = jax.nn.log_softmax(xb @ head, axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

        return jnp.sum(jax.lax.map(one_block, jnp.arange(0, s, block)))
