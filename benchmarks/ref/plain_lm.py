"""Plain reference of the configurations under ``benchmarks/configs/``.

The forward pass and the loss of a decoder-only LM as Mistral-7B-v0.3 and
Codestral-22B-v0.1 publish it (``model_type: mistral``): token embedding,
per layer RMSNorm -> grouped-query attention with rotary embeddings
(half-split rotation, as in the published implementation) -> residual ->
RMSNorm -> SwiGLU MLP -> residual, a final RMSNorm, an untied output head,
mean token cross-entropy.  No bias, no sliding window (the config's is
null).  Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, no kernel, no cache, no batching
tricks; attention runs in blocks of query rows so the scores of a 4096-token
row never exist at once.

It shares no code with the program.  What it has to know of the program is
only where each weight sits in the parameter tree (``_WEIGHTS``).
Departures from the published description: none.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512

# name here -> path in the program's parameter tree (layer paths take the
# layer's index).  Kernels: q (h, heads, d), k/v (h, kv_heads, d),
# o (heads, d, h), MLP (h, m) / (m, h), head (h, vocab).
_WEIGHTS = {
    "embed": ("embed_tokens",),
    "final_norm": ("final_norm", "scale"),
    "head": ("lm_head", "kernel"),
}
_LAYER_WEIGHTS = {
    "input_norm": ("input_norm", "scale"),
    "post_norm": ("post_norm", "scale"),
    "q": ("attention", "q_proj", "kernel"),
    "k": ("attention", "k_proj", "kernel"),
    "v": ("attention", "v_proj", "kernel"),
    "o": ("attention", "o_proj", "kernel"),
    "gate": ("mlp", "gate_proj", "kernel"),
    "up": ("mlp", "up_proj", "kernel"),
    "down": ("mlp", "down_proj", "kernel"),
}


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return jnp.asarray(tree, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """x: (s, heads, d), positions 0..s-1."""
    s, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
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
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(float(d))
        query_pos = start + jnp.arange(block)
        mask = key_pos[None, :] <= query_pos[:, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))
    return out.reshape(s, heads, d)


def hidden_of_row(cfg, params, ids):
    """ids: (s,) int32 -> the final norm's output, (s, hidden) float32.
    ``cfg`` holds the published keys (``num_hidden_layers``, ``rope_theta``,
    ``rms_norm_eps``)."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = _get(params, _WEIGHTS["embed"])[ids]
    for i in range(cfg["num_hidden_layers"]):
        layer = params[f"layers_{i}"]
        w = {name: _get(layer, path) for name, path in _LAYER_WEIGHTS.items()}
        h = _rms_norm(x, w["input_norm"], eps)
        q = _rotary(jnp.einsum("se,ehd->shd", h, w["q"]), theta)
        k = _rotary(jnp.einsum("se,ehd->shd", h, w["k"]), theta)
        v = jnp.einsum("se,ehd->shd", h, w["v"])
        x = x + jnp.einsum("shd,hde->se", _attention(q, k, v), w["o"])
        h = _rms_norm(x, w["post_norm"], eps)
        x = x + (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]
    return _rms_norm(x, _get(params, _WEIGHTS["final_norm"]), eps)


def logits_of_row(cfg, params, ids):
    """ids: (s,) int32 -> (s, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        return hidden_of_row(cfg, params, ids) @ _get(params, _WEIGHTS["head"])


def loss_of_row(cfg, params, ids, labels):
    """Summed token cross-entropy of one row (the caller divides by the
    number of tokens of the whole batch).  The head and the softmax run in
    blocks of positions, so a row's logits never exist at once."""
    s = ids.shape[0]
    block = min(QUERY_BLOCK, s)
    with jax.default_matmul_precision("highest"):
        x = hidden_of_row(cfg, params, ids)
        head = _get(params, _WEIGHTS["head"])

        def one_block(start):
            xb = jax.lax.dynamic_slice_in_dim(x, start, block, 0)
            lb = jax.lax.dynamic_slice_in_dim(labels, start, block, 0)
            logp = jax.nn.log_softmax(xb @ head, axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

        return jnp.sum(jax.lax.map(one_block, jnp.arange(0, s, block)))
