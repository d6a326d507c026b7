"""State-space duality (Mamba-2): the selective scan in its chunked form,
and the depthwise causal convolution that feeds it.

One head keeps a state ``S`` of shape (d_state, d_head).  With a step
``dt_t > 0``, a decay rate ``A < 0`` a head, and ``B_t``, ``C_t`` of width
``d_state`` shared by all heads (one group):

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * B_t (x) X_t
    y_t = C_t . S_t

:func:`ssd_chunked` computes exactly this recurrence, never token by token
and never through an ``s x s`` matrix.  A row is cut into chunks of
``chunk`` tokens and four named pieces do the work:

* ``ssd/diag``: inside a chunk, the ``chunk x chunk`` product
  ``(C B^T) * decay`` applied to ``dt * X``, lower-triangular;
* ``ssd/chunk_state``: the state each chunk would close with had it
  started from zero;
* ``ssd/recurrence``: the recurrence over the ``s / chunk`` chunk states;
* ``ssd/state_out``: what the state a chunk started from adds to its
  outputs.

The logarithms of the decay, their cumulative sums and the chunk states are
float32 whatever the compute dtype (a cumulative sum over 256 steps of up
to -1.6 reaches -400: bf16 would keep two digits of it); matmul operands
are the compute dtype with float32 accumulation.  Plain XLA: the backward
pass is autodiff's, and under the model's per-layer recomputation nothing
of a chunk's ``chunk x chunk`` block outlives its layer.

State is not reset at packed document boundaries: callers must not pack.
"""

import jax
import jax.numpy as jnp


def _decay_cumsum(a):
    """Inclusive cumulative sum of the log decays along a chunk, float32."""
    return jnp.cumsum(a.astype(jnp.float32), axis=2)


def ssd_chunked(x, dt, A, B, C, chunk: int):
    """x: (b, s, h, p); dt: (b, s, h), already positive; A: (h,), negative;
    B, C: (b, s, n).  Returns y: (b, s, h, p) in ``x.dtype``.  ``s`` must
    be a multiple of ``chunk``."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    if s % chunk:
        raise ValueError(
            f"ssd_chunked: sequence {s} is not a multiple of the chunk "
            f"{chunk}; pad the row or choose a chunk that divides it"
        )
    c, f32, dtype = s // chunk, jnp.float32, x.dtype
    dt = dt.astype(f32).reshape(b, c, chunk, h)
    xc = x.reshape(b, c, chunk, h, p)
    Bc, Cc = B.reshape(b, c, chunk, n), C.reshape(b, c, chunk, n)
    # cs[l] = sum of the log decays of steps 0..l of the chunk.
    cs = _decay_cumsum(dt * A.astype(f32))  # (b, c, l, h)

    with jax.named_scope("ssd/diag"):
        # Step j's input reaches step l >= j decayed by exp(cs[l] - cs[j]).
        cs_h = cs.transpose(0, 1, 3, 2)  # (b, c, h, l)
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        # Masked before the exponential: above the diagonal the difference
        # is positive and may overflow.
        decay = jnp.exp(jnp.where(
            causal, cs_h[..., :, None] - cs_h[..., None, :], -jnp.inf
        ))  # (b, c, h, l, j)
        cb = jnp.einsum("bcln,bcjn->bclj", Cc, Bc, preferred_element_type=f32)
        xdt = (xc.astype(f32) * dt[..., None]).astype(dtype)
        y = jnp.einsum(
            "bchlj,bcjhp->bclhp", (cb[:, :, None] * decay).astype(dtype), xdt,
            preferred_element_type=f32,
        )

    with jax.named_scope("ssd/chunk_state"):
        to_end = jnp.exp(cs[:, :, -1:, :] - cs)  # (b, c, l, h)
        x_end = (xc.astype(f32) * (dt * to_end)[..., None]).astype(dtype)
        closing = jnp.einsum(
            "bcln,bclhp->bchpn", Bc, x_end, preferred_element_type=f32
        )

    with jax.named_scope("ssd/recurrence"):
        chunk_decay = jnp.exp(cs[:, :, -1, :])  # (b, c, h)

        def step(state, inputs):
            closed, decayed = inputs
            # Emits the state the chunk starts from.
            return decayed[..., None, None] * state + closed, state

        _, entering = jax.lax.scan(
            step, jnp.zeros((b, h, p, n), f32),
            (closing.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)),
        )
        entering = entering.swapaxes(0, 1)  # (b, c, h, p, n)

    with jax.named_scope("ssd/state_out"):
        y = y + jnp.einsum(
            "bcln,bchpn->bclhp", Cc, entering.astype(dtype),
            preferred_element_type=f32,
        ) * jnp.exp(cs)[..., None]
    return y.reshape(b, s, h, p).astype(dtype)


def causal_conv1d(x, weight, bias=None):
    """Depthwise causal convolution along the sequence.  x: (b, s, ch);
    weight: (width, ch), its last row multiplying the current token;
    bias: (ch,) or None.  float32 inside, ``x.dtype`` out."""
    width, s = weight.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    weight = weight.astype(jnp.float32)
    y = sum(padded[:, k:k + s] * weight[k] for k in range(width))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)
