"""Mixture-of-Experts FFNs: two layers that share nothing but this file.

* :class:`MoEMLP` — capacity-based dispatch for ``LlamaModel``
  (``LlamaConfig.num_experts``).  It has never run on the chip.
* :class:`RoutedExperts` — the dropless layer of the layer-pattern models
  (``models/hybrid.py``): told which experts it holds, it routes over all
  of them and computes its own experts' part.  This is the one that runs
  on the chip (the benchmark's ``lfm2moe.steady``).

``MoEMLP``.  Reference parity: ``atorch/modules/moe/moe_layer.py:161``
(``MOELayer`` with ``_AllToAll:87`` dispatch), ``topk_gating.py``,
``switch_gating.py``, ``grouped_gemm_moe.py``.  TPU redesign (GShard/Switch
formulation): dispatch and combine are dense einsums over a static capacity
dim — no gather/scatter, no torch all-to-all calls; a token over its
expert's capacity is **dropped**.  Expert weights carry the ``expert``
logical axis; when the rule table maps it to the ``ep`` mesh axis, GSPMD
lowers the dispatch/combine einsums to the all-to-alls the reference
hand-codes, and the per-expert matmuls to grouped GEMMs on local experts.
Gating (top-1 "switch" or top-k, softmax) adds two sown losses the train
step folds into the objective:
- ``moe_aux_loss``: load-balancing loss  E * Σ_e f_e · P_e  (Switch eq. 4);
- ``moe_z_loss``: router logit magnitude regularizer.

``RoutedExperts``.  ``s = sigmoid(W_g n)`` in float32 over all
``num_experts``; picks = top-k of ``s + b``, where the per-expert bias ``b``
enters the selection only; weights ``s[picks] / (Σ s[picks] +
route_norm_eps)`` times ``routed_scaling_factor``; ``out = Σ_picks w_e ·
SwiGLU_e(n)`` over the picks whose expert is **held here** (a contiguous block, ``experts_held``
wide, number ``expert_block``; held = all is the uncut layer).  Picks whose
expert lies elsewhere add nothing: on one chip the layer runs without its
``ep`` exchange, and the partial sum is what goes on.  No token is dropped
whatever the imbalance: the (token, pick) pairs are sorted by expert, those
of the held experts in front, and the grouped products
(``ops/grouped_matmul.py``) do the work of the pairs that are there.
**How the buffer of sorted rows is sized.**  The sorted rows the layer
works on are not the worst case's (``top_k x tokens``, every pick here) but
a buffer of ``cap`` slots, ``cap`` = twice the pairs expected here
(:func:`small_buffer`), and one more row tile that no group reaches and
that therefore reads zeros.  One pass over it takes ``cap`` of the pairs
that landed here (``n_held``, the sum of the held experts' sizes), in
sorted order; the layer is a loop of such passes, one for every ``cap``
pairs or part of it, each over the next window of the sort
(:func:`_window`), its length counted on the device: one pass wherever the
load is within ``cap`` (:func:`_experts_in_passes`).  The pairs a pass's
window lacks read a row of zeros there and their own row in the pass that
has them: no pair is dropped, capped or re-weighted whatever the load, and
there is no capacity factor: the layer is as dropless as it was, and no
buffer of the worst case's size is left in it.  Where ``cap`` reaches the
worst case (every expert held, a handful of tokens) there is one pass over
all the slots and no loop: the program is the one it was.
``num_shared_experts`` > 0 adds a shared expert: one more SwiGLU
(``models/llama.py::MLP``, ``num_shared_experts x intermediate_size`` wide)
that every token passes, unweighted, whole on every chip that shares the
layer; it joins the routed sum in float32, before the one cast to the
compute dtype.  No auxiliary loss; the layer sows ``moe_load``, the pairs
routed to each held expert and, last, elsewhere, ``moe_compact``, whether
this call's load fit one pass over the small buffer, and ``moe_picks``.

The bias ``b`` is a leaf of zeros that no gradient reaches and the step has
no rule for (an optimizer's weight decay is all that touches it).
"""

import dataclasses
import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.ops.grouped_matmul import grouped_matmul
from dlrover_tpu.parallel.sharding import constrain

param_with_axes = nn.with_logical_partitioning


def _top_k_mask(router_probs, k: int):
    """0/1 mask of each token's top-k experts."""
    _, top_idx = jax.lax.top_k(router_probs, k)
    return jax.nn.one_hot(
        top_idx, router_probs.shape[-1], dtype=router_probs.dtype
    ).sum(axis=-2)


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense MLP inside a decoder block."""

    hidden_size: int
    intermediate_size: int
    num_experts: int
    num_experts_per_token: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 1e-3
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        e = self.num_experts
        k = self.num_experts_per_token
        # Static per-(batch-row, expert) capacity; tokens over capacity drop
        # through the residual (Switch Transformer semantics).
        capacity = max(1, int(self.capacity_factor * s * k / e))

        # -- router (f32 for numerics) ---------------------------------
        router_w = self.param(
            "router",
            param_with_axes(
                nn.initializers.normal(stddev=0.02), ("embed", "expert")
            ),
            (h, e),
            self.param_dtype,
        )
        logits = jnp.einsum(
            "bsh,he->bse", x.astype(jnp.float32), router_w.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)

        mask = _top_k_mask(probs, k)

        # Load-balancing aux loss: fraction of tokens per expert x mean
        # router prob per expert, scaled by E (Switch eq. 4, over all tokens).
        frac_tokens = jnp.mean(mask, axis=(0, 1))
        mean_probs = jnp.mean(probs, axis=(0, 1))
        aux_loss = e * jnp.sum(frac_tokens * mean_probs)
        z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        # Pipeline bubble ticks feed exactly-zero activations (bias-free
        # blocks keep them zero end to end); a uniform router over zeros
        # would still sow the constant balance loss k and z-loss (ln E)²,
        # biasing the reported loss vs the non-pipelined model.  Gate the
        # sows on input liveness so dead ticks contribute nothing.
        live = (jnp.sum(jnp.abs(logits)) > 0).astype(jnp.float32)
        self.sow(
            "intermediates",
            "moe_aux_loss",
            self.aux_loss_weight * aux_loss * live,
        )
        self.sow(
            "intermediates", "moe_z_loss", self.z_loss_weight * z_loss * live
        )

        # -- capacity assignment ----------------------------------------
        # Position of each token within its expert's buffer = how many
        # earlier tokens in the row chose that expert.
        gated = probs * mask
        if k > 1:
            # Mixtral-style: renormalize over the top-k probs BEFORE the
            # capacity drop, so the combine weight keeps a router gradient
            # (renormalizing after would make a lone survivor's weight a
            # constant 1.0 — zero gradient, the Switch failure mode).
            topk_sum = jnp.sum(gated, axis=-1, keepdims=True)
            gated = gated / jnp.maximum(topk_sum, 1e-9)
        position_in_expert = (
            jnp.cumsum(mask, axis=1) - mask
        )  # (b, s, e), counts along seq
        in_capacity = (position_in_expert < capacity) * mask
        gated = gated * in_capacity

        # combine[b, s, e, c]: weight of token (b, s) at slot c of expert e.
        onehot_pos = jax.nn.one_hot(
            position_in_expert.astype(jnp.int32), capacity, dtype=x.dtype
        )  # (b, s, e, c)
        combine = gated.astype(x.dtype)[..., None] * onehot_pos
        dispatch = (combine > 0).astype(x.dtype)

        # -- dispatch -> expert FFN -> combine --------------------------
        # (b, s, e, c) x (b, s, h) -> (e, b, c, h): the all-to-all under ep.
        expert_in = jnp.einsum("bsec,bsh->ebch", dispatch, x)
        expert_in = constrain(
            expert_in, ("act_expert", "batch", "act_capacity", "act_embed")
        )

        def expert_weights(name, shape, axes):
            return self.param(
                name,
                param_with_axes(nn.initializers.lecun_normal(), axes),
                shape,
                self.param_dtype,
            )

        m = self.intermediate_size
        w_gate = expert_weights("gate_proj", (e, h, m), ("expert", "embed", "mlp"))
        w_up = expert_weights("up_proj", (e, h, m), ("expert", "embed", "mlp"))
        w_down = expert_weights("down_proj", (e, m, h), ("expert", "mlp", "embed"))

        cast = lambda w: w.astype(self.dtype)  # noqa: E731
        gate = jnp.einsum("ebch,ehm->ebcm", expert_in, cast(w_gate))
        up = jnp.einsum("ebch,ehm->ebcm", expert_in, cast(w_up))
        act = nn.silu(gate) * up
        act = constrain(
            act, ("act_expert", "batch", "act_capacity", "act_mlp")
        )
        expert_out = jnp.einsum("ebcm,emh->ebch", act, cast(w_down))
        expert_out = constrain(
            expert_out, ("act_expert", "batch", "act_capacity", "act_embed")
        )

        out = jnp.einsum("bsec,ebch->bsh", combine, expert_out)
        return constrain(out, ("batch", "seq", "act_embed"))


@jax.custom_vjp
def _rows_of_pairs(tokens, order, position):
    """Row ``order[j] % t`` of ``tokens`` (t, h) for every sorted slot
    ``j``: the token of the pair sorted there (pair ``p * t + i`` is token
    ``i``'s pick ``p``).  ``position`` is each pair's slot: ``order``'s
    inverse, or, where ``order`` is a window of the sort, the last slot
    for a pair outside it, whose gradient is zeros.  The gradient is a
    gather too (each pair's slot, summed over a token's picks), where
    autodiff would scatter-add 2048-wide rows."""
    return tokens[order % tokens.shape[0]]


def _rows_of_pairs_fwd(tokens, order, position):
    return _rows_of_pairs(tokens, order, position), (
        position, tokens.shape[0])


def _rows_of_pairs_bwd(residual, grad):
    position, t = residual
    by_pair = grad[position].reshape(-1, t, grad.shape[-1])
    return jnp.sum(by_pair, axis=0, dtype=jnp.float32).astype(grad.dtype), \
        None, None


_rows_of_pairs.defvjp(_rows_of_pairs_fwd, _rows_of_pairs_bwd)


@jax.custom_vjp
def _unsort(rows, order, position):
    """``rows[position]``: every pair reads the slot it was sorted to, and
    a pair outside a window the last one, a row of zeros.  ``order`` is
    ``position``'s inverse over the rows, so the gradient is
    ``grad[order]``."""
    return rows[position]


def _unsort_fwd(rows, order, position):
    return rows[position], order


def _unsort_bwd(order, grad):
    return grad[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def router_scores(tokens, router):
    """sigmoid(tokens . router) in float32 whatever the compute dtype: on
    a TPU a float32 product at the default precision is one bf16 pass, and
    a score rounded to 8 bits moves the picks of near-ties."""
    return jax.nn.sigmoid(jnp.dot(
        tokens.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))


def route(scores, bias, k: int, scaling: float = 1.0, eps: float = 1e-6):
    """scores: (t, e) float32 in (0, 1); bias: (e,).  Picks are the top
    ``k`` of ``scores + bias``; the weights come from the scores alone,
    normalised over the picks (``eps`` in the normaliser).  Returns (picks
    (t, k) int32, weights)."""
    _, picks = jax.lax.top_k(scores + bias, k)
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return picks, weights * scaling


def sort_pairs(picks, first: int, held: int):
    """The (token, pick) pairs in order of expert, those of experts
    ``first .. first + held - 1`` in front, every other pair behind them.
    picks: (t, k); pair ``p * t + i`` is token ``i``'s pick ``p`` (picks
    outermost: a (k, t, h) view of the pairs' rows is then a free reshape,
    where (t, k, h) pads k = 4 to a tile of 8 and copies).  Returns
    ``order`` (slot -> pair), ``position`` (pair -> slot) and ``sizes``
    (held + 1,): pairs of each held expert, then of elsewhere.  Every pair
    has a slot: none is dropped."""
    local = picks.T.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # The inverse permutation by a second sort, and the counts by a
    # comparison: a scatter of 10^5 scalars runs one by one on a TPU.
    position = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(held + 1, dtype=key.dtype), axis=0,
        dtype=jnp.int32)
    return order, position, sizes


# The row tile ``ops/grouped_matmul.py::tilings`` gives a buffer of these
# sizes; a product's rows are a multiple of it.
_ROW_TILE = 512
# The small buffer holds this many times the pairs expected on the held
# experts: the loads on record reach 1.26 of the expectation by layer and
# seed (PERF.md, section 7).  A load past it is never a wrong answer: it
# takes one more pass over the buffer for every ``cap`` pairs or part of it.
_CAP_FACTOR = 2


def small_buffer(pairs: int, held: int, experts: int):
    """(cap, rows) of the small buffer for ``pairs`` (token, pick) pairs:
    ``cap``, the most pairs on the held experts that one pass over it
    takes, is ``_CAP_FACTOR`` times the ``pairs * held / experts``
    expected, rounded up to the row tile; its ``rows`` are one tile more,
    which no group reaches.  (pairs, pairs) where that would not be smaller
    than the worst case: no small buffer is built."""
    cap = -(-_CAP_FACTOR * pairs * held // experts)
    cap = -(-cap // _ROW_TILE) * _ROW_TILE
    if cap + _ROW_TILE < pairs:
        return cap, cap + _ROW_TILE
    return pairs, pairs


def _window(sort, start, cap: int, rows: int):
    """The sort as one pass over the small buffer sees it: the sorted slots
    ``start .. start + cap - 1`` in front of a buffer of ``rows`` slots,
    each held expert's group cut to the part that lies there, and every
    pair sorted elsewhere sent to the last slot, which lies in the tile no
    group reaches and reads zeros."""
    order, position, sizes = sort
    held = sizes.shape[0] - 1
    ends = jnp.cumsum(sizes[:held])
    begins = ends - sizes[:held]
    here = (jnp.clip(ends, start, start + cap)
            - jnp.clip(begins, start, start + cap))
    # Room for the last window, whatever lies in the padding: its slots
    # are past every group.
    windows = -(-order.shape[0] // cap)
    padded = jnp.pad(order, (0, windows * cap + rows - cap - order.shape[0]))
    inside = (position >= start) & (position < start + cap)
    return (jax.lax.dynamic_slice(padded, (start,), (rows,)),
            jnp.where(inside, position - start, rows - 1), here)


def _experts_over(dtype, inputs, sort):
    """The held experts' part of the layer for every token, (t, h) float32:
    ``sum_picks w . SwiGLU_e(token)`` over ``inputs`` (the tokens, the three
    stacked weights, the picks' weights) and the pairs ``sort`` names
    (:func:`sort_pairs`, or a :func:`_window` of it)."""
    tokens, w_gate, w_up, w_down, pick_weights = inputs
    order, position, sizes = sort
    held, _, m = w_gate.shape
    with jax.named_scope("moe/sort"):
        rows = _rows_of_pairs(tokens, order, position)
    with jax.named_scope("moe/gate_up"):
        gate_up = grouped_matmul(
            rows,
            jnp.concatenate([w_gate.astype(dtype), w_up.astype(dtype)], -1),
            sizes[:held])
        act = nn.silu(gate_up[:, :m]) * gate_up[:, m:]
    with jax.named_scope("moe/down"):
        out = grouped_matmul(act, w_down.astype(dtype), sizes[:held])
    with jax.named_scope("moe/combine"):
        # A pair sorted behind the held experts reads a zero row.
        by_pair = _unsort(out, order, position).reshape(-1, *tokens.shape)
        return jnp.sum(
            by_pair.astype(jnp.float32) * pick_weights.T[..., None],
            axis=0,
        )


def _pass_over(cap, rows, dtype, inputs, sort, i):
    """:func:`_experts_over` the ``i``-th window of the sort."""
    return _experts_over(dtype, inputs, _window(sort, i * cap, cap, rows))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _experts_in_passes(cap, rows, dtype, passes, inputs, sort):
    """The held experts' part of the layer in ``passes`` passes over the
    small buffer, a count on the device and 1 wherever the load is within
    ``cap``: a loop over the windows of the sort, each adding its pairs'
    part.  A loop of that length has no autodiff rule, so the layer brings
    its own: a second loop that runs each window's forward again, then its
    pullback, and adds up the gradients.  Under a policy that recomputes
    the layer that takes the place of the recomputation, as long as no
    gradient outside reads the layer's output: ``RoutedExperts`` names its
    output ``ROUTED_OUT`` for the policy to keep."""
    return jax.lax.fori_loop(
        0, passes,
        lambda i, out: out + _pass_over(cap, rows, dtype, inputs, sort, i),
        jnp.zeros(inputs[0].shape, jnp.float32))


def _experts_in_passes_fwd(cap, rows, dtype, passes, inputs, sort):
    return (_experts_in_passes(cap, rows, dtype, passes, inputs, sort),
            (passes, inputs, sort))


def _experts_in_passes_bwd(cap, rows, dtype, residual, grad):
    passes, inputs, sort = residual

    def one_more(i, grads):
        _, pullback = jax.vjp(
            lambda inputs: _pass_over(cap, rows, dtype, inputs, sort, i),
            inputs)
        return jax.tree.map(jnp.add, grads, pullback(grad)[0])

    return None, jax.lax.fori_loop(
        0, passes, one_more, jax.tree.map(jnp.zeros_like, inputs)), None


_experts_in_passes.defvjp(_experts_in_passes_fwd, _experts_in_passes_bwd)

# What a recomputation policy keeps of a routed layer (models/hybrid.py):
# its output, one row a token in the compute dtype.
ROUTED_OUT = "routed_experts_out"


@dataclasses.dataclass(frozen=True)
class _SharedWidths:
    """What ``models/llama.py::MLP`` reads of a configuration."""

    hidden_size: int
    intermediate_size: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype


class RoutedExperts(nn.Module):
    """The dropless routed-expert FFN (module docstring).  x: (b, s, h)."""

    hidden_size: int
    intermediate_size: int
    num_experts: int
    num_experts_per_token: int
    experts_held: Optional[int] = None  # None: all of them
    expert_block: int = 0
    routed_scaling_factor: float = 1.0
    route_norm_eps: float = 1e-6
    num_shared_experts: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        e, k, held = self.num_experts, self.num_experts_per_token, self.held
        first = self.expert_block * held
        if first + held > e:
            raise ValueError(
                f"block {self.expert_block} of {held} experts lies past "
                f"the router's {e}")
        m = self.intermediate_size
        tokens = x.reshape(b * s, h)

        def weights(name, init, shape, axes):
            return self.param(
                name, param_with_axes(init, axes), shape, self.param_dtype)

        with jax.named_scope("moe/router"):
            router = weights(
                "router", nn.initializers.normal(stddev=0.02), (h, e),
                ("embed", "router"))
            # Selection only: no gradient reaches it, and the step has no
            # other rule for it (the aux-loss-free update is a recipe the
            # published config does not give).
            bias = weights(
                "expert_bias", nn.initializers.zeros_init(), (e,),
                ("router",))
            picks, pick_weights = route(
                router_scores(tokens, router), bias.astype(jnp.float32), k,
                self.routed_scaling_factor, self.route_norm_eps)
        with jax.named_scope("moe/sort"):
            order, position, sizes = sort_pairs(picks, first, held)
            self.sow("intermediates", "moe_load", sizes)
            # For a comparison of routing (scripts/logits_check.py); the
            # step fetches the load only, so this costs a step nothing.
            self.sow("intermediates", "moe_picks", picks)
        lecun = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                             batch_axis=(0,))
        w_gate = weights("gate_proj", lecun, (held, h, m),
                         ("expert", "embed", "mlp"))
        w_up = weights("up_proj", lecun, (held, h, m),
                       ("expert", "embed", "mlp"))
        w_down = weights("down_proj", lecun, (held, m, h),
                         ("expert", "mlp", "embed"))

        inputs = (tokens, w_gate, w_up, w_down, pick_weights)
        sort = (order, position, sizes)
        pairs = k * b * s
        cap, rows = small_buffer(pairs, held, e)
        if cap < pairs:
            # Counted on the device: one pass over the small buffer takes
            # ``cap`` of the pairs that landed here, in sorted order.
            passes = -(-jnp.sum(sizes[:held]) // cap)
            compact = passes <= 1
            # The weights in the compute dtype from outside: the loop then
            # adds up their gradients in that dtype, as the products yield
            # them, and autodiff casts once.
            inputs = (tokens, *(w.astype(self.dtype) for w in inputs[1:4]),
                      pick_weights)
            out = _experts_in_passes(
                cap, rows, self.dtype, passes, inputs, sort)
        else:
            compact = jnp.bool_(False)
            out = _experts_over(self.dtype, inputs, sort)
        self.sow("intermediates", "moe_compact", compact.astype(jnp.int32))
        if self.num_shared_experts:
            from dlrover_tpu.models.llama import MLP

            with jax.named_scope("moe/shared"):
                shared = MLP(_SharedWidths(
                    h, m * self.num_shared_experts, self.dtype,
                    self.param_dtype), name="shared")(x)
                out = out + shared.reshape(b * s, h).astype(jnp.float32)
        out = checkpoint_name(out.astype(self.dtype), ROUTED_OUT)
        return constrain(
            out.reshape(b, s, h), ("batch", "seq", "act_embed"))


def collect_moe_metrics(intermediates) -> dict:
    """What the dropless layers sowed, as step metrics: ``moe_load``, the
    ``(held + 1,)`` int32 loads by layer name, and ``moe_compact``, the
    routed layers of this step whose load fit one pass over the small
    buffer (int32; 0 where none is built).  Empty where the model has no
    dropless layer."""
    if not intermediates:
        return {}
    loads, compact = {}, jnp.int32(0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
        if "moe_load" in names:
            loads["/".join(names[:names.index("moe_load")])] = leaf
        elif "moe_compact" in names:
            compact = compact + leaf
    return {"moe_load": loads, "moe_compact": compact} if loads else {}


def collect_moe_losses(intermediates) -> jnp.ndarray:
    """Sum every sown moe_*_loss leaf (zero when the model has no MoE)."""
    total = jnp.float32(0.0)
    if not intermediates:
        return total
    flat = jax.tree_util.tree_flatten_with_path(intermediates)[0]
    for path, leaf in flat:
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if any("moe_aux_loss" in str(n) or "moe_z_loss" in str(n)
               for n in names):
            total = total + jnp.sum(leaf)
    return total
