"""Expert parallelism: a GShard/Switch-style Mixture-of-Experts layer.

The reference has no MoE (SURVEY.md §2.5 marks EP "ABSENT"), but expert
parallelism completes this framework's parallelism set (data — parallel.step,
tensor — parallel.tp, pipeline — parallel.pipeline, sequence — parallel.ring
/ parallel.ulysses).

TPU-native design — the GShard dense-dispatch idiom, not dynamic routing:

* routing is *static-shaped*: every token gets a one-hot dispatch tensor
  (tokens × experts × capacity) built from a top-1 (Switch) or top-2 router
  with a fixed per-expert capacity; overflow tokens are dropped (combine
  weight 0) so no shape ever depends on the data — XLA requirement;
* expert FFN parameters are one stacked pytree (E, d, h)/(E, h, d) whose
  leading (expert) dim is sharded over an ``expert`` mesh axis; the dispatch/
  combine einsums are partitioned by GSPMD, which inserts the all-to-alls
  that move token slots to their expert's device and back — no hand-written
  communication;
* the router's load-balancing auxiliary loss (Shazeer et al.) keeps the
  dispatch near-uniform so per-expert capacity (and thus per-device compute)
  stays balanced.

``MoEMlp`` wraps the functional core as a Flax module for use inside model
heads; :func:`ep_param_specs` + :func:`make_moe_apply` give the meshed
expert-parallel execution path.

The second half of the module is the *dropless* form that a token model's
expert layer uses (``models/nemotron_h.py``): the layer is told which experts
it holds, the router stays as wide as all of them, and the held experts run
as one grouped product (``jax.lax.ragged_dot``) over token rows put in expert
order by a counting sort — no capacity, no ``(N, E, C)`` tensor, no token
dropped whatever the imbalance.  :func:`dropless_dispatch` orders the rows of
a *logical* buffer sized for the worst routing, as index arrays only;
:func:`dropless_routed` visits that buffer in chunks of :data:`CHUNK_ROWS`
rows and runs only the chunks that hold a token.  In each it gathers the
rows' tokens, runs the two grouped products with the chunk's own share of
every expert's group, and scatter-adds each row's output, times the row's
own routing weight, into the tokens' result.  Nothing wide is ever made at
the buffer's size: the cost follows the rows that hold a token
(``ceil(live / CHUNK_ROWS)`` chunks), not the worst case.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..telemetry import counters, scopes
from .mesh import make_mesh_1d

#: canonical expert axis name
EXPERT_AXIS = "expert"


def make_expert_mesh(experts: int, devices=None) -> Mesh:
    """A 1-D ``(expert,)`` mesh of ``experts`` devices — one expert each."""
    return make_mesh_1d(experts, EXPERT_AXIS, devices)


def expert_capacity(n_tokens: int, n_experts: int,
                    capacity_factor: float) -> int:
    """Per-expert token slots: ceil(tokens/experts · factor), min 1."""
    return max(1, math.ceil(n_tokens / n_experts * capacity_factor))


def router(x: jax.Array, w_gate: jax.Array, *, k: int,
           capacity: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-``k`` token→expert routing with fixed capacity.

    ``x``: (N, d) tokens; ``w_gate``: (d, E).  Returns
    ``(dispatch, combine, aux_loss)`` with ``dispatch``: (N, E, C) one-hot
    slot assignment, ``combine``: (N, E, C) gate-weighted dispatch, and the
    load-balancing auxiliary loss (scalar, ≥ 1 at perfect balance for k=1).

    Slot assignment is a cumsum over token order per expert (GShard's
    position-in-expert); tokens past ``capacity`` get all-zero rows — dropped,
    exactly like Switch's overflow (the caller's residual path carries them).
    """
    n, _ = x.shape
    n_experts = w_gate.shape[-1]
    if k > n_experts:
        # Beyond E rounds every expert is masked to -inf and argmax would
        # silently re-pick expert 0, double-dispatching tokens.
        raise ValueError(f"top-k routing needs k ({k}) <= experts "
                         f"({n_experts})")
    logits = jnp.einsum("nd,de->ne", x, w_gate,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (N, E)

    dispatch = jnp.zeros((n, n_experts, capacity), jnp.float32)
    combine = jnp.zeros((n, n_experts, capacity), jnp.float32)
    # Slots consumed per expert by earlier-priority rounds, so the k=2 second
    # choice allocates after the first choice's tokens.
    prior_alloc = jnp.zeros((n_experts,), jnp.float32)
    masked_probs = probs
    frac_dispatched = jnp.zeros((n_experts,), jnp.float32)
    for _ in range(k):
        choice = jnp.argmax(masked_probs, axis=-1)  # (N,)
        onehot = jax.nn.one_hot(choice, n_experts)  # (N, E)
        gate = (probs * onehot).sum(-1)  # (N,)
        # Slot index = same-expert tokens ahead of me (+ earlier-round
        # claims); exclusive cumsum keeps it static-shaped.
        ahead = jnp.cumsum(onehot, axis=0) - onehot + prior_alloc[None, :]
        pos = (ahead * onehot).sum(-1).astype(jnp.int32)  # (N,)
        # one_hot of an out-of-capacity position is the zero row — overflow
        # tokens drop out of dispatch/combine with no dynamic shapes.
        slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # (N, C)
        d = onehot[:, :, None] * slot[:, None, :]
        dispatch = dispatch + d
        combine = combine + gate[:, None, None] * d
        frac_dispatched = frac_dispatched + onehot.mean(0)
        prior_alloc = prior_alloc + onehot.sum(0)
        # the next round must pick a different expert per token
        masked_probs = jnp.where(onehot > 0, -jnp.inf, masked_probs)
    # Load-balancing loss: E · Σ_e (token fraction to e) · (mean prob of e).
    aux = n_experts * jnp.sum((frac_dispatched / k) * probs.mean(0))
    return dispatch, combine, aux


def moe_ffn(stacked: dict[str, jax.Array], x: jax.Array, *, k: int = 1,
            capacity_factor: float = 1.25,
            mesh: Mesh | None = None) -> tuple[jax.Array, jax.Array]:
    """The functional MoE FFN: route, dispatch, per-expert MLP, combine.

    ``stacked``: {'w_gate': (d, E), 'w1': (E, d, h), 'b1': (E, h),
    'w2': (E, h, d), 'b2': (E, d)}.  ``x``: (N, d) tokens.  Returns
    ``(y, aux_loss)`` with ``y``: (N, d); dropped tokens produce zero rows
    (callers keep a residual connection, as in Switch).

    With ``mesh``, expert-dim intermediates are sharding-constrained to the
    ``expert`` axis so GSPMD runs each expert's matmuls on its own device and
    inserts the dispatch/return all-to-alls.
    """
    n, d = x.shape
    n_experts = stacked["w1"].shape[0]
    capacity = expert_capacity(n, n_experts, capacity_factor)
    dispatch, combine, aux = router(x, stacked["w_gate"], k=k,
                                    capacity=capacity)
    # (N,E,C)·(N,d) -> (E,C,d): the all-to-all boundary under EP.
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x,
                           preferred_element_type=jnp.float32)
    if mesh is not None:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(EXPERT_AXIS)))
    h = jax.nn.relu(
        jnp.einsum("ecd,edh->ech", expert_in, stacked["w1"],
                   preferred_element_type=jnp.float32)
        + stacked["b1"][:, None, :])
    out = jnp.einsum("ech,ehd->ecd", h, stacked["w2"],
                     preferred_element_type=jnp.float32) \
        + stacked["b2"][:, None, :]
    if mesh is not None:
        out = jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P(EXPERT_AXIS)))
    y = jnp.einsum("nec,ecd->nd", combine, out,
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype), aux


def ep_param_specs(stacked: dict[str, Any]) -> dict[str, P]:
    """PartitionSpec pytree: expert-stacked leaves sharded on their leading
    (expert) dim; the router gate replicated."""
    return {
        k: (P() if k == "w_gate"
            else P(*([EXPERT_AXIS] + [None] * (v.ndim - 1))))
        for k, v in stacked.items()
    }


def make_moe_apply(mesh: Mesh, *, k: int = 1, capacity_factor: float = 1.25):
    """Jitted expert-parallel ``(stacked_params, tokens) -> (y, aux)``:
    expert-stacked params sharded over the ``expert`` axis, tokens
    replicated in/out.  GSPMD owns the all-to-alls."""

    def global_fn(stacked, x):
        return moe_ffn(stacked, x, k=k, capacity_factor=capacity_factor,
                       mesh=mesh)

    def place(stacked):
        specs = ep_param_specs(stacked)
        return {kk: jax.device_put(v, NamedSharding(mesh, specs[kk]))
                for kk, v in stacked.items()}

    return jax.jit(global_fn), place


def init_moe_params(rng: jax.Array, *, d: int, hidden: int,
                    n_experts: int) -> dict[str, jax.Array]:
    """LeCun-normal expert stacks + zero biases + small router."""
    kg, k1, k2 = jax.random.split(rng, 3)
    init = nn.initializers.lecun_normal()
    return {
        "w_gate": init(kg, (d, n_experts), jnp.float32),
        "w1": init(k1, (n_experts, d, hidden), jnp.float32),
        "b1": jnp.zeros((n_experts, hidden), jnp.float32),
        "w2": init(k2, (n_experts, hidden, d), jnp.float32),
        "b2": jnp.zeros((n_experts, d), jnp.float32),
    }


class MoEMlp(nn.Module):
    """Flax wrapper: tokens (B, N, d) -> (B, N, d) with a residual carrying
    dropped tokens; stores the aux loss in the ``losses`` collection so a
    training loss can add ``aux_weight * moe_aux``."""

    n_experts: int
    hidden: int
    k: int = 1
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x):
        b, n, d = x.shape
        stacked = {
            "w_gate": self.param("w_gate", nn.initializers.lecun_normal(),
                                 (d, self.n_experts)),
            "w1": self.param("w1", nn.initializers.lecun_normal(),
                             (self.n_experts, d, self.hidden)),
            "b1": self.param("b1", nn.initializers.zeros,
                             (self.n_experts, self.hidden)),
            "w2": self.param("w2", nn.initializers.lecun_normal(),
                             (self.n_experts, self.hidden, d)),
            "b2": self.param("b2", nn.initializers.zeros,
                             (self.n_experts, d)),
        }
        y, aux = moe_ffn(stacked, x.reshape(b * n, d), k=self.k,
                         capacity_factor=self.capacity_factor)
        self.sow("losses", "moe_aux", aux)
        return x + y.reshape(b, n, d)


# ------------------------------------------------------- dropless dispatch
#: what an expert layer counts (``telemetry/counters.py``): assignments that
#: found no row of the buffer, summed over layers and steps; the fullest held
#: expert's rows over the mean, the worst of them; the chunks of the row
#: buffer that ran over the chunks in all, the worst layer's
COUNTER_DROPPED = counters.declare("moe_tokens_dropped", "sum")
COUNTER_LOAD = counters.declare("moe_expert_load_max_over_mean", "max")
COUNTER_CHUNKS = counters.declare("moe_chunks_run_share", "max")

#: rows of the buffer that :func:`dropless_routed` visits at a time: one
#: constant, set from chip runs (PERF.md section 6, PR 32).  A multiple of
#: the grouped product's row tile.
CHUNK_ROWS = 4096


class Dispatch(NamedTuple):
    """Where each (token, held expert) assignment sits in the expert-ordered
    row buffer, which exists as these index arrays only.  ``rows``: (M,)
    token of each buffer row (``N``, out of range, for a row that holds
    none); ``pos``: (N, held) row of each assignment; ``kept``: (N, held)
    whether the assignment exists and has a row; ``group_sizes``: (held,)
    rows of each expert, one group after the other from row 0, so the rows
    from ``group_sizes.sum()`` on hold no token; ``dropped``: the
    assignments that found no row (0 while the buffer is the worst case's,
    :func:`dropless_buffer_rows`)."""

    rows: jax.Array
    pos: jax.Array
    kept: jax.Array
    group_sizes: jax.Array
    dropped: jax.Array


#: ``checkpoint_name``s of what :func:`sigmoid_route`'s reverse pass reads
#: of its forward pass past a product or a selection: the logits (before the
#: sigmoid, whose rule reads its own result), the choice and the chosen
#: scores (the gather that normalises the weights)
ROUTER_KEPT = (_LOGITS, _IDX, _CHOSEN) = (
    "moe_router_logits", "moe_topk_idx", "moe_chosen_scores")


def sigmoid_route(x: jax.Array, router: jax.Array, bias: jax.Array, *,
                  k: int, held: int, offset: int, norm_topk: bool,
                  scale: float, precision=None) -> tuple:
    """A token model's sigmoid router over EVERY published expert, under the
    ``router`` scope: ``s = sigmoid(x @ router)`` in float32, the ``k``
    experts of largest ``s + bias`` (the score-correction bias moves the
    choice and not the weights; no gradient reaches it through the
    selection), and the weights of the ``held`` experts from ``offset`` on,
    ``s_e / (sum of the chosen s)`` where ``norm_topk``, times ``scale``.
    ``x``: (N, d) -> ``(weights (N, held) float32, idx (N, k))``; a held
    expert that a token did not choose has a weight here and no row in the
    dispatch.  ``precision``: the product's (``None``: the default's).  What
    the reverse pass reads is named :data:`ROUTER_KEPT`.  The expert layers
    of ``models/nemotron_h.py`` and ``models/kanana_lm.py`` call it."""
    with jax.named_scope(scopes.MOE_ROUTER):
        scores = jax.nn.sigmoid(checkpoint_name(
            jnp.dot(x.astype(jnp.float32), router, precision=precision),
            _LOGITS))
        idx = checkpoint_name(jax.lax.top_k(scores + bias, k)[1], _IDX)
        weights = scores[:, offset:offset + held]
        if norm_topk:
            chosen = checkpoint_name(
                jnp.take_along_axis(scores, idx, axis=-1), _CHOSEN)
            weights = weights / (chosen.sum(-1)[:, None] + 1e-20)
        return weights * scale, idx


def dropless_buffer_rows(n_tokens: int, k: int, n_held: int) -> int:
    """Rows that hold every assignment whatever the routing: a token picks
    an expert at most once, so at most ``min(k, held)`` of its choices are
    held here."""
    return n_tokens * min(k, n_held)


def dropless_dispatch(idx: jax.Array, *, expert_offset: int,
                      n_held: int) -> Dispatch:
    """Order the assignments of ``idx`` (N, k: each token's chosen experts,
    numbered over ALL experts) that fall on the ``n_held`` experts from
    ``expert_offset`` on, by expert: a counting sort over an (N, held) table,
    which for a chip's share of the experts is far smaller than the (N, k)
    list a general sort would order."""
    n, k = idx.shape
    m = dropless_buffer_rows(n, k, n_held)
    held = expert_offset + jnp.arange(n_held, dtype=idx.dtype)
    sel = (idx[:, :, None] == held[None, None, :]).any(axis=1)   # (N, held)
    counts = sel.sum(axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.cumsum(sel, axis=0, dtype=jnp.int32) - sel
    pos = starts[None, :] + rank
    kept = sel & (pos < m)
    group_sizes = jnp.clip(m - starts, 0, counts)
    token = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                             pos.shape)
    rows = jnp.full((m,), n, jnp.int32).at[
        jnp.where(kept, pos, m)].set(token, mode="drop")
    dropped = counts.sum() - group_sizes.sum()
    return Dispatch(rows, pos, kept, group_sizes, dropped)


def chunk_rows_of(buffer_rows: int, chunk_rows: int | None = None) -> int:
    """Rows of one chunk of a ``buffer_rows``-row buffer: ``chunk_rows``
    (:data:`CHUNK_ROWS`), or the whole of a smaller buffer."""
    return max(1, min(chunk_rows or CHUNK_ROWS, buffer_rows))


class _Chunk(NamedTuple):
    """One chunk's view of the row buffer: ``r`` rows from ``c * r``."""

    rows: jax.Array      # (r,) token of each row; N where the row has none
    sizes: jax.Array     # (held,) the chunk's rows of each expert
    expert: jax.Array    # (r, held) whether the row belongs to the expert
    live: jax.Array      # (r,) whether the row holds a token


def _chunk_view(rows: jax.Array, group_sizes: jax.Array, c, r: int) -> _Chunk:
    with jax.named_scope(scopes.MOE_DISPATCH):
        start = c * r
        ends = jnp.cumsum(group_sizes)
        starts = ends - group_sizes
        sizes = jnp.clip(ends - start, 0, r) - jnp.clip(starts - start, 0, r)
        row = (start + jnp.arange(r, dtype=jnp.int32))[:, None]
        expert = (row >= starts[None, :]) & (row < ends[None, :])
        return _Chunk(jax.lax.dynamic_slice(rows, (start,), (r,)), sizes,
                      expert, expert.any(-1))


def _chunk_inputs(low, weights, ch: _Chunk):
    """The chunk's token rows, and each row's own routing weight."""
    with jax.named_scope(scopes.MOE_DISPATCH):
        xs = jnp.take(low, ch.rows, axis=0, mode="fill", fill_value=0)
    with jax.named_scope(scopes.MOE_COMBINE):
        w_rows = jnp.take(weights, ch.rows, axis=0, mode="fill",
                          fill_value=0)
        w_row = jnp.where(ch.expert, w_rows, 0).sum(-1)
    return xs, w_row.astype(jnp.float32)


def _chunk_outputs(xs, w_row, w1, w2, ch: _Chunk, act) -> jax.Array:
    """(r, d) float32: ``w_row * (act(xs @ w1[e]) @ w2[e])`` on the rows of
    expert ``e``, zeros on a row that holds no token (the grouped product
    leaves rows past its last group unwritten on some backends)."""
    with jax.named_scope(scopes.MOE_ROUTED_EXPERTS):
        hidden = act(jax.lax.ragged_dot(xs, w1, ch.sizes))
        ys = jax.lax.ragged_dot(hidden.astype(xs.dtype), w2, ch.sizes)
        ys = jnp.where(ch.live[:, None], ys, 0)
    with jax.named_scope(scopes.MOE_COMBINE):
        return ys.astype(jnp.float32) * w_row[:, None]


def _zeros_after(ready, *like) -> tuple:
    """Float32 zeros shaped like each of ``like``, for a loop to accumulate
    into, that exist only once ``ready`` does.  A loop
    whose accumulators start from a constant is free to be scheduled away
    from the work around it, and XLA:TPU then keeps every layer's weight
    gradient until the end of the step before it applies any update: 1.5 GB
    more at the step's peak (PERF.md section 6, PR 32)."""
    zeros = tuple(jnp.zeros(a.shape, jnp.float32) for a in like)
    return jax.lax.optimization_barrier((ready, zeros))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _routed(low, weights, w1, w2, rows, group_sizes, n_run, act, r):
    return _routed_fwd(low, weights, w1, w2, rows, group_sizes, n_run, act,
                       r)[0]


def _routed_fwd(low, weights, w1, w2, rows, group_sizes, n_run, act, r):
    w1c, w2c = w1.astype(low.dtype), w2.astype(low.dtype)

    def chunk(c, out):
        ch = _chunk_view(rows, group_sizes, c, r)
        xs, w_row = _chunk_inputs(low, weights, ch)
        ys = _chunk_outputs(xs, w_row, w1c, w2c, ch, act)
        with jax.named_scope(scopes.MOE_COMBINE):
            return out.at[ch.rows].add(ys, mode="drop")

    out = jax.lax.fori_loop(0, n_run, chunk, _zeros_after(low, low)[0])
    return out, (low, weights, w1, w2, rows, group_sizes, n_run)


def _routed_bwd(act, r, res, g):
    """The reverse pass over the same chunks: each recomputes its rows and
    its products (only the layer's inputs were kept), and adds its share of
    the four gradients in float32."""
    low, weights, w1, w2, rows, group_sizes, n_run = res
    w1c, w2c = w1.astype(low.dtype), w2.astype(low.dtype)

    def chunk(c, acc):
        d_low, d_weights, d_w1, d_w2 = acc
        ch = _chunk_view(rows, group_sizes, c, r)
        xs, w_row = _chunk_inputs(low, weights, ch)
        with jax.named_scope(scopes.MOE_COMBINE):
            g_rows = jnp.take(g, ch.rows, axis=0, mode="fill", fill_value=0)
        _, vjp = jax.vjp(
            lambda xs, w_row, w1c, w2c: _chunk_outputs(
                xs, w_row, w1c, w2c, ch, act), xs, w_row, w1c, w2c)
        d_xs, d_w_row, c_w1, c_w2 = vjp(g_rows)
        with jax.named_scope(scopes.MOE_DISPATCH):
            d_low = d_low.at[ch.rows].add(d_xs.astype(jnp.float32),
                                          mode="drop")
        with jax.named_scope(scopes.MOE_COMBINE):
            d_weights = d_weights.at[ch.rows].add(
                jnp.where(ch.expert, d_w_row[:, None], 0.0), mode="drop")
        with jax.named_scope(scopes.MOE_ROUTED_EXPERTS):
            d_w1 = d_w1 + c_w1.astype(jnp.float32)
            d_w2 = d_w2 + c_w2.astype(jnp.float32)
        return d_low, d_weights, d_w1, d_w2

    acc = jax.lax.fori_loop(0, n_run, chunk,
                            _zeros_after(g, low, weights, w1, w2))
    return tuple(a.astype(p.dtype) for a, p in
                 zip(acc, (low, weights, w1, w2))) + (None, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


def dropless_routed(low: jax.Array, weights: jax.Array, w1: jax.Array,
                    w2: jax.Array, d: Dispatch, act,
                    chunk_rows: int | None = None):
    """The held experts over the rows of ``d``: ``(N, d)`` float32, each
    token's sum over the held experts ``e`` it chose of ``weights[t, e] *
    (act(low[t] @ w1[e]) @ w2[e])``, and the share of the buffer's chunks
    that ran.  ``low``: (N, d) tokens in the compute dtype; ``weights``:
    (N, held); ``w1``: (held, d, h), ``w2``: (held, h, d), cast to ``low``'s
    dtype for the products, which accumulate in float32.

    The buffer is visited in chunks of ``chunk_rows`` rows
    (:data:`CHUNK_ROWS`; a whole small buffer is one chunk) by a loop whose
    trip count is the chunks that hold a token, decided on the device from
    ``d.group_sizes``: no shape depends on the data and no row is left out,
    a routing that fills the buffer runs every chunk.  Each chunk's three
    parts run under the ``dispatch`` / ``routed_experts`` / ``combine``
    scopes, forward and reverse (a custom VJP: the loop's trip count is
    data, which JAX cannot differentiate through)."""
    n, m = low.shape[0], d.rows.shape[0]
    r = chunk_rows_of(m, chunk_rows)
    n_chunks = -(-m // r)
    rows = jnp.pad(d.rows, (0, n_chunks * r - m), constant_values=n)
    n_run = (d.group_sizes.sum() + r - 1) // r
    out = _routed(low, weights, w1, w2, rows, d.group_sizes, n_run, act, r)
    return out, n_run.astype(jnp.float32) / n_chunks


def expert_load_max_over_mean(d: Dispatch) -> jax.Array:
    """The fullest held expert's rows over the mean (1 = balanced)."""
    sizes = d.group_sizes.astype(jnp.float32)
    return sizes.max() / jnp.maximum(sizes.mean(), 1.0)
