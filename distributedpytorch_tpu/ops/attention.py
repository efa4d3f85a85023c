"""Attention primitives for the dual-attention segmentation head.

The reference's DANet model (imported from PyTorch-Encoding at reference
train_pascal.py:32,86) pairs a *position* attention module (full self-attention
over the H/8 x W/8 spatial tokens) with a *channel* attention module (gram-matrix
attention over feature channels).  Those live in external CUDA code there; here
they are pure jnp functions the flax modules call, designed for the MXU:

* everything is batched einsum — XLA tiles these straight onto the systolic
  array; no python loops over tokens;
* :func:`blocked_position_attention` is the same math with an online-softmax
  scan over key/value blocks, so the N x N score matrix is never materialized.
  This is the memory-bound form that scales to long token counts and is the
  building block the ring/sequence-parallel path reuses (each ring hop feeds
  one key/value block and carries the same running (max, sum, acc) state).

:func:`causal_attention` is a token model's layer (``models/nemotron_h.py``):
causal, grouped-query, scaled by 1/sqrt(head_dim), heads kept apart; its
values may be narrower than its queries and keys (``models/kanana_lm.py``'s
latent attention: 192-wide q and k, 128-wide v).
:func:`causal_attention` with ``keep`` is the same over a per-query key set
(:func:`head_mean_probs` its probabilities, heads averaged), and
:func:`indexer_scores` with :func:`topk_keep` / :func:`threshold_keep` are
the learned selector that picks the set (``models/keye_lm.py``).

The token layers differ by a **mask rule** — which (query, key) pairs
attend, from positions alone — and this module holds each rule's whole-array
form, the kernels' oracle and the path off the TPU: none
(:func:`position_attention`: every pair), causal (:func:`causal_attention`:
``s <= t``, optionally cut to a key set), block diffusion
(:func:`block_diffusion_mask`, :func:`block_diffusion_attention`: a doubled
sequence ``[clean ‖ noised]``, ``models/sdar_lm.py``).
``ops/pallas_attention.py::MaskRule`` is the same three as tile rules.

Layouts: spatial features are (B, N, C) token-major — N = H*W spatial tokens —
the natural NHWC flattening.  Scores accumulate in float32 regardless of input
dtype (bf16-safe softmax).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def position_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       score_dtype: jnp.dtype | None = None) -> jax.Array:
    """Full position (spatial self-) attention.

    ``q``/``k``: (B, N, Ck), ``v``: (B, N, Cv) -> (B, N, Cv).

    Semantics of the reference DANet position-attention module (consumed via
    the 3-tuple output indexed at reference train_pascal.py:258-260): raw
    dot-product scores over all token pairs, softmax over keys, no scaling
    term — DANet uses unscaled energies with a learned residual gate (the
    gate lives in the calling flax module).

    ``score_dtype`` controls the dtype the N x N score matrix is
    *materialized* in between the einsum and the softmax — the single
    largest HBM tenant of the whole step at big crops (4096 tokens: 64 MB
    in f32, written once and re-read by the softmax's reduce+exp passes).
    ``bfloat16`` halves that traffic.  Numerics stay conservative either
    way: the einsum always *accumulates* in f32 (rounded only on store)
    and the softmax arithmetic (max, exp, sum, div) always runs in f32 —
    XLA fuses the up/downcasts into the neighboring kernels, so the only
    cost is one bf16 rounding of the raw scores and none of the reductions
    lose precision.  The attention-weight matrix itself already
    materializes in ``v.dtype`` (bf16 under mixed precision) regardless.
    ``None`` keeps the f32 materialization.
    """
    scores = jnp.einsum("bnc,bmc->bnm", q, k, preferred_element_type=jnp.float32)
    if score_dtype is not None:
        scores = scores.astype(score_dtype)
    attn = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(v.dtype)
    return jnp.einsum("bnm,bmc->bnc", attn, v)


def blocked_position_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, block_size: int = 1024
) -> jax.Array:
    """Position attention with online softmax over key/value blocks.

    Identical math to :func:`position_attention` but O(N * block) memory: a
    ``lax.scan`` over K/V blocks carries running (row-max, row-sum, weighted
    accumulator) state — the flash-attention recurrence.  Use when N*N scores
    would not fit HBM (large crops / long sequences); also the per-hop kernel
    of the ring-attention path (parallel.ring).
    """
    b, n, ck = q.shape
    cv = v.shape[-1]
    nb = -(-n // block_size)  # ceil
    pad = nb * block_size - n
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, nb, block_size, ck)
    vb = v.reshape(b, nb, block_size, cv)
    # Mask padded keys with -inf scores so they never receive weight.
    key_valid = (jnp.arange(nb * block_size) < n).reshape(nb, block_size)

    def step(carry, blk):
        m, s, acc = carry  # (B,N) running max, (B,N) running sum, (B,N,Cv)
        kblk, vblk, valid = blk
        scores = jnp.einsum(
            "bnc,bmc->bnm", q, kblk, preferred_element_type=jnp.float32
        )
        scores = jnp.where(valid[None, None, :], scores, -jnp.inf)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # Rescale previous accumulator to the new max; exp(-inf - m) == 0
        # handles the first block / fully-masked rows without special cases.
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        s_new = s * correction + p.sum(axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bnm,bmc->bnc", p, vblk.astype(jnp.float32)
        )
        return (m_new, s_new, acc_new), None

    init = (
        jnp.full((b, n), -jnp.inf, jnp.float32),
        jnp.zeros((b, n), jnp.float32),
        jnp.zeros((b, n, cv), jnp.float32),
    )
    (m, s, acc), _ = jax.lax.scan(
        step,
        init,
        (kb.swapaxes(0, 1), vb.swapaxes(0, 1), key_valid),
    )
    return (acc / s[..., None]).astype(v.dtype)


def channel_attention(x: jax.Array) -> jax.Array:
    """Channel (gram-matrix) attention: (B, N, C) -> (B, N, C).

    Semantics of the reference DANet channel-attention module (its map is the
    4th visualization panel at reference train_pascal.py:260,274-275): the
    C x C channel-affinity gram matrix, passed through the max-subtraction
    trick (affinity' = rowmax - affinity) before softmax — attending to the
    *least* similar channels, which is DANet's published CAM formulation —
    then applied back over channels.  No projections; the learned residual
    gate lives in the calling module.
    """
    xf = x.astype(jnp.float32)
    energy = jnp.einsum("bni,bnj->bij", xf, xf)  # (B, C, C)
    energy = energy.max(axis=-1, keepdims=True) - energy
    attn = jax.nn.softmax(energy, axis=-1)
    out = jnp.einsum("bij,bnj->bni", attn, xf)
    return out.astype(x.dtype)


def _causal_scores(q, k, keep=None):
    """float32 (B, G, R, S, S) scaled scores of each query's causal keys, or
    of those that ``keep`` (B, S, S) marks, ``-inf`` elsewhere; ``q`` grouped
    by key/value head."""
    length, hd = q.shape[1], q.shape[-1]
    sc = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                    preferred_element_type=jnp.float32) / math.sqrt(hd)
    pos = jnp.arange(length)
    seen = pos[:, None] >= pos[None, :]
    if keep is not None:
        seen = (seen & keep)[:, None, None]
    return jnp.where(seen, sc, -jnp.inf)


def _attend(sc, v, b, length, dtype):
    """Softmax over the keys of grouped scores (B, G, R, S, S), float32, and
    the probabilities' product with the values: (B, S, Hq, Dv)."""
    w = jax.nn.softmax(sc, axis=-1).astype(dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", w, v,
                     preferred_element_type=jnp.float32)
    return out.astype(dtype).reshape(b, length, -1, v.shape[-1])


def block_diffusion_mask(length: int, block: int) -> jax.Array:
    """bool (2·length, 2·length): which key ``s`` (columns) a query ``t``
    (rows) of a doubled sequence ``[clean ‖ noised]`` attends under a
    block-diffusion objective.  With ``c(p) = p < length`` and ``B(p) = (p
    mod length) // block``: ``c(s) & c(t) & B(s) <= B(t)`` (the clean copy,
    block-causal), or ``c(s) & ~c(t) & B(s) < B(t)`` (a noised block sees
    the clean blocks before its own), or ``~c(s) & ~c(t) & B(s) = B(t)`` (a
    noised block sees itself, both directions): ``length² + length·block``
    pairs."""
    if length % block:
        raise ValueError(f"blocks of {block} do not tile a sequence of "
                         f"{length}")
    pos = jnp.arange(2 * length)
    clean = pos < length
    blk = (pos % length) // block
    ct, cs = clean[:, None], clean[None, :]
    bt, bs = blk[:, None], blk[None, :]
    return (cs & ct & (bs <= bt)) | (cs & ~ct & (bs < bt)) \
        | (~cs & ~ct & (bs == bt))


def block_diffusion_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                              length: int, block: int) -> jax.Array:
    """Grouped-query attention over ``[clean ‖ noised]`` (2·``length``
    positions) under :func:`block_diffusion_mask`, scores scaled by
    1/sqrt(head_dim), softmax in float32; shapes as
    :func:`causal_attention`.  The (B, Hq, 2L, 2L) scores are a whole array
    here; ``ops/pallas_attention.py::flash_block_diffusion_attention`` is the
    same mathematics tile by tile."""
    b, s, qh, hd = q.shape
    kvh = k.shape[2]
    sc = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(b, s, kvh, qh // kvh, hd),
                    k, preferred_element_type=jnp.float32) / math.sqrt(hd)
    sc = jnp.where(block_diffusion_mask(length, block), sc, -jnp.inf)
    return _attend(sc, v, b, s, q.dtype)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     keep: jax.Array | None = None) -> jax.Array:
    """Causal grouped-query attention, scores scaled by 1/sqrt(D), ``D`` the
    width of q and k.

    ``q``: (B, S, Hq, D); ``k``: (B, S, Hkv, D); ``v``: (B, S, Hkv, Dv),
    values as wide as the keys or not (latent attention's are narrower);
    ``Hq`` a multiple of ``Hkv``: query heads ``g·r … g·r + r − 1`` read
    key/value head ``g`` -> (B, S, Hq, Dv) in ``q``'s dtype.  ``keep``
    (bool, (B, S, S)): query ``t`` of every head attends only to the keys
    ``s <= t`` with ``keep[b, t, s]`` (a learned sparse attention's key
    set).  The float32 (B, Hq, S, S) scores
    and their probabilities in ``q``'s dtype are whole arrays here: the form
    that runs off-TPU and in float32; ``ops/pallas_attention.py::
    flash_causal_attention`` / ``flash_sparse_attention`` are the same
    mathematics tile by tile."""
    b, length, qh, hd = q.shape
    kvh = k.shape[2]
    dtype = q.dtype
    sc = _causal_scores(q.reshape(b, length, kvh, qh // kvh, hd), k, keep)
    return _attend(sc, v, b, length, dtype)


def head_mean_probs(q: jax.Array, k: jax.Array,
                    keep: jax.Array | None = None) -> jax.Array:
    """The mean over the query heads of :func:`causal_attention`'s
    probabilities: float32 (B, S, S), zero outside the key set, each row
    summing to one — what a learned selector is aligned with."""
    b, length, qh, hd = q.shape
    kvh = k.shape[2]
    sc = _causal_scores(q.reshape(b, length, kvh, qh // kvh, hd), k, keep)
    return jax.nn.softmax(sc, axis=-1).sum(axis=(1, 2)) / qh


def indexer_scores(qi: jax.Array, ki: jax.Array, w: jax.Array) -> jax.Array:
    """A learned sparse attention's index scores ``I[b, t, s] = (J·Di)^-½ ·
    Σ_j w[b, t, j] · ReLU(qi[b, t, j] · ki[b, s])``, float32 at full
    precision (a pick that differs is another key set, not a rounding).

    ``qi``: (B, S, J, Di); ``ki``: (B, S, Di), one key head; ``w``: (B, S, J)
    -> (B, S, S), every pair, causal or not.  The (B, J, S, S) products are a
    whole array here; ``ops/pallas_attention.py::flash_indexer_scores`` is
    the same sum tile by tile."""
    j, di = qi.shape[2:]
    s = jnp.einsum("bqjd,bkd->bjqk", qi.astype(jnp.float32),
                   ki.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    w = jnp.moveaxis(w.astype(jnp.float32), -1, 1)[..., None]
    return (w * jnp.maximum(s, 0.0)).sum(axis=1) / math.sqrt(j * di)


def keys_wanted(length: int, topk: int) -> jax.Array:
    """``min(t + 1, topk)`` for every query position ``t``: (S,) int32."""
    return jnp.minimum(jnp.arange(length, dtype=jnp.int32) + 1, topk)


def topk_keep(scores: jax.Array, topk: int) -> jax.Array:
    """The key set of every query by ``jax.lax.top_k``: bool (B, S, S),
    ``keep[b, t, s]`` where ``s`` is among the ``min(t + 1, topk)`` causal
    keys of largest ``scores[b, t, ·]``, equal scores to the lower index
    (``top_k``'s rule).  The form that sorts; :func:`threshold_keep` gives
    the same set without a sort."""
    b, length, _ = scores.shape
    pos = jnp.arange(length)
    causal = pos[:, None] >= pos[None, :]
    if topk >= length:
        return jnp.broadcast_to(causal, scores.shape)
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    rows = jnp.arange(b * length)[:, None]
    keep = jnp.zeros((b * length, length), bool).at[
        rows, idx.reshape(b * length, topk)].set(True)
    return keep.reshape(scores.shape) & causal


def _sortable(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose signed order is the floats' total order (-0
    below +0, as ``top_k`` has it)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def threshold_keep(scores: jax.Array, topk: int) -> jax.Array:
    """:func:`topk_keep`'s set, exactly, by a threshold per row and no sort:
    the ``n``-th largest score of the row's causal keys is found bit by bit
    (32 counts of the row; ``n = min(t + 1, topk)``), then, among the keys
    that equal it, the index up to which they are kept (14 more counts at
    8,192 keys), so that exactly ``n`` keys stay and equal scores go to the
    lower index."""
    b, length, _ = scores.shape
    pos = jnp.arange(length, dtype=jnp.int32)
    causal = pos[:, None] >= pos[None, :]
    if topk >= length:
        return jnp.broadcast_to(causal, scores.shape)
    lowest = jnp.iinfo(jnp.int32).min
    # outside the causal row a key sorts below every score
    key = jnp.where(causal, _sortable(scores), lowest)
    want = keys_wanted(length, topk)[None, :, None]

    def count(hit):
        return hit.sum(axis=-1, keepdims=True, dtype=jnp.int32)

    def value_bit(i, t):
        # the candidate sets one more bit of the threshold's offset-binary
        # form, from the top; it stands if enough keys reach it
        bit = jnp.left_shift(jnp.int32(1), 31 - i)
        cand = jnp.where(i == 0, jnp.zeros_like(t), t + bit)
        return jnp.where(count(key >= cand) >= want, cand, t)

    t = jax.lax.fori_loop(0, 32, value_bit,
                          jnp.full((b, length, 1), lowest, jnp.int32))
    above = key > t
    tied = (key == t) & causal
    spare = want - count(above)          # >= 1: ties to keep, lowest first

    def index_bit(i, j):
        cand = j + jnp.left_shift(jnp.int32(1), n_bits - 1 - i)
        return jnp.where(count(tied & (pos < cand)) < spare, cand, j)

    n_bits = max(1, (length - 1).bit_length())
    # the largest j with fewer than ``spare`` ties below it: the last kept
    last = jax.lax.fori_loop(0, n_bits, index_bit,
                             jnp.zeros((b, length, 1), jnp.int32))
    return (above | (tied & (pos <= last))) & causal
