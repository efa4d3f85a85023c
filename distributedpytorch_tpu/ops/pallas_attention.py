"""Pallas TPU kernels for BOTH DANet attention branches, and for a token
model's causal grouped-query attention — the hot path.

The reference's dual-attention head materializes its intermediates in
external CUDA code (PyTorch-Encoding's DANet head, reference
train_pascal.py:32,86): the (H·W/64)² position-attention score matrix and
the C×C channel gram matrix.  :mod:`ops.attention` is the XLA einsum
re-expression; this module is the hand-scheduled TPU form — the default
hot path on TPU (``model.attention_impl=auto``), with the XLA forms as
the off-TPU fallback:

* :func:`flash_position_attention` — one kernel computes Q·Kᵀ on the
  MXU, the online softmax on the VPU, and the P·V matmul on the MXU per
  (Q-block, K-block) tile, keeping everything in VMEM and never writing
  an N×N intermediate to HBM.  Grid ``(batch, q_blocks, k_blocks)``,
  K innermost; the running (max, sum, accumulator) state lives in VMEM
  scratch across the K sweep (the canonical flash-attention schedule).
  Blocks default 256×256, aligned to the (8,128) f32 tile.
* :func:`flash_causal_attention` — the same kernels, forward and reverse,
  as a token model's ``*`` layer asks for them (``models/nemotron_h.py``):
  ``scale`` 1/√head_dim; a **mask rule** (:class:`MaskRule`: which tiles
  run, and the mask inside a tile, from positions alone) — under
  :data:`CAUSAL` a query sees the keys at or before
  it, a tile wholly above the diagonal is neither computed (``pl.when``)
  nor fetched (its block index stays on the last tile that ran), and only
  a tile that crosses the diagonal pays for the mask; ``group`` — heads lie
  on the grid's first axis beside the batch, and the query heads of a group
  read their one key/value head through the index map (nothing is repeated
  in HBM; the reverse pass writes one float32 dK, dV per query head, summed
  over the group in XLA).  What differs from DANet's calls is static at
  trace time; with :data:`NO_MASK` and ``group=1`` the kernels' Mosaic
  modules are DANet's, op for op.
* :func:`flash_block_diffusion_attention` — the same kernels under the rule
  :class:`BlockDiffusion` (``models/sdar_lm.py``): a doubled sequence
  ``[clean ‖ noised]``, block-causal in the clean copy, a noised block
  seeing the clean blocks before it and itself in both directions.
* :func:`flash_channel_attention` — the gram branch: one kernel streams
  the (N, C) tokens through VMEM in row blocks, accumulates the C×C
  gram on the MXU in VMEM scratch and finishes with DANet's
  max-subtraction softmax on the VPU *in the same kernel* (the energy
  matrix never round-trips HBM between the einsum and the softmax);
  a second streamed kernel applies the attention back over channels.
  Only the C×C attention map (≤1 MB at C=512) crosses HBM between the
  two.

Backward, both ``jax.custom_vjp``:

* position attention: the flash backward as Mosaic kernels.  Under
  differentiation the forward also emits the per-row log-sum-exp; the
  reverse pass rebuilds each probability tile from it (``P = exp(S −
  lse)``, no second online softmax) and accumulates dV, dK and dQ on the
  MXU without writing an N×N intermediate to HBM — see
  :func:`_flash_backward_local` for the two schedules and how the shapes
  choose between them.
* channel attention: the reverse pass recomputes with the jnp reference
  form and differentiates that (the gram is one (C, C) matmul).

Mosaic compiles the kernels unless a caller passes ``interpret=True`` —
only the CPU test suite does (pallas's interpreter executes the same
program), including forward AND backward parity against the XLA forms.
Off-TPU the ``auto`` selector in :mod:`models.danet` picks the einsum
forms; the kernels themselves never fall back.

Mosaic refuses automatic partitioning, so under a multi-device ``jit``
each forward runs inside a ``shard_map`` on its device's batch shard —
see :func:`_on_local_batch`.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import scopes
from .attention import channel_attention

_NEG_INF = -1e30
# dot_general dimension numbers of the kernels' 2-D matmuls
_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_NN = (((1,), (0,)), ((), ()))   # a · b
_TN = (((0,), (0,)), ((), ()))   # aᵀ · b


def _on_local_batch(kernel, *operands):
    """Run ``kernel`` (batch-leading operands -> batch-leading result) on
    each device's batch shard.

    GSPMD cannot partition a Mosaic call, so when the program is traced
    under a context mesh (``parallel.step`` enters the step's mesh) the
    call is wrapped in a ``shard_map`` over every mesh axis: batch rows
    split over ``data``, token and channel dims whole on each device.
    With no context mesh (one device) or inside per-device code already
    (the bucketed step's shard_map region) the kernel is called as is."""
    from ..parallel.mesh import batch_spec

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes:
        return kernel(*operands)
    return jax.shard_map(kernel, in_specs=batch_spec(),
                         out_specs=batch_spec(), check_vma=False)(*operands)


# ------------------------------------------------------------- mask rules
def _value(x):
    """A grid index given as it is, or as the call that reads it (a kernel
    that never needs a ``program_id`` does not emit one)."""
    return x() if callable(x) else x


@dataclasses.dataclass(frozen=True)
class MaskRule:
    """Which (query, key) pairs a flash call attends, from positions alone:
    which tiles of the grid run, and the mask inside a tile that runs.
    Static and hashable (a ``custom_vjp``'s non-differentiated argument, a
    kernel's ``functools.partial``).  This one is no mask: every tile runs
    and only the keys past the true token count (padding) are masked —
    DANet's position attention.

    ``forward``, ``reverse_scope``, ``reverse_calls`` name the Mosaic calls
    (forward; the reverse pass's scope and its fused / dK-dV / dQ calls);
    ``kept``: the ``checkpoint_name``s of the forward call's output and
    log-sum-exp, for a block that keeps them across its recomputation;
    ``kept_operands``: those of the call's q, k and v, heads-first as the
    reverse pass reads them (none: the operands are not named).

    Tiles are addressed by block index (``i`` queries, ``j`` keys) and size;
    ``reverse``: a reverse-pass tile, square, keys on sublanes and queries on
    lanes.  With :data:`NO_MASK` and :data:`CAUSAL` the kernels trace to what
    they were when ``causal`` was a flag, equation for equation."""

    forward: str = scopes.PAM_KERNEL
    reverse_scope: str = scopes.PAM_BWD
    reverse_calls: tuple = (scopes.PAM_BWD_FUSED, scopes.PAM_BWD_DKV,
                            scopes.PAM_BWD_DQ)
    kept: tuple = ()
    kept_operands: tuple = ()
    #: the first key tile counts for every query tile, so the fused reverse
    #: call writes each row of its resident dQ before it adds to it
    first_key_tile_meets_all = True

    def tiles(self, i, j, block_q: int, block_k: int, *, n_real: int,
              reverse: bool = False, merged: bool = False):
        """Does tile ``(i, j)`` run: yields ``(condition, mask)`` for each
        way it may — ``condition`` a traced bool (``None``: always), ``mask``
        the boolean mask of the tile from its row and column offsets, called
        inside the tile as ``mask(shape)`` -> bool array, True where the
        pair attends (``shape``: (queries, keys), or (keys, queries) of a
        ``reverse`` tile), or ``None`` (every pair attends).  ``merged``: the
        caller masks every tile by a key set that holds this rule already,
        and wants one condition."""
        if reverse and not n_real % block_k:
            yield None, None
        elif reverse:
            yield None, lambda shape: j * block_k \
                + jax.lax.broadcasted_iota(jnp.int32, shape, 0) < n_real
        else:
            yield None, lambda shape: j * block_k \
                + jax.lax.broadcasted_iota(jnp.int32, shape, 1) < n_real

    def hold_key(self, i, j, block_q: int, block_k: int,
                 reverse: bool = False):
        """The key tile a sweep over the keys holds at step ``j`` of query
        tile ``i``: ``j`` where that tile runs, else a tile that does (one
        that is stepped over starts no copy)."""
        return j

    def hold_query(self, j, i, block_q: int, block_k: int):
        """The query tile the reverse pass's sweep over the queries holds at
        step ``i`` of key tile ``j``."""
        return i


NO_MASK = MaskRule()


@dataclasses.dataclass(frozen=True)
class Causal(MaskRule):
    """A query sees the keys at or before its own position.  A tile wholly
    above the diagonal is not computed, and only a tile that crosses it pays
    for the mask; padded keys lie past every real query, so the mask is
    theirs too."""

    forward: str = scopes.CAUSAL_ATTN
    reverse_scope: str = scopes.CAUSAL_ATTN_BWD
    reverse_calls: tuple = (scopes.CAUSAL_ATTN_BWD_FUSED,
                            scopes.CAUSAL_ATTN_BWD_DKV,
                            scopes.CAUSAL_ATTN_BWD_DQ)
    kept: tuple = ("causal_attn_out", "causal_attn_lse")

    def tiles(self, i, j, block_q, block_k, *, n_real, reverse=False,
              merged=False):
        if reverse:  # square tiles: below the diagonal whole, on it masked
            i = _value(i)
            if merged:
                yield i >= j, None
                return
            yield i > j, None
            yield i == j, lambda shape: \
                jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
                <= jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            return
        q_lo = _value(i) * block_q
        k_lo = j * block_k
        crosses = k_lo + block_k - 1 > q_lo
        if merged:
            yield k_lo < q_lo + block_q, None
            return

        def mask(shape, crossing=True):
            key_idx = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, shape, 1)
            if not crossing:
                return None
            query_idx = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            return key_idx <= query_idx

        yield jnp.logical_and(k_lo < q_lo + block_q, crosses), mask
        yield jnp.logical_not(crosses), functools.partial(mask,
                                                          crossing=False)

    def hold_key(self, i, j, block_q, block_k, reverse=False):
        if reverse:
            return jnp.minimum(j, i)
        return jnp.minimum(j, (i * block_q + block_q - 1) // block_k)

    def hold_query(self, j, i, block_q, block_k):
        return jnp.maximum(i, j)


CAUSAL = Causal()


def _div(x, d: int):
    """``x // d`` of a non-negative int32 (a shift where ``d`` is a power of
    two: Mosaic's vector division is slow)."""
    if d & (d - 1) == 0:
        return jax.lax.shift_right_logical(
            jnp.asarray(x, jnp.int32), jnp.int32(d.bit_length() - 1))
    return jax.lax.div(jnp.asarray(x, jnp.int32), jnp.int32(d))


@dataclasses.dataclass(frozen=True, kw_only=True)
class BlockDiffusion(MaskRule):
    """The mask of a block-diffusion objective over a doubled sequence
    ``[clean ‖ noised]`` of ``2·length`` positions in blocks of ``block``:
    with ``c(p) = p < length`` and ``B(p) = (p mod length) // block``, query
    ``t`` attends key ``s`` iff ``c(s) & c(t) & B(s) <= B(t)`` (the clean
    copy is block-causal), or ``c(s) & ~c(t) & B(s) < B(t)`` (a noised block
    sees the clean blocks before its own), or ``~c(s) & ~c(t) & B(s) = B(t)``
    (a noised block sees itself, both directions).  Neither causal nor a
    subset of it: ``length² + length·block`` pairs a head of ``4·length²``.

    With tiles of ``T`` that divide ``length`` and hold whole blocks, a tile
    runs iff its keys are clean and ``j mod n <= i mod n`` (``n = length /
    T``), or both sides are noised and ``i = j``: ``n² + 2n`` of ``4n²``;
    of those, the ``n(n − 1)`` strictly below a diagonal run with no mask.
    Any other tile size runs every tile that holds an attended pair."""

    length: int = 0
    block: int = 1
    forward: str = scopes.BLOCKDIFF_ATTN
    reverse_scope: str = scopes.BLOCKDIFF_ATTN_BWD
    reverse_calls: tuple = (scopes.BLOCKDIFF_ATTN_BWD_FUSED,
                            scopes.BLOCKDIFF_ATTN_BWD_DKV,
                            scopes.BLOCKDIFF_ATTN_BWD_DQ)
    kept: tuple = ("blockdiff_attn_out", "blockdiff_attn_lse")
    #: a block that keeps the call's operands replays neither the
    #: transposes nor what makes q, k and v
    kept_operands: tuple = ("blockdiff_attn_q", "blockdiff_attn_k",
                            "blockdiff_attn_v")
    #: a query tile of the first noised block alone sees no clean key
    first_key_tile_meets_all = False

    def __post_init__(self):
        if self.length < 1 or self.block < 1 or self.length % self.block:
            raise ValueError(f"blocks of {self.block} do not tile a "
                             f"sequence of {self.length}")

    # block of a clean / of a noised position (non-negative arguments)
    def _block(self, p):
        return _div(p, self.block)

    def _key_tiles(self, i, block_q, block_k):
        """``(hi1, lo2, hi2)``: the clean key tiles ``0 … hi1`` and the
        noised key tiles ``lo2 … hi2`` that hold a key which some query of
        query tile ``i`` attends (an empty range has ``hi < lo``)."""
        n, b = self.length, self.block
        q_lo = i * block_q
        q_hi = q_lo + block_q - 1
        # the last clean key a clean query of the tile sees ends its own
        # block; a noised query's, the block before its own
        by_clean = jnp.where(
            q_lo < n, (self._block(jnp.minimum(q_hi, n - 1)) + 1) * b - 1, -1)
        last = jnp.maximum(jnp.minimum(q_hi, 2 * n - 1) - n, 0)
        by_noised = jnp.where(q_hi >= n, self._block(last) * b - 1, -1)
        key = jnp.maximum(by_clean, by_noised)
        hi1 = jnp.where(key >= 0, _div(jnp.maximum(key, 0), block_k), -1)
        first = jnp.maximum(q_lo, n) - n
        lo2 = _div(n + self._block(first) * b, block_k)
        hi2 = jnp.where(
            q_hi >= n, _div(n + self._block(last) * b + b - 1, block_k), -1)
        return hi1, lo2, hi2

    def _runs(self, i, j, block_q, block_k):
        hi1, lo2, hi2 = self._key_tiles(i, block_q, block_k)
        return jnp.logical_or(j <= hi1,
                              jnp.logical_and(j >= lo2, j <= hi2))

    def _whole(self, i, j, block_q, block_k):
        """Every pair of the tile attends: clean keys, all in blocks before
        (a clean query tile: at or before) the first query's."""
        n = self.length
        q_lo, k_hi = i * block_q, j * block_k + block_k - 1
        last_key = self._block(jnp.minimum(k_hi, n - 1))
        clean = jnp.logical_and(q_lo + block_q <= n,
                                last_key <= self._block(q_lo))
        noised = jnp.logical_and(
            q_lo >= n, last_key < self._block(jnp.maximum(q_lo - n, 0)))
        return jnp.logical_and(k_hi < n, jnp.logical_or(clean, noised))

    def tiles(self, i, j, block_q, block_k, *, n_real, reverse=False,
              merged=False):
        i = _value(i)
        runs = self._runs(i, j, block_q, block_k)
        whole = self._whole(i, j, block_q, block_k)
        yield whole, None
        yield jnp.logical_and(runs, jnp.logical_not(whole)), \
            functools.partial(self.mask, q_lo=i * block_q, k_lo=j * block_k,
                              reverse=reverse)

    def mask(self, shape, q_lo, k_lo, reverse=False):
        """The tile's mask from its offsets (a padded key lies in no block
        of a real query's).  One comparison and one equality a pair: a key's
        code is its block (a noised key's, past every clean block), a
        query's two codes are the last clean block it sees and the noised
        block it is in."""
        n = self.length
        blocks = n // self.block
        rows, cols = shape
        q_shape, q_dim = ((1, cols), 1) if reverse else ((rows, 1), 0)
        k_shape, k_dim = ((rows, 1), 0) if reverse else ((1, cols), 1)
        t = q_lo + jax.lax.broadcasted_iota(jnp.int32, q_shape, q_dim)
        s = k_lo + jax.lax.broadcasted_iota(jnp.int32, k_shape, k_dim)
        t_block = self._block(jnp.where(t < n, t, t - n))
        s_block = self._block(jnp.where(s < n, s, s - n))
        sees = jnp.where(t < n, t_block, t_block - 1)
        own = jnp.where(t < n, -1, t_block + blocks)
        code = jnp.where(s < n, s_block, s_block + blocks)
        return jnp.logical_or(code <= sees, code == own)

    def hold_key(self, i, j, block_q, block_k, reverse=False):
        hi1, lo2, hi2 = self._key_tiles(i, block_q, block_k)
        return jnp.where(
            jnp.logical_and(j > hi1, lo2 <= hi2), jnp.clip(j, lo2, hi2),
            jnp.clip(j, 0, jnp.maximum(hi1, 0)))

    def hold_query(self, j, i, block_q, block_k):
        n, b = self.length, self.block
        k_lo = j * block_k
        k_hi = k_lo + block_k - 1
        first = self._block(jnp.minimum(k_lo, n - 1))  # of the clean keys
        # clean queries from the first clean key's block on
        lo1 = jnp.where(k_lo < n, _div(first * b, block_q), 0)
        hi1 = jnp.where(k_lo < n, (n - 1) // block_q, -1)
        # noised queries: after that block (clean keys), in the keys' own
        # blocks (noised keys)
        after = n + (first + 1) * b
        lo_a = jnp.where(jnp.logical_and(k_lo < n, after < 2 * n),
                         _div(jnp.minimum(after, 2 * n - 1), block_q),
                         2 ** 30)
        lo_b = jnp.where(k_hi >= n, _div(jnp.maximum(k_lo, n), block_q),
                         2 ** 30)
        hi_b = jnp.where(k_hi >= n,
                         _div(jnp.minimum(k_hi, 2 * n - 1), block_q), -1)
        lo2 = jnp.minimum(lo_a, lo_b)
        hi2 = jnp.where(lo_a < 2 ** 30, (2 * n - 1) // block_q, hi_b)
        return jnp.where(
            jnp.logical_and(i > hi1, lo2 <= hi2), jnp.clip(i, lo2, hi2),
            jnp.clip(i, lo1, jnp.maximum(hi1, lo1)))


def _rule_tiles(rule: MaskRule, tile, i, j, block_q: int, block_k: int,
                **kw):
    """Run ``tile(mask)`` on the tiles of the grid that ``rule`` says run,
    each under its condition."""
    for condition, mask in rule.tiles(i, j, block_q, block_k, **kw):
        if condition is None:
            tile(mask)
        else:
            pl.when(condition)(functools.partial(tile, mask))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs,
                  n_real: int, block_k: int, scale: float | None,
                  rule: MaskRule = NO_MASK, keep_ref=None):
    """One (q-block, k-block) tile of online-softmax attention.  ``refs``:
    the running (max, sum, accumulator) scratch, after the log-sum-exp
    output where the call was built with one (the differentiated forward).
    ``rule``: which tiles run and the mask inside one (:class:`MaskRule`); a
    tile that the rule steps over is not computed, and only a tile that its
    mask crosses pays for it.  ``keep_ref`` (a causal call's): the tile of
    each query's key set, int8; every tile that runs is masked by it, and it
    holds the causal mask already."""
    *lse_ref, m_ref, s_ref, acc_ref = refs
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        s_ref[:] = jnp.zeros_like(s_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile(mask=None):
        q = q_ref[0]          # (bq, ck)
        k = k_ref[0]          # (bk, ck)
        v = v_ref[0]          # (bk, cv)
        scores = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32)   # (bq, bk)
        if scale is not None:
            scores = scores * scale
        if keep_ref is not None:
            # a row none of whose keys has been kept yet sums rubbish at the
            # running max -1e30; its first kept key (at the latest its own
            # position's tile) rescales that to nothing
            scores = jnp.where(_kept(keep_ref[0]), scores, _NEG_INF)
        elif mask is not None:
            # the rule's: keys past the true token count (N was padded to a
            # block multiple), a diagonal that the tile crosses; as the key
            # set's, a row with no key yet is rescaled by its first
            seen = mask(scores.shape)
            if seen is not None:
                scores = jnp.where(seen, scores, _NEG_INF)

        m_prev = m_ref[:, :1]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                      # (bq, bk)
        s_new = s_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        s_ref[:] = jnp.broadcast_to(s_new, s_ref.shape)

    _rule_tiles(rule, tile, lambda: pl.program_id(1), j, q_ref.shape[1],
                block_k, n_real=n_real, merged=keep_ref is not None)

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(s_ref[:, :1], 1e-30)
                    ).astype(o_ref.dtype)
        if lse_ref:  # every lane of a row holds the row's value
            lse_ref[0][0] = m_ref[:] + jnp.log(s_ref[:])


def _kept(tile):
    """Where an int8 tile of a key set holds a key."""
    return tile.astype(jnp.int32) != 0


def _sparse_flash_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, *refs, **kw):
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs, keep_ref=keep_ref, **kw)


def _pad_keep(keep, rows: int, cols: int):
    """Zero-pad a (B, S, S) array of token pairs (an int8 key set: a padded
    query keeps nothing, a padded key is kept by no query) to (B, rows,
    cols)."""
    return jnp.pad(keep, ((0, 0), (0, rows - keep.shape[1]),
                          (0, cols - keep.shape[2])))


def _pad_tokens(x, n_padded: int):
    """Zero-pad the token dim (1) of a (B, N, ...) array to ``n_padded``."""
    pad = n_padded - x.shape[1]
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def _flash_local(q, k, v, keep=None, *, block_q: int, block_k: int,
                 scale: float | None, interpret: bool, with_lse: bool,
                 rule: MaskRule = NO_MASK, group: int = 1, heads: int = 1):
    """``keep``: int8 (B / heads, N, N), each query's key set, shared by the
    ``heads`` rows of ``q`` that are one sequence's query heads."""
    b, n, ck = q.shape
    cv = v.shape[-1]
    nq = pl.cdiv(n, block_q)
    nk = pl.cdiv(n, block_k)
    q = _pad_tokens(q, nq * block_q)
    k = _pad_tokens(k, nk * block_k)
    v = _pad_tokens(v, nk * block_k)

    kernel = functools.partial(
        _flash_kernel if keep is None else _sparse_flash_kernel,
        n_real=n, block_k=block_k, scale=scale, rule=rule)
    # a token call's tiles (``_CAUSAL_TILE``) pass Mosaic's default scope
    extra = dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_BWD_VMEM_LIMIT)) if rule is not NO_MASK else {}

    def kv_map(b_, i, j):
        if group > 1:  # row ``b_`` is a query head of key/value head:
            b_ = b_ // group
        # on a tile that the rule steps over the index stays on one that
        # runs, so it starts no copy
        return (b_, rule.hold_key(i, j, block_q, block_k), 0)

    in_specs = [
        pl.BlockSpec((1, block_q, ck), lambda b_, i, j: (b_, i, 0)),
        pl.BlockSpec((1, block_k, ck), kv_map),
        pl.BlockSpec((1, block_k, cv), kv_map),
    ]
    operands = (q, k, v)
    name = rule.forward
    if keep is not None:
        def keep_map(b_, i, j):
            return (b_ // heads, i, kv_map(b_, i, j)[1])
        in_specs.append(pl.BlockSpec((1, block_q, block_k), keep_map))
        operands += (_pad_keep(keep, nq * block_q, nk * block_k),)
        name = scopes.SPARSE_ATTN
    out_specs = [pl.BlockSpec((1, block_q, cv), lambda b_, i, j: (b_, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, nq * block_q, cv), v.dtype)]
    if with_lse:
        # float32, the row's value on all 128 lanes (the layout the running
        # max and sum already have); lane 0 is what the reverse pass keeps
        out_specs.append(
            pl.BlockSpec((1, block_q, 128), lambda b_, i, j: (b_, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, nq * block_q, 128), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=(b, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
            pltpu.VMEM((block_q, cv), jnp.float32),    # accumulator
        ],
        interpret=interpret,
        # the call's name is its innermost scope, and the TPU compiler names
        # the custom-call after that: the trace shows ``%pam`` whatever
        # encloses the call (a module, a shard_map)
        name=name,
        **extra,
    )(*operands)
    out = res[0][:, :n, :]
    if with_lse:
        return out, res[1][:, :n, 0]
    return out


def _flash_forward(q, k, v, block_q: int, block_k: int,
                   scale: float | None, interpret: bool,
                   with_lse: bool = False, rule: MaskRule = NO_MASK,
                   group: int = 1, keep=None, heads: int = 1):
    return _on_local_batch(
        functools.partial(_flash_local, block_q=block_q, block_k=block_k,
                          scale=scale, interpret=interpret,
                          with_lse=with_lse, rule=rule, group=group,
                          heads=heads),
        q, k, v, *(() if keep is None else (keep,)))


# ------------------------------------------------- position reverse pass
#: tokens per side of a reverse-pass tile (keys on sublanes, queries on
#: lanes); fewer tokens than that run as one tile, padded to the lane.
#: On the v5e at 4,096 tokens 256 / 512 / 1,024 a side take 2.70 / 2.30 /
#: 2.20 ms (PERF.md, PR 27): 512 has the gain and a quarter of the VMEM
_BWD_BLOCK = 512
#: what the reverse-pass calls may take of VMEM (a v5e core has 128 MiB;
#: Mosaic's default scope of 16 MiB is below one 512-tile's working set
#: beside a resident dQ)
_BWD_VMEM_LIMIT = 64 * 2 ** 20
#: the fused schedule keeps one image's float32 dQ in VMEM for a whole
#: (key-block, query-block) sweep; beyond this many bytes of it (as VMEM
#: lays it out: 128 lanes a row, two buffers) the two-sweep schedule runs
_BWD_DQ_RESIDENT_LIMIT = 16 * 2 ** 20

def _bwd_plan(n: int, ck: int) -> tuple[int, bool]:
    """``(block, fused)`` of the reverse pass at ``n`` tokens with
    ``ck``-channel queries, from the shapes alone."""
    block = min(_BWD_BLOCK, 128 * pl.cdiv(n, 128))
    n_padded = block * pl.cdiv(n, block)
    resident = 2 * n_padded * 128 * pl.cdiv(ck, 128) * 4
    return block, resident <= _BWD_DQ_RESIDENT_LIMIT


def _bwd_tile(q, k, v, do, lse, delta, *, scale: float | None, mask=None,
              keep=None):
    """``(Pᵀ, dSᵀ)`` of one tile, keys on sublanes and queries on lanes —
    the orientation in which ``lse`` and ``delta`` (per query) are lane-
    dense rows and dV, dK need no transpose.  float32 throughout.
    ``mask``: the rule's for this tile (``MaskRule.tiles``: the padded keys,
    a diagonal the tile lies on), or ``None`` where every pair attends.
    ``keep``: the tile of the queries' key sets, keys on sublanes, int8; it
    holds the causal mask and the padding's already."""
    st = jax.lax.dot_general(k, q, _NT,
                             preferred_element_type=jnp.float32)  # (bk, bq)
    if scale is not None:
        st = st * scale
    pt = jnp.exp(st - lse)
    if keep is not None:
        pt = jnp.where(_kept(keep), pt, 0.0)
    elif mask is not None:
        seen = mask(pt.shape)
        if seen is not None:
            pt = jnp.where(seen, pt, 0.0)
    dpt = jax.lax.dot_general(v, do, _NT,
                              preferred_element_type=jnp.float32)
    dst = pt * (dpt - delta)
    if scale is not None:
        dst = dst * scale
    return pt, dst


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *refs, n_real: int,
                    scale: float | None, rule: MaskRule = NO_MASK,
                    keep_ref=None):
    """The key-block sweep, queries innermost: dK and dV of the block
    accumulate in float32 scratch.  Built with a dQ output (the fused
    schedule) it also adds the tile's ``dS·k`` into the image's dQ, which
    stays in VMEM until the grid moves to the next image."""
    *dq_ref, dk_acc, dv_acc = refs
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile(mask=None):
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        pt, dst = _bwd_tile(q, k, v_ref[0], do, lse_ref[0], delta_ref[0],
                            scale=scale, mask=mask,
                            keep=None if keep_ref is None else keep_ref[0])
        dst = dst.astype(q.dtype)
        dv_acc[:] += jax.lax.dot_general(pt.astype(do.dtype), do, _NN,
                                         preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(dst, q, _NN,
                                         preferred_element_type=jnp.float32)
        if dq_ref:
            dq = jax.lax.dot_general(dst, k, _TN,
                                     preferred_element_type=jnp.float32)
            block = q.shape[0]
            rows = pl.ds(pl.multiple_of(i * block, block), block)

            if not rule.first_key_tile_meets_all:
                dq_ref[0][0, rows, :] += dq   # zeroed at the sweep's start
                return

            # causal: key block 0 counts for every query block, so each
            # row of dQ is still written before it is added to
            @pl.when(j == 0)
            def _first():
                dq_ref[0][0, rows, :] = dq

            @pl.when(j > 0)
            def _add():
                dq_ref[0][0, rows, :] += dq

    if dq_ref and not rule.first_key_tile_meets_all:
        @pl.when(jnp.logical_and(i == 0, j == 0))
        def _init_dq():
            dq_ref[0][:] = jnp.zeros_like(dq_ref[0])

    block = q_ref.shape[1]
    _rule_tiles(rule, tile, i, j, block, block, n_real=n_real, reverse=True,
                merged=keep_ref is not None)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _sparse_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           keep_ref, dk_ref, dv_ref, *refs, **kw):
    _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                    dv_ref, *refs, keep_ref=keep_ref, **kw)


def _sparse_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          keep_ref, dq_ref, dq_acc, **kw):
    _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, keep_ref=keep_ref, **kw)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, n_real: int, scale: float | None,
                   rule: MaskRule = NO_MASK, keep_ref=None):
    """The query-block sweep of the two-sweep schedule, keys innermost."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def tile(mask=None):
        k = k_ref[0]
        _, dst = _bwd_tile(q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0],
                           delta_ref[0], scale=scale, mask=mask,
                           keep=None if keep_ref is None else keep_ref[0])
        dq_acc[:] += jax.lax.dot_general(dst.astype(k.dtype), k, _TN,
                                         preferred_element_type=jnp.float32)

    block = q_ref.shape[1]
    _rule_tiles(rule, tile, lambda: pl.program_id(1), j, block, block,
                n_real=n_real, reverse=True, merged=keep_ref is not None)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_backward_local(q, k, v, out, lse, do, keep_t=None, *,
                          scale: float | None, interpret: bool,
                          rule: MaskRule = NO_MASK, group: int = 1,
                          heads: int = 1):
    """dq, dk, dv of flash position attention from the saved output and
    log-sum-exp.  Per tile: ``S = q·kᵀ`` (× ``scale``), ``P = exp(S − lse)``
    with padded keys at 0, ``dP = dO·vᵀ``, ``dS = P ∘ (dP − δ)`` with ``δ =
    rowsum(dO ∘ out)``; then ``dV += Pᵀ·dO``, ``dK += dSᵀ·q``, ``dQ +=
    dS·k``.  MXU operands in the inputs' dtype, everything else float32.

    Two schedules, chosen by :func:`_bwd_plan` from the shapes.  *Fused*
    (one call, ``pam_bwd_fused``): grid ``(batch, k_blocks, q_blocks)``; the
    whole float32 dQ of an image is the call's resident output block, so S
    and dP are computed once.  *Two sweeps* (``pam_bwd_dkv`` without the dQ
    output, then ``pam_bwd_dq`` on grid ``(batch, q_blocks, k_blocks)``):
    O(block) VMEM at any N, at the price of computing S and dP twice.

    ``rule`` (the calls take its names: ``causal_attn_bwd_…``,
    ``blockdiff_attn_bwd_…``): the tiles that it says do not run are stepped
    over in both schedules (a causal call's: those above the diagonal), and
    the inner axis' block index stays on one that runs while they are, so
    they copy nothing.  ``group`` > 1: a row of ``q`` is a query head and ``group`` of
    them read one row of ``k``, ``v``; each writes its own float32 dK, dV,
    summed over the group here.  ``keep_t`` (the calls are then named
    ``sparse_attn_bwd_…``): int8 (B / heads, N keys, N queries), the queries'
    key sets transposed to the tiles' orientation."""
    b, n, ck = q.shape
    cv = v.shape[-1]
    block, fused = _bwd_plan(n, ck)
    nb = pl.cdiv(n, block)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    # padded query rows: q = 0, dO = 0, δ = 0 and lse = 0, so P = 1 there
    # and dS = 0; they add nothing to dK, dV and their own dQ is cut off
    q, k, v, do = (_pad_tokens(x, nb * block) for x in (q, k, v, do))
    lse, delta = (_pad_tokens(x, nb * block)[:, None, :]
                  for x in (lse, delta))

    static = dict(n_real=n, scale=scale, rule=rule)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_BWD_VMEM_LIMIT)
    names = rule.reverse_calls
    dkv_kernel, dq_kernel, operands = _bwd_dkv_kernel, _bwd_dq_kernel, ()
    if keep_t is not None:
        names = (scopes.SPARSE_ATTN_BWD_FUSED, scopes.SPARSE_ATTN_BWD_DKV,
                 scopes.SPARSE_ATTN_BWD_DQ)
        dkv_kernel, dq_kernel = _sparse_bwd_dkv_kernel, _sparse_bwd_dq_kernel
        operands = (_pad_keep(keep_t, nb * block, nb * block),)

    def specs(at_q, at_k):
        """In-specs of (q, k, v, dO, lse, δ); ``at_q`` / ``at_k``: which
        grid axis walks the query blocks / the key blocks."""
        def block_at(axis):
            if axis != 2:
                return lambda g: g[axis]
            # the inner axis does not leave the tiles that run (a causal
            # call's: the queries' index not below the key block's, the
            # keys' not above the query block's)
            if axis == at_q:
                return lambda g: rule.hold_query(g[1], g[2], block, block)
            return lambda g: rule.hold_key(g[1], g[2], block, block,
                                           reverse=True)
        qi, ki = block_at(at_q), block_at(at_k)
        kv_row = (lambda g: g[0] // group) if group > 1 else (lambda g: g[0])

        def tokens(c, row, at):
            return pl.BlockSpec((1, block, c),
                                lambda *g: (row(g), at(g), 0))
        q_row = lambda g: g[0]
        row = pl.BlockSpec((1, 1, block), lambda *g: (g[0], 0, qi(g)))
        sparse = [] if keep_t is None else [pl.BlockSpec(
            (1, block, block), lambda *g: (g[0] // heads, ki(g), qi(g)))]
        return [tokens(ck, q_row, qi), tokens(ck, kv_row, ki),
                tokens(cv, kv_row, ki), tokens(cv, q_row, qi), row, row
                ] + sparse

    # the key-block sweep: grid (batch, k_blocks, q_blocks)
    dkv_specs = specs(2, 1)
    out_specs = [dkv_specs[1], dkv_specs[2]]
    out_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if group > 1:  # one dK, dV per query head
        out_specs = [pl.BlockSpec((1, block, c), lambda b_, j, i: (b_, j, 0))
                     for c in (ck, cv)]
        out_shape = [jax.ShapeDtypeStruct((b,) + x.shape[1:], jnp.float32)
                     for x in (k, v)]
    if fused:
        out_specs.append(
            pl.BlockSpec((1,) + q.shape[1:], lambda b_, j, i: (b_, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(q.shape, jnp.float32))
    res = pl.pallas_call(
        functools.partial(dkv_kernel, **static),
        grid=(b, nb, nb),
        in_specs=dkv_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block, ck), jnp.float32),
                        pltpu.VMEM((block, cv), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name=names[0] if fused else names[1],
    )(q, k, v, do, lse, delta, *operands)
    dk, dv = res[0], res[1]
    if group > 1:
        dk, dv = (x.reshape((b // group, group) + x.shape[1:]).sum(1)
                  .astype(like.dtype) for x, like in ((dk, k), (dv, v)))
    if fused:
        dq = res[2].astype(q.dtype)
    else:
        dq = pl.pallas_call(
            functools.partial(dq_kernel, **static),
            grid=(b, nb, nb),
            in_specs=specs(1, 2),
            out_specs=pl.BlockSpec((1, block, ck),
                                   lambda b_, i, j: (b_, i, 0)),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((block, ck), jnp.float32)],
            compiler_params=params,
            interpret=interpret,
            name=names[2],
        )(q, k, v, do, lse, delta, *operands)
    return dq[:, :n], dk[:, :n], dv[:, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_position_attention(q, k, v, block_q: int = 256, block_k: int = 256,
                             scale: float | None = None,
                             interpret: bool = False,
                             rule: MaskRule = NO_MASK, group: int = 1):
    """Flash position attention: same math as
    :func:`ops.attention.position_attention` (unscaled DANet energies unless
    ``scale``), O(N·block) memory, MXU-scheduled.  ``block_q`` / ``block_k``
    tile the forward; the reverse pass sizes its own tiles from the shapes.

    ``q``/``k``: (B, N, Ck); ``v``: (B, N, Cv) -> (B, N, Cv).  ``rule``: a
    :class:`MaskRule` — :data:`NO_MASK`, :data:`CAUSAL` (row ``t`` attends to
    rows ``<= t``), a :class:`BlockDiffusion`.  ``group``: ``q`` has ``group``
    times the rows of ``k`` and ``v``, and rows ``g·group … g·group + group
    − 1`` of it read row ``g`` of theirs (grouped-query heads laid on the
    batch axis: :func:`flash_causal_attention`).
    """
    return _flash_forward(q, k, v, block_q, block_k, scale, interpret,
                          rule=rule, group=group)


#: ``checkpoint_name``s of what the causal reverse pass keeps of its forward
#: call: a block that recomputes itself under a policy that saves these
#: names (8 MB + 131 KB at the token cell's shape) runs no second forward
#: call.  A policy that names nothing (``nn.remat``'s default) is unmoved
KEPT_BY_REVERSE = CAUSAL.kept


def _fwd(q, k, v, block_q, block_k, scale, interpret, rule, group):
    out, lse = _flash_forward(q, k, v, block_q, block_k, scale, interpret,
                              with_lse=True, rule=rule, group=group)
    if rule.kept:
        out, lse = map(checkpoint_name, (out, lse), rule.kept)
    return out, (q, k, v, out, lse)


def _bwd(block_q, block_k, scale, interpret, rule, group, res, g):
    # the flash backward as Mosaic calls: no recompute of the forward's
    # recurrence, no N×N array in HBM (see _flash_backward_local)
    with jax.named_scope(rule.reverse_scope):
        return _on_local_batch(
            functools.partial(_flash_backward_local, scale=scale,
                              interpret=interpret, rule=rule,
                              group=group), *res, g)


flash_position_attention.defvjp(_fwd, _bwd)


#: (queries, keys) of a causal forward tile.  On the v5e at 8,192 tokens, 4
#: query heads of 128 (PERF.md, PR 34): 256 / 512 / 1,024 a side take 2.65 /
#: 1.29 / 0.68 ms, the last 57% of the MXU's peak for the tiles it runs
_CAUSAL_TILE = (1024, 1024)


def flash_causal_attention(q, k, v, interpret: bool = False,
                           rule: Causal = CAUSAL):
    """:func:`ops.attention.causal_attention` through the flash kernels,
    forward and reverse: no (S, S) array of scores, probabilities or their
    gradients reaches HBM, and the half of the tiles above the diagonal is
    neither computed nor fetched.  Softmax statistics in float32, MXU
    operands in the inputs' dtype.

    ``q``: (B, S, Hq, D); ``k``: (B, S, Hkv, D); ``v``: (B, S, Hkv, Dv),
    as wide as the keys or narrower (latent attention: D 192, Dv 128; the
    kernels take the two widths apart, scores scaled by 1/√D), with ``Hq`` a
    multiple of ``Hkv``: query heads ``g·r … g·r + r − 1`` read key/value
    head ``g`` through the kernels' index maps, nothing is repeated in HBM.
    Returns (B, S, Hq, Dv).  ``rule``: :data:`CAUSAL`, or a :class:`Causal`
    whose ``kept_operands`` name the heads-first operands, for a block that
    keeps them across its recomputation."""
    b, s, hq, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    if hq % hkv:
        raise ValueError(f"{hq} query heads do not share {hkv} key/value "
                         f"heads evenly")
    if k.shape[-1] != d:
        raise ValueError(f"keys {k.shape[-1]} wide do not meet queries {d} "
                         "wide")

    out = flash_position_attention(
        *_operands(rule, q, k, v), *_causal_blocks(s), 1 / math.sqrt(d),
        interpret=interpret, rule=rule, group=hq // hkv)
    return out.reshape(b, hq, s, dv).transpose(0, 2, 1, 3)


def flash_block_diffusion_attention(q, k, v, length: int, block: int,
                                    interpret: bool = False):
    """:func:`ops.attention.block_diffusion_attention` through the flash
    kernels, forward and reverse, under the rule
    :class:`BlockDiffusion`: ``q``: (B, 2·length, Hq, D), the clean copy of
    a sequence and its noised copy end to end; ``k``, ``v``: (B, 2·length,
    Hkv, D) -> (B, 2·length, Hq, D).  Of the grid's tiles those that hold an
    attended pair run (24 of 64 at tiles of 1,024 and 4,096 tokens), half of
    them with no mask; the calls are ``blockdiff_attn`` and
    ``blockdiff_attn_bwd_…``, and a block that saves the rule's ``kept``
    and ``kept_operands`` names keeps their output and log-sum-exp and the
    heads-first operands across its recomputation."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if hq % hkv:
        raise ValueError(f"{hq} query heads do not share {hkv} key/value "
                         f"heads evenly")
    if s != 2 * length:
        raise ValueError(f"{s} positions are not a sequence of {length} "
                         "and its noised copy")
    rule = BlockDiffusion(length=length, block=block)
    out = flash_position_attention(
        *_operands(rule, q, k, v), *_causal_blocks(s), 1 / math.sqrt(d),
        interpret=interpret, rule=rule, group=hq // hkv)
    return out.reshape(b, hq, s, d).transpose(0, 2, 1, 3)


# --------------------------------------------- learned sparse attention
#: ``checkpoint_name``s of what a sparse attention block keeps across its
#: recomputation: the forward call's output and log-sum-exp (as
#: :data:`KEPT_BY_REVERSE`) and the key set, so that the reverse pass runs
#: neither a second forward call nor a second selection.  The block's model
#: keeps the index scores' gradients beside them, under names of its own
#: (``models/keye_lm.py::INDEX_GRADS_KEPT``): nothing in the reverse pass
#: then reads an (S, S) array but the key set
SPARSE_KEPT_BY_REVERSE = ("sparse_attn_out", "sparse_attn_lse",
                          "sparse_attn_keep")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _sparse_attention(q, k, v, keep, block_q, block_k, scale, interpret,
                      group, heads):
    return _flash_forward(q, k, v, block_q, block_k, scale, interpret,
                          with_lse=True, rule=CAUSAL, group=group, keep=keep,
                          heads=heads)


def _sparse_fwd(q, k, v, keep, block_q, block_k, scale, interpret, group,
                heads):
    out, lse = _flash_forward(q, k, v, block_q, block_k, scale, interpret,
                              with_lse=True, rule=CAUSAL, group=group,
                              keep=keep, heads=heads)
    out, lse = map(checkpoint_name, (out, lse), SPARSE_KEPT_BY_REVERSE[:2])
    return (out, lse), (q, k, v, keep, out, lse)


def _sparse_bwd(block_q, block_k, scale, interpret, group, heads, res, g):
    q, k, v, keep, out, lse = res
    with jax.named_scope(scopes.SPARSE_ATTN_BWD):
        # the reverse tiles hold keys on sublanes and queries on lanes
        grads = _on_local_batch(
            functools.partial(_flash_backward_local, scale=scale,
                              interpret=interpret, rule=CAUSAL, group=group,
                              heads=heads),
            q, k, v, out, lse, g[0], keep.swapaxes(1, 2))
    return (*grads, None)


_sparse_attention.defvjp(_sparse_fwd, _sparse_bwd)


def _heads_first(x):
    """(B, S, H, D) -> (B·H, S, D): heads beside the batch on the grid's
    first axis."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _operands(rule: MaskRule, q, k, v) -> tuple:
    """q, k, v heads-first, named by ``rule.kept_operands`` where it names
    them."""
    operands = map(_heads_first, (q, k, v))
    if rule.kept_operands:  # each named as it is made
        operands = map(checkpoint_name, operands, rule.kept_operands)
    return tuple(operands)


def _causal_blocks(s: int) -> tuple[int, int]:
    return tuple(min(t, 128 * pl.cdiv(s, 128)) for t in _CAUSAL_TILE)


def flash_sparse_attention(q, k, v, keep, interpret: bool = False):
    """:func:`flash_causal_attention` over a per-query key set
    (``ops/attention.py::causal_attention`` with ``keep``): every tile at or
    below the diagonal runs, masked by its tile of ``keep``; the calls are
    ``sparse_attn`` and ``sparse_attn_bwd_…``.

    ``keep``: int8 (B, S, S), ``keep[b, t, s] != 0`` where query ``t`` of
    every head attends to key ``s``; it holds the causal mask (``s <= t``)
    and at least one key a row.  Read once per query head and pass.
    Returns ``(out (B, S, Hq, D), lse (B·Hq, S) float32)``; no gradient
    reaches ``keep`` or comes from ``lse``."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if hq % hkv:
        raise ValueError(f"{hq} query heads do not share {hkv} key/value "
                         f"heads evenly")
    out, lse = _sparse_attention(
        _heads_first(q), _heads_first(k), _heads_first(v), keep,
        *_causal_blocks(s), 1 / math.sqrt(d), interpret, hq // hkv, hq)
    return out.reshape(b, hq, s, d).transpose(0, 2, 1, 3), lse


def _probs_kernel(q_ref, k_ref, lse_ref, keep_ref, s_ref, lse_i_ref, d_ref,
                  kl_ref, *, scale: float, heads: int):
    """One (q-block, k-block) tile of the alignment loss's terms, the query
    heads on the grid's last axis: the tile of the head-averaged target
    ``p`` stays in VMEM while they add ``exp(S − lse)`` of their own scores
    to it, and the last head turns it into ``D = softmax(I) − p`` over the
    key set (``logq = I − lse_I``) and adds the tile's part of the rows' KL
    (keys where ``p > 0``) to the query block's, which stays in VMEM over
    the key tiles."""
    i, j, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    block_q, block_k = d_ref.shape[1:]
    causal = j * block_k < (i + 1) * block_q

    @pl.when(h == 0)
    def _init():
        d_ref[0] = jnp.zeros_like(d_ref[0])

    @pl.when(jnp.logical_and(h == 0, j == 0))
    def _init_rows():
        kl_ref[0] = jnp.zeros_like(kl_ref[0])

    @pl.when(causal)
    def _tile():
        scores = jax.lax.dot_general(
            q_ref[0], k_ref[0], _NT,
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(scores - lse_ref[0])
        d_ref[0] += jnp.where(_kept(keep_ref[0]), p, 0.0) * (1.0 / heads)

    @pl.when(jnp.logical_and(h == heads - 1, causal))
    def _finish():
        keep, p = _kept(keep_ref[0]), d_ref[0]
        logq = s_ref[0] - lse_i_ref[0]
        d_ref[0] = jnp.where(keep, jnp.exp(logq) - p, 0.0)
        live = jnp.logical_and(keep, p > 0)
        kl_ref[0] += jnp.sum(jnp.where(
            live, p * (jnp.log(jnp.where(live, p, 1.0)) - logq), 0.0),
            axis=-1, keepdims=True)


def _probs_local(q, k, lse, keep, scores, lse_i, *, block_q: int,
                 block_k: int, scale: float, interpret: bool, group: int,
                 heads: int):
    bh, n, d = q.shape
    b = bh // heads
    nq, nk = pl.cdiv(n, block_q), pl.cdiv(n, block_k)
    q = _pad_tokens(q, nq * block_q)
    k = _pad_tokens(k, nk * block_k)
    lse = _pad_tokens(lse, nq * block_q)[:, :, None]
    lse_i = _pad_tokens(lse_i, nq * block_q)[:, :, None]

    def last(i, j):  # a tile above the diagonal fetches nothing new
        return jnp.minimum(j, (i * block_q + block_q - 1) // block_k)

    pairs = pl.BlockSpec((1, block_q, block_k),
                         lambda b_, i, j, h: (b_, i, last(i, j)))
    rows = pl.BlockSpec((1, block_q, 1), lambda b_, i, j, h: (b_, i, 0))
    d, kl = pl.pallas_call(
        functools.partial(_probs_kernel, scale=scale, heads=heads),
        grid=(b, nq, nk, heads),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b_, i, j, h: (b_ * heads + h, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b_, i, j, h: ((b_ * heads + h) // group,
                                              last(i, j), 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda b_, i, j, h: (b_ * heads + h, i, 0)),
            pairs, pairs, rows,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, block_k),
                         lambda b_, i, j, h: (b_, i, j)),
            rows,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nq * block_q, nk * block_k),
                                 jnp.float32),
            jax.ShapeDtypeStruct((b, nq * block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # the rows' KL adds up over the key tiles
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
        name=scopes.SPARSE_ATTN_PROBS,
    )(q, k, lse, _pad_keep(keep, nq * block_q, nk * block_k),
      _pad_keep(scores, nq * block_q, nk * block_k), lse_i)
    return d[:, :n, :n], kl[:, :n, 0]


def flash_index_align_terms(q, k, keep, lse, scores, lse_i,
                            interpret: bool = False):
    """The alignment loss's terms (``models/keye_lm.py::index_align_terms``)
    from the log-sum-exp that :func:`flash_sparse_attention` handed back and
    the index scores' over the key set that :func:`flash_topk_keep` did:
    ``(D, row_kl)``, float32 (B, S, S) and (B, S) — ``D = softmax over the
    set of scores − p``, zero outside the set, and each row's KL from ``p``
    to that softmax, where ``p`` is the mean over the query heads of each
    head's probabilities over the set.  One Mosaic call (``sparse_probs``),
    heads innermost: ``p`` never leaves VMEM, the only (S, S) array written
    is ``D`` (a tile above the diagonal as zeros).  No gradient (a
    selector's alignment target and its gradient are constants)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    block_q, block_k = _causal_blocks(s)
    q, k, keep, lse, scores, lse_i = map(
        jax.lax.stop_gradient, (q, k, keep, lse, scores, lse_i))
    return _on_local_batch(
        functools.partial(_probs_local, block_q=block_q, block_k=block_k,
                          scale=1 / math.sqrt(d), interpret=interpret,
                          group=hq // hkv, heads=hq),
        _heads_first(q), _heads_first(k), lse, keep, scores, lse_i)


#: query rows of a selection block (each holds its whole row of keys in
#: VMEM: 4 MB of float32 scores and as much of sortable keys at 8,192), and
#: the keys of one counting pass's chunk
_SELECT_ROWS = 128
_SELECT_CHUNK = 1024
_INT_MIN = -2 ** 31


def _topk_keep_kernel(s_ref, o_ref, lse_ref, key_ref, *, topk: int,
                      chunk: int):
    """The key sets of a block of queries, exactly and with no sort
    (``ops/attention.py::threshold_keep``): the scores become int32 keys of
    the same order, the ``n``-th largest key of each row is built bit by bit
    from counts of the row (``n = min(t + 1, topk)``), then the index up to
    which the keys that equal it are kept.  A pass counts only the chunks
    that hold a causal key of the block.  Beside the set, each row's
    log-sum-exp of its scores over the set (the largest causal score is
    always kept)."""
    rows, length = key_ref.shape
    q_lo = pl.program_id(1) * rows
    t = q_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    n_chunks = length // chunk
    live = (q_lo + rows + chunk - 1) // chunk    # chunks with a causal key

    def cols_of(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def col_index(c):
        return c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (rows, chunk), 1)

    def scores_of(c):
        return s_ref[0, :, cols_of(c)]

    def write(keep_of):
        def top_of(c, top):
            return jnp.maximum(top, jnp.max(
                jnp.where(col_index(c) <= t, scores_of(c), -jnp.inf),
                axis=-1, keepdims=True))
        top = jax.lax.fori_loop(0, live, top_of,
                                jnp.full((rows, 1), -jnp.inf, jnp.float32))

        def body(c, total):
            keep = jnp.logical_and(keep_of(c), col_index(c) <= t)
            o_ref[0, :, cols_of(c)] = keep.astype(jnp.int32).astype(jnp.int8)
            return total + jnp.sum(jnp.where(keep, jnp.exp(scores_of(c) - top),
                                             0.0), axis=-1, keepdims=True)
        total = jax.lax.fori_loop(0, live, body,
                                  jnp.zeros((rows, 1), jnp.float32))
        lse_ref[0] = top + jnp.log(total)

        def past(c, carry):   # no causal key of the block lies here
            o_ref[0, :, cols_of(c)] = jnp.zeros((rows, chunk), jnp.int8)
            return carry
        jax.lax.fori_loop(live, n_chunks, past, 0)

    @pl.when(q_lo + rows <= topk)
    def _every_causal_key():
        write(lambda c: jnp.full((rows, chunk), True))

    @pl.when(q_lo + rows > topk)
    def _select():
        want = jnp.minimum(t + 1, topk).astype(jnp.float32)

        def fill(c, carry):
            bits = jax.lax.bitcast_convert_type(scores_of(c), jnp.int32)
            key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
            key_ref[:, cols_of(c)] = jnp.where(col_index(c) <= t, key,
                                               jnp.int32(_INT_MIN))
            return carry
        jax.lax.fori_loop(0, live, fill, 0)

        def count(hit):
            """(rows, 1) float32: the row's keys that ``hit(key, col)``."""
            def body(c, acc):
                return acc + jnp.sum(
                    jnp.where(hit(key_ref[:, cols_of(c)], col_index(c)),
                              1.0, 0.0), axis=-1, keepdims=True)
            return jax.lax.fori_loop(0, live, body,
                                     jnp.zeros((rows, 1), jnp.float32))

        def value_bit(i, thr):
            # offset-binary: INT_MIN + 2^31 wraps to 0, the top bit's turn
            cand = thr + jnp.left_shift(jnp.int32(1), 31 - i)
            enough = count(lambda key, col: key >= cand) >= want
            return jnp.where(enough, cand, thr)

        thr = jax.lax.fori_loop(
            0, 32, value_bit, jnp.full((rows, 1), _INT_MIN, jnp.int32))
        spare = want - count(lambda key, col: key > thr)

        def index_bit(i, last):
            cand = last + jnp.left_shift(jnp.int32(1), n_bits - 1 - i)
            below = count(lambda key, col: jnp.logical_and(key == thr,
                                                           col < cand))
            return jnp.where(below < spare, cand, last)

        n_bits = max(1, (length - 1).bit_length())
        last = jax.lax.fori_loop(0, n_bits, index_bit,
                                 jnp.zeros((rows, 1), jnp.int32))

        def keep_of(c):
            key = key_ref[:, cols_of(c)]
            return jnp.logical_or(key > thr, jnp.logical_and(
                key == thr, col_index(c) <= last))
        write(keep_of)


def _topk_keep_local(scores, *, topk: int, interpret: bool):
    b, n, _ = scores.shape
    rows = min(_SELECT_ROWS, 8 * pl.cdiv(n, 8))
    chunk = min(_SELECT_CHUNK, 128 * pl.cdiv(n, 128))
    nq = pl.cdiv(n, rows)
    length = chunk * pl.cdiv(n, chunk)
    # padded keys lie past every query; padded queries are cut off
    scores = _pad_keep(scores.astype(jnp.float32), nq * rows, length)
    rows_of = pl.BlockSpec((1, rows, length), lambda b_, i: (b_, i, 0))
    keep, lse = pl.pallas_call(
        functools.partial(_topk_keep_kernel, topk=topk, chunk=chunk),
        grid=(b, nq),
        in_specs=[rows_of],
        out_specs=[rows_of,
                   pl.BlockSpec((1, rows, 1), lambda b_, i: (b_, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, nq * rows, length), jnp.int8),
                   jax.ShapeDtypeStruct((b, nq * rows, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, length), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
        name=scopes.TOPK_KEEP,
    )(scores)
    return keep[:, :n, :n], lse[:, :n, 0]


def flash_topk_keep(scores, topk: int, interpret: bool = False):
    """``ops/attention.py::threshold_keep`` as one Mosaic call
    (``topk_keep``), a block of queries with their whole rows of keys in
    VMEM: the int8 (B, S, S) key set that :func:`flash_sparse_attention`
    reads — query ``t`` keeps exactly the ``min(t + 1, topk)`` causal keys
    of largest score, equal scores to the lower index — and float32 (B, S)
    each row's log-sum-exp of its scores over the set, which
    :func:`flash_index_align_terms` reads.  The scores are read once and
    the set is written once."""
    n = scores.shape[1]
    if topk >= n:
        pos = jnp.arange(n)
        causal = pos[:, None] >= pos[None, :]
        return (jnp.broadcast_to(causal, scores.shape).astype(jnp.int8),
                jax.nn.logsumexp(jnp.where(causal, scores, -jnp.inf), -1))
    return _on_local_batch(
        functools.partial(_topk_keep_local, topk=topk, interpret=interpret),
        jax.lax.stop_gradient(scores))


#: (queries, keys) of an index-score tile, forward and reverse
_INDEXER_TILE = (512, 512)
_HIGHEST = jax.lax.Precision.HIGHEST


def _indexer_kernel(q_ref, k_ref, w_ref, o_ref, *, scale: float):
    """One tile of ``I = scale · Σ_j w_j ∘ ReLU(q_j · kᵀ)``, float32 at
    full precision; a tile above the diagonal is left as it is (no causal
    pair lies there)."""
    i, j = pl.program_id(1), pl.program_id(2)
    block_q, block_k = o_ref.shape[1:]

    @pl.when(j * block_k < (i + 1) * block_q)
    def _tile():
        k = k_ref[0]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(q_ref.shape[1]):
            s = jax.lax.dot_general(q_ref[0, h], k, _NT, precision=_HIGHEST,
                                    preferred_element_type=jnp.float32)
            acc += w_ref[0][:, h:h + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = acc * scale


def _indexer_bwd_kernel(q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref, dw_ref,
                        *, scale: float):
    """One tile of the reverse pass: the products again (full precision, so
    that a ReLU is open here where it was forward), then ``dw_j += Σ_s g ∘
    ReLU(s_j)``, ``dS_j = g ∘ w_j ∘ [s_j > 0]``, ``dq_j += dS_j · k`` and
    ``dk += dS_jᵀ · q_j`` in bfloat16 with float32 sums.  Keys innermost:
    the query block's dq and dw stay in VMEM over the sweep, and the whole
    sequence's dk over the call."""
    i, j = pl.program_id(1), pl.program_id(2)
    block_q, block_k = g_ref.shape[1:]

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_keys():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])

    @pl.when(j == 0)
    def _init_queries():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])
        dw_ref[0] = jnp.zeros_like(dw_ref[0])

    @pl.when(j * block_k < (i + 1) * block_q)
    def _tile():
        k = k_ref[0]
        g = g_ref[0] * scale
        k16 = k.astype(jnp.bfloat16)
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        dk = jnp.zeros(k.shape, jnp.float32)
        dw = jnp.zeros(dw_ref.shape[1:], jnp.float32)
        head = jax.lax.broadcasted_iota(jnp.int32, dw.shape, 1)
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h]
            s = jax.lax.dot_general(q, k, _NT, precision=_HIGHEST,
                                    preferred_element_type=jnp.float32)
            dw = jnp.where(head == h, jnp.sum(
                g * jnp.maximum(s, 0.0), axis=-1, keepdims=True), dw)
            ds = jnp.where(s > 0.0, g * w_ref[0][:, h:h + 1], 0.0
                           ).astype(jnp.bfloat16)
            dq_ref[0, h] += jax.lax.dot_general(
                ds, k16, _NN, preferred_element_type=jnp.float32)
            dk += jax.lax.dot_general(
                ds, q.astype(jnp.bfloat16), _TN,
                preferred_element_type=jnp.float32)
        dk_ref[0, rows, :] += dk
        dw_ref[0] += dw


def _indexer_operands(qi, ki, w, block_q: int, block_k: int):
    """(B, S, J, Di), (B, S, Di), (B, S, J) -> float32, heads first, padded
    to the tiles."""
    n = qi.shape[1]
    nq, nk = pl.cdiv(n, block_q), pl.cdiv(n, block_k)
    q = _pad_tokens(qi.astype(jnp.float32), nq * block_q).transpose(0, 2, 1, 3)
    k = _pad_tokens(ki.astype(jnp.float32), nk * block_k)
    w = _pad_tokens(w.astype(jnp.float32), nq * block_q)
    return q, k, w, nq, nk


def _indexer_specs(q, k, w, block_q: int, block_k: int):
    _, heads, _, di = q.shape

    def last(i, j):
        return jnp.minimum(j, (i * block_q + block_q - 1) // block_k)

    return [pl.BlockSpec((1, heads, block_q, di),
                         lambda b_, i, j: (b_, 0, i, 0)),
            pl.BlockSpec((1, block_k, di),
                         lambda b_, i, j: (b_, last(i, j), 0)),
            pl.BlockSpec((1, block_q, heads), lambda b_, i, j: (b_, i, 0))
            ], last


def _indexer_scale(qi) -> float:
    return 1 / math.sqrt(qi.shape[2] * qi.shape[3])


def _indexer_fwd_local(qi, ki, w, *, interpret: bool):
    n = qi.shape[1]
    block_q, block_k = (min(t, 128 * pl.cdiv(n, 128)) for t in _INDEXER_TILE)
    q, k, w, nq, nk = _indexer_operands(qi, ki, w, block_q, block_k)
    in_specs, _ = _indexer_specs(q, k, w, block_q, block_k)
    out = pl.pallas_call(
        functools.partial(_indexer_kernel, scale=_indexer_scale(qi)),
        grid=(q.shape[0], nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, block_k),
                               lambda b_, i, j: (b_, i, j)),
        out_shape=jax.ShapeDtypeStruct((q.shape[0], nq * block_q,
                                        nk * block_k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
        name=scopes.INDEXER_SCORES,
    )(q, k, w)
    return out[:, :n, :n]


def _indexer_bwd_local(qi, ki, w, g, *, interpret: bool):
    n = qi.shape[1]
    block_q, block_k = (min(t, 128 * pl.cdiv(n, 128)) for t in _INDEXER_TILE)
    q, k, wp, nq, nk = _indexer_operands(qi, ki, w, block_q, block_k)
    in_specs, last = _indexer_specs(q, k, wp, block_q, block_k)
    g = _pad_keep(g.astype(jnp.float32), nq * block_q, nk * block_k)
    in_specs.append(pl.BlockSpec((1, block_q, block_k),
                                 lambda b_, i, j: (b_, i, last(i, j))))
    dq, dk, dw = pl.pallas_call(
        functools.partial(_indexer_bwd_kernel, scale=_indexer_scale(qi)),
        grid=(q.shape[0], nq, nk),
        in_specs=in_specs,
        out_specs=[in_specs[0],
                   pl.BlockSpec((1,) + k.shape[1:], lambda b_, i, j: (b_, 0, 0)),
                   in_specs[2]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32)
                   for x in (q, k, wp)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret,
        name=scopes.INDEXER_SCORES_BWD,
    )(q, k, wp, g)
    return (dq.transpose(0, 2, 1, 3)[:, :n].astype(qi.dtype),
            dk[:, :n].astype(ki.dtype), dw[:, :n].astype(w.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_indexer_scores(qi, ki, w, interpret: bool = False):
    """``ops/attention.py::indexer_scores`` tile by tile, forward and
    reverse (``indexer_scores``, ``indexer_scores_bwd``): no (J, S, S) array
    reaches HBM.  The scores of the pairs above the diagonal are not
    computed and hold anything (a selector reads the causal pairs), and the
    reverse pass reads no cotangent there: tile by tile, what the forward
    call did not write has no gradient."""
    return _indexer_forward(qi, ki, w, interpret)


def _indexer_forward(qi, ki, w, interpret):
    return _on_local_batch(
        functools.partial(_indexer_fwd_local, interpret=interpret),
        qi, ki, w)


def _indexer_fwd(qi, ki, w, interpret):
    return _indexer_forward(qi, ki, w, interpret), (qi, ki, w)


def _indexer_bwd(interpret, res, g):
    return _on_local_batch(
        functools.partial(_indexer_bwd_local, interpret=interpret), *res, g)


flash_indexer_scores.defvjp(_indexer_fwd, _indexer_bwd)


def flash_indexer_scores_grads(qi, ki, w, g, interpret: bool = False):
    """:func:`flash_indexer_scores`'s reverse call (``indexer_scores_bwd``)
    for a caller that holds the scores' cotangent ``g`` (B, S, S) already:
    ``(dqi, dki, dw)``, no forward call."""
    return _indexer_bwd(interpret, (qi, ki, w), g)


# ---------------------------------------------------- channel (gram) branch

def _cam_energy_kernel(x_ref, attn_ref, energy_ref):
    """Fused gram + softmax: accumulate Xᵀ·X over row blocks in VMEM
    scratch; on the last block run DANet's max-subtraction softmax on
    the VPU and emit the (C, C) attention map.  Zero-padded rows (N not
    a block multiple) contribute zero to the gram — no masking needed."""
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        energy_ref[:] = jnp.zeros_like(energy_ref)

    x = x_ref[0]  # (block_n, C)
    energy_ref[:] += jax.lax.dot_general(
        x, x, _TN, preferred_element_type=jnp.float32)   # (C, C)

    @pl.when(j == nb - 1)
    def _finalize():
        energy = energy_ref[:]
        # DANet CAM: attend to the LEAST similar channels — rowmax - E
        energy = energy.max(axis=-1, keepdims=True) - energy
        m = energy.max(axis=-1, keepdims=True)
        p = jnp.exp(energy - m)
        attn_ref[0] = (p / p.sum(axis=-1, keepdims=True)
                       ).astype(attn_ref.dtype)


def _cam_apply_kernel(attn_ref, x_ref, o_ref):
    """Streamed apply: out row block = X_block · Attnᵀ (MXU), the
    attention map resident in VMEM for the whole sweep."""
    x = x_ref[0].astype(jnp.float32)  # (block_n, C)
    attn = attn_ref[0]                # (C, C), f32
    o_ref[0] = jax.lax.dot_general(
        x, attn, _NT, preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _cam_local(x, *, block_n: int, interpret: bool):
    b, n, c = x.shape
    nb = pl.cdiv(n, block_n)
    x = _pad_tokens(x, nb * block_n)
    attn = pl.pallas_call(
        _cam_energy_kernel,
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, block_n, c), lambda b_, j: (b_, j, 0))],
        out_specs=pl.BlockSpec((1, c, c), lambda b_, j: (b_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((c, c), jnp.float32)],
        interpret=interpret,
        name=scopes.CAM_ENERGY,
    )(x)
    out = pl.pallas_call(
        _cam_apply_kernel,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, c, c), lambda b_, j: (b_, 0, 0)),
            pl.BlockSpec((1, block_n, c), lambda b_, j: (b_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n, c), lambda b_, j: (b_, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nb * block_n, c), x.dtype),
        interpret=interpret,
        name=scopes.CAM_APPLY,
    )(attn, x)
    return out[:, :n, :]


def _cam_forward(x, block_n: int, interpret: bool):
    return _on_local_batch(
        functools.partial(_cam_local, block_n=block_n, interpret=interpret),
        x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def flash_channel_attention(x, block_n: int = 256,
                            interpret: bool = False):
    """Fused channel (gram-matrix) attention: same math as
    :func:`ops.attention.channel_attention` — C×C gram of the (B, N, C)
    tokens, max-subtraction softmax, applied back over channels — with
    the gram accumulation and softmax fused into one VMEM-resident
    kernel and the apply streamed.  ``(B, N, C) -> (B, N, C)``."""
    return _cam_forward(x, block_n, interpret)


def _cam_fwd(x, block_n, interpret):
    return _cam_forward(x, block_n, interpret), (x,)


def _cam_bwd(block_n, interpret, res, g):
    (x,) = res
    # Recompute with the jnp reference form and differentiate that — the
    # gram is cheap to rebuild (one (C, C) matmul) vs storing the
    # attention map's softmax residuals.
    with jax.named_scope(scopes.CAM_BWD):
        _, vjp = jax.vjp(channel_attention, x)
        return vjp(g)


flash_channel_attention.defvjp(_cam_fwd, _cam_bwd)
