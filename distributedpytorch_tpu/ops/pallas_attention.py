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
  ``scale`` 1/√head_dim; ``causal`` — a query sees the keys at or before
  it, a tile wholly above the diagonal is neither computed (``pl.when``)
  nor fetched (its block index stays on the last tile that ran), and only
  a tile that crosses the diagonal pays for the mask; ``group`` — heads lie
  on the grid's first axis beside the batch, and the query heads of a group
  read their one key/value head through the index map (nothing is repeated
  in HBM; the reverse pass writes one float32 dK, dV per query head, summed
  over the group in XLA).  What differs from DANet's calls is static at
  trace time; with ``causal=False`` and ``group=1`` the kernels' Mosaic
  modules are DANet's, op for op.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs,
                  n_real: int, block_k: int, scale: float | None,
                  causal: bool = False):
    """One (q-block, k-block) tile of online-softmax attention.  ``refs``:
    the running (max, sum, accumulator) scratch, after the log-sum-exp
    output where the call was built with one (the differentiated forward).
    ``causal``: a query sees the keys at or before its own position; a tile
    wholly above the diagonal is not computed, and only a tile that crosses
    it pays for the mask."""
    *lse_ref, m_ref, s_ref, acc_ref = refs
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        s_ref[:] = jnp.zeros_like(s_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile(q_lo=None):
        q = q_ref[0]          # (bq, ck)
        k = k_ref[0]          # (bk, ck)
        v = v_ref[0]          # (bk, cv)
        scores = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32)   # (bq, bk)
        if scale is not None:
            scores = scores * scale
        key_idx = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        if not causal:
            # Mask keys past the true token count (N was padded to a block
            # multiple).
            scores = jnp.where(key_idx < n_real, scores, _NEG_INF)
        elif q_lo is not None:
            # the tile crosses the diagonal.  Padded keys lie past every
            # real query, so this mask is theirs too
            query_idx = q_lo + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 0)
            scores = jnp.where(key_idx <= query_idx, scores, _NEG_INF)

        m_prev = m_ref[:, :1]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                      # (bq, bk)
        s_new = s_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        s_ref[:] = jnp.broadcast_to(s_new, s_ref.shape)

    if causal:
        block_q = q_ref.shape[1]
        q_lo = pl.program_id(1) * block_q
        k_lo = j * block_k
        crosses = k_lo + block_k - 1 > q_lo
        pl.when(jnp.logical_and(k_lo < q_lo + block_q, crosses))(
            functools.partial(tile, q_lo))
        pl.when(jnp.logical_not(crosses))(tile)
    else:
        tile()

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(s_ref[:, :1], 1e-30)
                    ).astype(o_ref.dtype)
        if lse_ref:  # every lane of a row holds the row's value
            lse_ref[0][0] = m_ref[:] + jnp.log(s_ref[:])


def _pad_tokens(x, n_padded: int):
    """Zero-pad the token dim (1) of a (B, N, ...) array to ``n_padded``."""
    pad = n_padded - x.shape[1]
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def _flash_local(q, k, v, *, block_q: int, block_k: int,
                 scale: float | None, interpret: bool, with_lse: bool,
                 causal: bool = False, group: int = 1):
    b, n, ck = q.shape
    cv = v.shape[-1]
    nq = pl.cdiv(n, block_q)
    nk = pl.cdiv(n, block_k)
    q = _pad_tokens(q, nq * block_q)
    k = _pad_tokens(k, nk * block_k)
    v = _pad_tokens(v, nk * block_k)

    kernel = functools.partial(_flash_kernel, n_real=n, block_k=block_k,
                               scale=scale, causal=causal)
    # a causal call's tiles (``_CAUSAL_TILE``) pass Mosaic's default scope
    extra = dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_BWD_VMEM_LIMIT)) if causal else {}

    def kv_map(b_, i, j):
        if group > 1:  # row ``b_`` is a query head of key/value head:
            b_ = b_ // group
        if causal:
            # past the query block's last tile the index stays where it is,
            # so a tile that is stepped over starts no copy
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        return (b_, j, 0)

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
        in_specs=[
            pl.BlockSpec((1, block_q, ck), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_k, ck), kv_map),
            pl.BlockSpec((1, block_k, cv), kv_map),
        ],
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
        name=scopes.CAUSAL_ATTN if causal else scopes.PAM_KERNEL,
        **extra,
    )(q, k, v)
    out = res[0][:, :n, :]
    if with_lse:
        return out, res[1][:, :n, 0]
    return out


def _flash_forward(q, k, v, block_q: int, block_k: int,
                   scale: float | None, interpret: bool,
                   with_lse: bool = False, causal: bool = False,
                   group: int = 1):
    return _on_local_batch(
        functools.partial(_flash_local, block_q=block_q, block_k=block_k,
                          scale=scale, interpret=interpret,
                          with_lse=with_lse, causal=causal, group=group),
        q, k, v)


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


def _bwd_tile(q, k, v, do, lse, delta, *, key_block, n_real: int,
              scale: float | None, diagonal: bool | None = None):
    """``(Pᵀ, dSᵀ)`` of one tile, keys on sublanes and queries on lanes —
    the orientation in which ``lse`` and ``delta`` (per query) are lane-
    dense rows and dV, dK need no transpose.  float32 throughout.
    ``diagonal``: of a causal call, whether the tile's key block is its
    query block, where a key counts for the queries at or after it."""
    st = jax.lax.dot_general(k, q, _NT,
                             preferred_element_type=jnp.float32)  # (bk, bq)
    if scale is not None:
        st = st * scale
    pt = jnp.exp(st - lse)
    block = k.shape[0]
    if diagonal:  # padded keys lie past every real query: masked here too
        pt = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, pt.shape, 0)
            <= jax.lax.broadcasted_iota(jnp.int32, pt.shape, 1), pt, 0.0)
    elif diagonal is None and n_real % block:
        # keys past the true token count (zero-padded)
        key_idx = key_block * block + jax.lax.broadcasted_iota(
            jnp.int32, pt.shape, 0)
        pt = jnp.where(key_idx < n_real, pt, 0.0)
    dpt = jax.lax.dot_general(v, do, _NT,
                              preferred_element_type=jnp.float32)
    dst = pt * (dpt - delta)
    if scale is not None:
        dst = dst * scale
    return pt, dst


def _causal_tiles(tile, key_block, query_block):
    """Run ``tile(diagonal)`` where a causal call has work: below the
    diagonal as it is, on it masked, above it not at all."""
    pl.when(query_block > key_block)(functools.partial(tile, False))
    pl.when(query_block == key_block)(functools.partial(tile, True))


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *refs, n_real: int,
                    scale: float | None, causal: bool = False):
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

    def tile(diagonal=None):
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        pt, dst = _bwd_tile(q, k, v_ref[0], do, lse_ref[0], delta_ref[0],
                            key_block=j, n_real=n_real, scale=scale,
                            diagonal=diagonal)
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

            # causal: key block 0 counts for every query block, so each
            # row of dQ is still written before it is added to
            @pl.when(j == 0)
            def _first():
                dq_ref[0][0, rows, :] = dq

            @pl.when(j > 0)
            def _add():
                dq_ref[0][0, rows, :] += dq

    if causal:
        _causal_tiles(tile, j, i)
    else:
        tile()

    @pl.when(i == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, n_real: int, scale: float | None,
                   causal: bool = False):
    """The query-block sweep of the two-sweep schedule, keys innermost."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def tile(diagonal=None):
        k = k_ref[0]
        _, dst = _bwd_tile(q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0],
                           delta_ref[0], key_block=j, n_real=n_real,
                           scale=scale, diagonal=diagonal)
        dq_acc[:] += jax.lax.dot_general(dst.astype(k.dtype), k, _TN,
                                         preferred_element_type=jnp.float32)

    if causal:
        _causal_tiles(tile, j, pl.program_id(1))
    else:
        tile()

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_backward_local(q, k, v, out, lse, do, *, scale: float | None,
                          interpret: bool, causal: bool = False,
                          group: int = 1):
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

    ``causal`` (the calls are then named ``causal_attn_bwd_…``): tiles above
    the diagonal are stepped over in both schedules, and the inner axis'
    block index stays on the diagonal's while they are, so they copy
    nothing.  ``group`` > 1: a row of ``q`` is a query head and ``group`` of
    them read one row of ``k``, ``v``; each writes its own float32 dK, dV,
    summed over the group here."""
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

    static = dict(n_real=n, scale=scale, causal=causal)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_BWD_VMEM_LIMIT)
    names = (scopes.CAUSAL_ATTN_BWD_FUSED, scopes.CAUSAL_ATTN_BWD_DKV,
             scopes.CAUSAL_ATTN_BWD_DQ) if causal else (
        scopes.PAM_BWD_FUSED, scopes.PAM_BWD_DKV, scopes.PAM_BWD_DQ)

    def specs(at_q, at_k):
        """In-specs of (q, k, v, dO, lse, δ); ``at_q`` / ``at_k``: which
        grid axis walks the query blocks / the key blocks."""
        def block_at(axis):
            if not causal or axis != 2:
                return lambda g: g[axis]
            # the inner axis does not leave the tiles that run: the queries'
            # index not below the key block's, the keys' not above the
            # query block's
            bound = jnp.maximum if axis == at_q else jnp.minimum
            return lambda g: bound(g[2], g[1])
        qi, ki = block_at(at_q), block_at(at_k)
        kv_row = (lambda g: g[0] // group) if group > 1 else (lambda g: g[0])

        def tokens(c, row, at):
            return pl.BlockSpec((1, block, c),
                                lambda *g: (row(g), at(g), 0))
        q_row = lambda g: g[0]
        row = pl.BlockSpec((1, 1, block), lambda *g: (g[0], 0, qi(g)))
        return [tokens(ck, q_row, qi), tokens(ck, kv_row, ki),
                tokens(cv, kv_row, ki), tokens(cv, q_row, qi), row, row]

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
        functools.partial(_bwd_dkv_kernel, **static),
        grid=(b, nb, nb),
        in_specs=dkv_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block, ck), jnp.float32),
                        pltpu.VMEM((block, cv), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name=names[0] if fused else names[1],
    )(q, k, v, do, lse, delta)
    dk, dv = res[0], res[1]
    if group > 1:
        dk, dv = (x.reshape((b // group, group) + x.shape[1:]).sum(1)
                  .astype(like.dtype) for x, like in ((dk, k), (dv, v)))
    if fused:
        dq = res[2].astype(q.dtype)
    else:
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, **static),
            grid=(b, nb, nb),
            in_specs=specs(1, 2),
            out_specs=pl.BlockSpec((1, block, ck),
                                   lambda b_, i, j: (b_, i, 0)),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((block, ck), jnp.float32)],
            compiler_params=params,
            interpret=interpret,
            name=names[2],
        )(q, k, v, do, lse, delta)
    return dq[:, :n], dk[:, :n], dv[:, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_position_attention(q, k, v, block_q: int = 256, block_k: int = 256,
                             scale: float | None = None,
                             interpret: bool = False, causal: bool = False,
                             group: int = 1):
    """Flash position attention: same math as
    :func:`ops.attention.position_attention` (unscaled DANet energies unless
    ``scale``), O(N·block) memory, MXU-scheduled.  ``block_q`` / ``block_k``
    tile the forward; the reverse pass sizes its own tiles from the shapes.

    ``q``/``k``: (B, N, Ck); ``v``: (B, N, Cv) -> (B, N, Cv).  ``causal``:
    row ``t`` attends to rows ``<= t``.  ``group``: ``q`` has ``group``
    times the rows of ``k`` and ``v``, and rows ``g·group … g·group + group
    − 1`` of it read row ``g`` of theirs (grouped-query heads laid on the
    batch axis: :func:`flash_causal_attention`).
    """
    return _flash_forward(q, k, v, block_q, block_k, scale, interpret,
                          causal=causal, group=group)


#: ``checkpoint_name``s of what the causal reverse pass keeps of its forward
#: call: a block that recomputes itself under a policy that saves these
#: names (8 MB + 131 KB at the token cell's shape) runs no second forward
#: call.  A policy that names nothing (``nn.remat``'s default) is unmoved
KEPT_BY_REVERSE = ("causal_attn_out", "causal_attn_lse")


def _fwd(q, k, v, block_q, block_k, scale, interpret, causal, group):
    out, lse = _flash_forward(q, k, v, block_q, block_k, scale, interpret,
                              with_lse=True, causal=causal, group=group)
    if causal:
        out, lse = map(checkpoint_name, (out, lse), KEPT_BY_REVERSE)
    return out, (q, k, v, out, lse)


def _bwd(block_q, block_k, scale, interpret, causal, group, res, g):
    # the flash backward as Mosaic calls: no recompute of the forward's
    # recurrence, no N×N array in HBM (see _flash_backward_local)
    with jax.named_scope(scopes.CAUSAL_ATTN_BWD if causal
                         else scopes.PAM_BWD):
        return _on_local_batch(
            functools.partial(_flash_backward_local, scale=scale,
                              interpret=interpret, causal=causal,
                              group=group), *res, g)


flash_position_attention.defvjp(_fwd, _bwd)


#: (queries, keys) of a causal forward tile.  On the v5e at 8,192 tokens, 4
#: query heads of 128 (PERF.md, PR 34): 256 / 512 / 1,024 a side take 2.65 /
#: 1.29 / 0.68 ms, the last 57% of the MXU's peak for the tiles it runs
_CAUSAL_TILE = (1024, 1024)


def flash_causal_attention(q, k, v, interpret: bool = False):
    """:func:`ops.attention.causal_attention` through the flash kernels,
    forward and reverse: no (S, S) array of scores, probabilities or their
    gradients reaches HBM, and the half of the tiles above the diagonal is
    neither computed nor fetched.  Softmax statistics in float32, MXU
    operands in the inputs' dtype.

    ``q``: (B, S, Hq, D); ``k``, ``v``: (B, S, Hkv, D) with ``Hq`` a
    multiple of ``Hkv``: query heads ``g·r … g·r + r − 1`` read key/value
    head ``g`` through the kernels' index maps, nothing is repeated in HBM.
    Returns (B, S, Hq, D)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if hq % hkv:
        raise ValueError(f"{hq} query heads do not share {hkv} key/value "
                         f"heads evenly")

    def heads_first(x):   # heads beside the batch on the grid's first axis
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], s, d)

    block_q, block_k = (min(t, 128 * pl.cdiv(s, 128)) for t in _CAUSAL_TILE)
    out = flash_position_attention(
        heads_first(q), heads_first(k), heads_first(v), block_q, block_k,
        1 / math.sqrt(d), interpret=interpret, causal=True, group=hq // hkv)
    return out.reshape(b, hq, s, d).transpose(0, 2, 1, 3)


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
