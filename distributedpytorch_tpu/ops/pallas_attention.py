"""Pallas TPU kernels for BOTH DANet attention branches — the hot path.

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
* :func:`flash_channel_attention` — the gram branch: one kernel streams
  the (N, C) tokens through VMEM in row blocks, accumulates the C×C
  gram on the MXU in VMEM scratch and finishes with DANet's
  max-subtraction softmax on the VPU *in the same kernel* (the energy
  matrix never round-trips HBM between the einsum and the softmax);
  a second streamed kernel applies the attention back over channels.
  Only the C×C attention map (≤1 MB at C=512) crosses HBM between the
  two.

Backward for both: a ``jax.custom_vjp`` whose reverse pass recomputes
with the O(N·block) / jnp reference form and differentiates that —
recompute-not-store, the standard flash trade.

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import scopes
from .attention import blocked_position_attention, channel_attention

_NEG_INF = -1e30


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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, s_ref, acc_ref,
                  *, n_real: int, block_k: int, scale: float | None):
    """One (q-block, k-block) tile of online-softmax attention."""
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        s_ref[:] = jnp.zeros_like(s_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0]          # (bq, ck)
    k = k_ref[0]          # (bk, ck)
    v = v_ref[0]          # (bk, cv)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (bq, bk)
    if scale is not None:
        scores = scores * scale
    # Mask keys past the true token count (N was padded to a block multiple).
    key_idx = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)
    scores = jnp.where(key_idx < n_real, scores, _NEG_INF)

    m_prev = m_ref[:, :1]                            # (bq, 1)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                      # (bq, bk)
    s_new = s_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    s_ref[:] = jnp.broadcast_to(s_new, s_ref.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(s_ref[:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def _flash_local(q, k, v, *, block_q: int, block_k: int,
                 scale: float | None, interpret: bool):
    b, n, ck = q.shape
    cv = v.shape[-1]
    nq = pl.cdiv(n, block_q)
    nk = pl.cdiv(n, block_k)
    pad_q = nq * block_q - n
    pad_k = nk * block_k - n
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(_flash_kernel, n_real=n, block_k=block_k,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, ck), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_k, ck), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((1, block_k, cv), lambda b_, i, j: (b_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, cv), lambda b_, i, j: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq * block_q, cv), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
            pltpu.VMEM((block_q, cv), jnp.float32),    # accumulator
        ],
        interpret=interpret,
        # the call's name is its innermost scope, and the TPU compiler names
        # the custom-call after that: the trace shows ``%pam`` whatever
        # encloses the call (a module, a shard_map)
        name=scopes.PAM_KERNEL,
    )(q, k, v)
    return out[:, :n, :]


def _flash_forward(q, k, v, block_q: int, block_k: int,
                   scale: float | None, interpret: bool):
    return _on_local_batch(
        functools.partial(_flash_local, block_q=block_q, block_k=block_k,
                          scale=scale, interpret=interpret), q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_position_attention(q, k, v, block_q: int = 256, block_k: int = 256,
                             scale: float | None = None,
                             interpret: bool = False):
    """Flash position attention: same math as
    :func:`ops.attention.position_attention` (unscaled DANet energies unless
    ``scale``), O(N·block) memory, MXU-scheduled.

    ``q``/``k``: (B, N, Ck); ``v``: (B, N, Cv) -> (B, N, Cv).
    """
    return _flash_forward(q, k, v, block_q, block_k, scale, interpret)


def _fwd(q, k, v, block_q, block_k, scale, interpret):
    out = _flash_forward(q, k, v, block_q, block_k, scale, interpret)
    return out, (q, k, v)


def _bwd(block_q, block_k, scale, interpret, res, g):
    q, k, v = res
    # Recompute with the O(N*block) jnp form and differentiate that — the
    # flash backward without a second hand-written kernel.
    def ref(q_, k_, v_):
        if scale is not None:  # score scaling == scaling q
            q_ = q_ * scale
        return blocked_position_attention(q_, k_, v_, block_size=block_k)
    with jax.named_scope(scopes.PAM_BWD):
        _, vjp = jax.vjp(ref, q, k, v)
        return vjp(g)


flash_position_attention.defvjp(_fwd, _bwd)


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
        x, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (C, C)

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
        x, attn, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _cam_local(x, *, block_n: int, interpret: bool):
    b, n, c = x.shape
    nb = pl.cdiv(n, block_n)
    pad = nb * block_n - n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
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
