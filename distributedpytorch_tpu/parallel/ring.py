"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has no sequence dimension at all (fixed 512x512 crops,
train_pascal.py:127; SURVEY.md §2.5 marks SP/CP "ABSENT") — but its
position-attention module is full self-attention over H/8 x W/8 spatial
tokens, the quadratic-memory part of the model.  This module is the TPU-native
scaling path for that attention when token counts outgrow one chip's HBM
(bigger crops, 3D volumes, or any long-sequence head built on these ops):

* the token axis is *sharded over a mesh axis*; each device holds one block
  of Q and one block of K/V;
* each device computes online-softmax attention of its Q block against the
  K/V block it currently holds, then passes that K/V block to its ring
  neighbour with ``jax.lax.ppermute`` — after ``axis_size`` hops every Q
  block has seen every K/V block;
* the carried state is the flash-attention (running-max, running-sum,
  accumulator) triple, so no N x N score matrix ever exists anywhere;
* compute and the ICI transfer overlap: XLA schedules the next hop's
  ``ppermute`` concurrently with the current block's einsum (the
  collective-permute latency hides behind the matmul at realistic sizes).

This is the "ring attention" construction (Liu et al.) expressed with XLA
collectives instead of hand-written RDMA: ``shard_map`` gives per-device
code, ``ppermute`` rides the ICI ring the mesh axis was laid out on
(parallel.mesh builds meshes in ICI-contiguous device order).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS


def _online_block(q, k_blk, v_blk, m, s, acc, scale: float | None):
    """One online-softmax update of (m, s, acc) with a new K/V block.

    ``q``: (B, Nq, Ck); ``k_blk``/``v_blk``: (B, Nb, Ck)/(B, Nb, Cv);
    ``m``/``s``: (B, Nq, 1) running max / normalizer; ``acc``: (B, Nq, Cv).
    Scores accumulate in f32 (bf16-safe), matching ops.attention semantics
    (unscaled DANet energies unless ``scale`` is given).
    """
    scores = jnp.einsum("bnc,bmc->bnm", q, k_blk,
                        preferred_element_type=jnp.float32)
    if scale is not None:
        scores = scores * scale
    m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new)
    s_new = s * corr + p.sum(axis=-1, keepdims=True)
    # P·V accumulates in f32 regardless of input dtype (like
    # blocked_position_attention / the pallas kernel) — in bf16 the per-hop
    # products would drift, and the drift compounds with ring size.
    acc_new = acc * corr + jnp.einsum(
        "bnm,bmc->bnc", p, v_blk.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, s_new, acc_new


def ring_attention_local(q, k, v, axis_name: str = DATA_AXIS,
                         scale: float | None = None):
    """Per-device body: full attention over a token axis sharded on
    ``axis_name``.  Call inside ``shard_map`` (or ``pmap``); use
    :func:`make_ring_attention` for the meshed convenience wrapper.

    ``q``/``k``/``v``: (B, N_local, C*) — the local token block.
    Returns (B, N_local, Cv), bit-matching full softmax attention over the
    global token axis (up to f32 accumulation order).
    """
    n_hops = jax.lax.axis_size(axis_name)
    b, nq, _ = q.shape
    cv = v.shape[-1]
    m0 = jnp.full((b, nq, 1), -jnp.inf, jnp.float32)
    s0 = jnp.zeros((b, nq, 1), jnp.float32)
    acc0 = jnp.zeros((b, nq, cv), jnp.float32)
    perm = [(i, (i + 1) % n_hops) for i in range(n_hops)]

    def hop(carry, _):
        m, s, acc, k_cur, v_cur = carry
        m, s, acc = _online_block(q, k_cur, v_cur, m, s, acc, scale)
        # Pass K/V to the next device on the ring. The last hop's permute is
        # redundant but keeps the loop uniform; XLA overlaps it with the
        # einsum above.
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m, s, acc, k_nxt, v_nxt), None

    (m, s, acc, _, _), _ = jax.lax.scan(
        hop, (m0, s0, acc0, k, v), None, length=n_hops)
    return (acc / jnp.maximum(s, 1e-30)).astype(v.dtype)


def make_ring_attention_inline(mesh: Mesh, axis_name: str = DATA_AXIS,
                               scale: float | None = None,
                               batch_axis: str | None = None):
    """Unjitted shard_map ring attention, for embedding inside a larger
    traced program (e.g. the DANet head's ``pam_impl='ring'`` path).

    ``batch_axis`` optionally shards the leading batch dim over a second
    mesh axis (the flagship's ``data`` axis); token axis rides
    ``axis_name``.
    """
    spec = P(batch_axis, axis_name, None)
    return jax.shard_map(
        functools.partial(ring_attention_local, axis_name=axis_name,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )


def make_ring_attention(mesh: Mesh, axis_name: str = DATA_AXIS,
                        scale: float | None = None):
    """Jitted ``(q, k, v) -> out`` with the token axis sharded over
    ``axis_name`` of ``mesh``; batch/feature axes replicated.

    The returned function accepts *global* (B, N, C) arrays and computes
    exact attention while each device only ever materializes its
    N/axis_size token slice of K/V — the long-context configuration.
    """
    fn = make_ring_attention_inline(mesh, axis_name, scale)
    sharding = NamedSharding(mesh, P(None, axis_name, None))
    return jax.jit(fn, in_shardings=(sharding,) * 3,
                   out_shardings=sharding)
