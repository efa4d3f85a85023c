"""Ulysses-style all-to-all sequence parallelism.

The second of the two standard long-context constructions (the first, ring
attention, lives in :mod:`parallel.ring`).  The reference has no sequence
dimension (fixed 512x512 crops, train_pascal.py:127; SURVEY.md §2.5 marks
SP/CP "ABSENT"), but long-context support is first-class in this framework,
and the two schemes trade off differently on TPU:

* **ring** keeps tokens resident and cycles K/V blocks around the ICI ring —
  communication grows with ``axis_size`` hops of the K/V block, compute
  overlaps transfer, works for any head count (even 1, like DANet's PAM);
* **ulysses** (DeepSpeed-Ulysses) re-shards *once*: an ``all_to_all`` swaps
  the token sharding for a head sharding, each device then runs ordinary
  full attention over ALL tokens for its subset of heads, and a second
  ``all_to_all`` swaps back.  Two collectives total regardless of axis size,
  but the head count must be divisible by the axis size.

Per-device code via ``shard_map``; the exchanges are ``jax.lax.all_to_all``
(tiled), which XLA lowers to the native ICI all-to-all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS


def _heads_attention(q, k, v, scale: float | None):
    """Full attention with explicit heads: (B, N, H, D) -> (B, N, H, Dv).

    Scores/normalization accumulate in f32 (bf16-safe), matching
    ops.attention semantics — unscaled energies unless ``scale`` is given
    (the DANet PAM convention; pass ``1/sqrt(D)`` for transformer-style).
    """
    scores = jnp.einsum("bnhd,bmhd->bhnm", q, k,
                        preferred_element_type=jnp.float32)
    if scale is not None:
        scores = scores * scale
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhnm,bmhd->bnhd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def ulysses_attention_local(q, k, v, axis_name: str = DATA_AXIS,
                            scale: float | None = None):
    """Per-device body: exact multi-head attention over a token axis sharded
    on ``axis_name``.  Call inside ``shard_map``; use
    :func:`make_ulysses_attention` for the meshed wrapper.

    ``q``/``k``/``v``: (B, N_local, H, D*) — the local token block, all
    heads.  H must be divisible by the axis size.  Returns
    (B, N_local, H, Dv), bit-matching full attention over the global token
    axis (up to f32 accumulation order).
    """
    ax = jax.lax.axis_size(axis_name)
    h = q.shape[2]
    if h % ax:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by axis size ({ax}); "
            "use ring attention for indivisible/single-head cases")

    def seq_to_heads(x):  # (B, N/ax, H, D) -> (B, N, H/ax, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):  # (B, N, H/ax, D) -> (B, N/ax, H, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    out = _heads_attention(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v),
                           scale)
    return heads_to_seq(out)


def make_ulysses_attention(mesh: Mesh, axis_name: str = DATA_AXIS,
                           scale: float | None = None):
    """Jitted ``(q, k, v) -> out`` over global (B, N, H, D) arrays with the
    token axis sharded on ``axis_name`` of ``mesh`` — the all-to-all
    long-context configuration (two ICI collectives per call)."""
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(ulysses_attention_local, axis_name=axis_name,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    sharding = NamedSharding(mesh, spec)
    return jax.jit(fn, in_shardings=(sharding,) * 3,
                   out_shardings=sharding)
