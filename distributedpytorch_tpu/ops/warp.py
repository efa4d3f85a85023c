"""Device-side ragged resizes as separable weight matmuls.

The full-res semantic val protocol (reference train_pascal.py:280-306
generalized to multi-class — metric at ORIGINAL resolution) needs every
sample's crop-space class probabilities resized to that sample's own
native size.  Done on the host that is ragged per-image work: one
21-channel cv2 resize per image, after reading a 22 MB probability volume
back from the device (1.5 imgs/s on a 1-core host, 2026-08 session).

TPU-native formulation: bilinear resize to a *per-sample* target size is
a pair of matmuls with weight matrices built from compares over a static
padded grid — ``W_h[o, i] = tent(src_center(o) - i)`` — so one jitted,
vmapped program handles every native size up to ``val_max_im_size`` with
static shapes, no gathers (the r4 lesson: XLA lowers gathers to ~1.6
GiB/s scalar loops on TPU, ``prof_deeplab_b8.json``), and MXU-friendly
contractions.  Only the argmax CLASS MAP (uint8, 21x fewer bytes than
the bf16 probability volume) crosses the wire; the host slices each
sample's valid region and bincounts the confusion matrix.

Weight semantics pin cv2.INTER_LINEAR (the imaging backend the host path
uses, ``imaging.resize``): half-pixel centers ``src = (dst + 0.5) *
(in / out) - 0.5`` clamped to the valid range (edge replicate), a plain
tent — cv2 applies no antialias prefilter for INTER_LINEAR in either
direction, so the same weights hold for the protocol's slight downscales
(513² crop -> ≤500² native) as for upscales.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _linear_weight_matrix(out_size: jax.Array, n_out: int,
                          in_size: int) -> jax.Array:
    """(n_out, in_size) bilinear weights for a traced per-sample target.

    Rows at or beyond ``out_size`` are zeroed; callers mask/slice them.
    Built from iota compares only — no gather, no dynamic shape.
    """
    out_size = jnp.asarray(out_size, jnp.float32)
    o = jnp.arange(n_out, dtype=jnp.float32)
    src = (o + 0.5) * (jnp.float32(in_size) / out_size) - 0.5
    src = jnp.clip(src, 0.0, jnp.float32(in_size - 1))
    lo = jnp.floor(src)
    frac = src - lo
    i = jnp.arange(in_size, dtype=jnp.float32)
    is_lo = i[None, :] == lo[:, None]
    is_hi = i[None, :] == (lo[:, None] + 1.0)
    w = is_lo * (1.0 - frac[:, None]) + is_hi * frac[:, None]
    return jnp.where(o[:, None] < out_size, w, 0.0)


def resize_bilinear_ragged(x: jax.Array, out_hw: jax.Array,
                           max_hw: tuple[int, int]) -> jax.Array:
    """Per-sample bilinear resize of ``x`` (B, H, W, C) to each sample's
    ``out_hw[b] = (h_b, w_b)`` inside a static (B, max_h, max_w, C) canvas.

    Rows/cols beyond a sample's own size are zero.  f32 arithmetic
    matching the host path (which widens to f32 before cv2).
    """
    max_h, max_w = int(max_hw[0]), int(max_hw[1])
    in_h, in_w = x.shape[1], x.shape[2]

    def one(xi, hw):
        wh = _linear_weight_matrix(hw[0], max_h, in_h)
        ww = _linear_weight_matrix(hw[1], max_w, in_w)
        y = jnp.einsum("oi,iwc->owc", wh, xi.astype(jnp.float32))
        return jnp.einsum("pj,ojc->opc", ww, y)

    return jax.vmap(one)(x, out_hw)


@functools.partial(jax.jit, static_argnums=(2,))
def fullres_argmax(probs: jax.Array, out_hw: jax.Array,
                   max_hw: tuple[int, int]) -> jax.Array:
    """Device half of the full-res semantic protocol: resize class
    probabilities (B, H, W, C) to each sample's native size and argmax.

    Returns (B, max_h, max_w) uint8 class ids — the only array that
    crosses the wire; callers slice ``[:h_b, :w_b]`` per sample before
    scoring (out-of-range pixels are argmax-of-zeros and must not be
    scored).
    """
    if probs.shape[-1] > 256:
        raise ValueError(
            f"{probs.shape[-1]} classes do not fit the uint8 class-map "
            "wire; use resize_bilinear_ragged + argmax directly")
    full = resize_bilinear_ragged(probs, out_hw, max_hw)
    return jnp.argmax(full, axis=-1).astype(jnp.uint8)
