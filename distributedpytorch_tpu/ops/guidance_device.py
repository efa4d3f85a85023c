"""On-device guidance synthesis — the 4th input channel, inside the step.

The reference synthesizes its guidance channel (extreme points -> n-ellipse +
gaussian heatmap, custom_transforms.py:30-51 via the never-committed
``dataloaders.nellipse``) per sample on the host CPU.  That is the single most
expensive host transform in the pipeline: rasterizing two 512x512 maps per sample dominates the per-sample
augmentation budget even with the native C++ kernels.

On TPU the same math is a handful of fused elementwise ops over a static
512x512 grid — effectively free next to the forward pass.  This module is the
jittable twin of :mod:`..data.guidance`:

* :func:`extreme_points_random` / :func:`extreme_points_fixed` — the 4
  extreme pixels of a binary mask, random-tie vs deterministic-median
  selection, matching the host contracts (``data/guidance.py:56,72``);
* :func:`guidance_map` — one (H, W) guidance channel from a mask, any of the
  three point-based families (``nellipse_gaussians`` — the live channel —
  ``nellipse``, ``extreme_points``), numerically matching the host maps;
* :func:`make_device_guidance` — the ``(batch, rng) -> batch`` stage for
  ``ops.augment.make_device_augment(guidance_fn=...)``: computes the channel
  from ``crop_gt`` AFTER the device geometric augmentations (the reference's
  stage order: geometry happens before guidance, train_pascal.py:123-134) and
  appends it to ``concat``.

Randomness note: the live path samples extreme points with ``pert=0`` — the
jitter is the uniform choice among each side's tied extreme pixels.  The host
picks a uniform index into the candidate list; here the same distribution is
realized as an argmax over iid uniforms (a different RNG stream, identical
law).  The deterministic (val) variant is bit-exact vs the host at ``pert=0``,
where each side's candidates have unique sort keys.

The confidence-map families (``confidence_l1l2``/``confidence_gaussian``,
the reference's inactive alternative at custom_transforms.py:253-298) are
covered too: mask moments are masked sums over the static grid and the 2x2
covariance/axes inverses are closed-form — no linear-algebra escape hatch
needed.
"""

from __future__ import annotations

from typing import Callable, Mapping

import jax
import jax.numpy as jnp

Batch = Mapping[str, jax.Array]

#: families this module can synthesize on device
FAMILIES = ("nellipse_gaussians", "nellipse", "extreme_points",
            "confidence_l1l2", "confidence_gaussian")

# Plain python int, NOT jnp.int32(...): a module-level jnp call executes a
# primitive at import time, which initializes the default backend — and on
# a TPU host that takes the chip: a launcher that only imports the package
# (a supervisor, a fleet manager) would hold it against its own children.
# Inside the jitted functions the weak int promotes to int32 as before.
_BIG = 1 << 30


def _side_candidates(mask: jax.Array, pert: int):
    """Boolean candidate maps for (left, top, right, bottom) — foreground
    pixels within ``pert`` px of each side's extreme coordinate (the host
    ``_extreme_point_candidates`` contract, data/guidance.py:41)."""
    fg = mask > 0.5
    h, w = mask.shape
    x = jnp.arange(w, dtype=jnp.int32)[None, :]
    y = jnp.arange(h, dtype=jnp.int32)[:, None]
    xmin = jnp.min(jnp.where(fg, x, _BIG))
    ymin = jnp.min(jnp.where(fg, y, _BIG))
    xmax = jnp.max(jnp.where(fg, x, -1))
    ymax = jnp.max(jnp.where(fg, y, -1))
    return (
        fg & (jnp.abs(x - xmin) <= pert),
        fg & (jnp.abs(y - ymin) <= pert),
        fg & (jnp.abs(x - xmax) <= pert),
        fg & (jnp.abs(y - ymax) <= pert),
    )


def extreme_points_random(mask: jax.Array, rng: jax.Array,
                          pert: int = 0) -> jax.Array:
    """Randomized 4 extreme points of ``mask`` as a (4, 2) float32 (x, y)
    array — uniform over each side's candidate set, the training-time jitter
    of the host ``extreme_points`` (data/guidance.py:56).

    Selection is the host's own ``k = integers(0, n_candidates)`` realized
    as a cumsum rank-pick — 4 random ints per sample, not a random field
    per side (threefry over the full grid would cost more than the map
    rasterization itself).

    Undefined (but finite) for an empty mask; callers zero the resulting map.
    """
    h, w = mask.shape
    cands = jnp.stack([c.ravel()
                       for c in _side_candidates(mask, pert)])  # (4, H*W)
    counts = cands.sum(axis=1)
    ks = jax.random.randint(rng, (4,), 0, jnp.maximum(counts, 1))
    # the first flat index whose candidate-cumsum reaches k+1 IS the k-th
    # candidate in row-major order
    csum = jnp.cumsum(cands, axis=1)
    idx = jnp.argmax(csum == (ks + 1)[:, None], axis=1)
    return jnp.stack([idx % w, idx // w], axis=1).astype(jnp.float32)


def extreme_points_fixed(mask: jax.Array, pert: int = 0) -> jax.Array:
    """Deterministic 4 extreme points — per side, the candidate of median
    rank when ordered by the non-extreme coordinate (the host
    ``extreme_points_fixed`` contract, data/guidance.py:72; ties — possible
    only at ``pert > 0`` — break by row-major position where the host's
    unstable sort is unspecified).  (4, 2) float32 (x, y)."""
    h, w = mask.shape
    x = jnp.arange(w, dtype=jnp.int32)[None, :]
    y = jnp.arange(h, dtype=jnp.int32)[:, None]
    # sort keys: (other coordinate, tie-break) packed into one int32
    key_lr = y * w + x          # left/right sides: other = y -> (y, x) order
    key_tb = x * h + y          # top/bottom sides: other = x -> (x, y) order
    pts = []
    for i, cand in enumerate(_side_candidates(mask, pert)):
        keys = jnp.where(cand, key_lr if i in (0, 2) else key_tb, _BIG)
        sel = jnp.sort(keys.ravel())[jnp.sum(cand) // 2]
        if i in (0, 2):
            pts.append((sel % w, sel // w))
        else:
            pts.append((sel // h, sel % h))
    return jnp.stack([jnp.stack(p) for p in pts]).astype(jnp.float32)


def _nellipse_z(shape_hw, pts: jax.Array, softness: float) -> jax.Array:
    """Soft n-ellipse indicator in [0, 1] — jittable twin of the host
    ``compute_nellipse`` (data/guidance.py:99): boundary at the multifocal
    level set through the outermost focal point, sigmoid falloff of relative
    width ``softness``, exponent clipped to +-50."""
    h, w = shape_hw
    xx = jnp.arange(w, dtype=jnp.float32)[None, :]
    yy = jnp.arange(h, dtype=jnp.float32)[:, None]
    px = pts[:, 0][:, None, None]
    py = pts[:, 1][:, None, None]
    d = jnp.sqrt((xx - px) ** 2 + (yy - py) ** 2).sum(axis=0)
    pair = jnp.sqrt(((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1))
    c = pair.sum(axis=1).max()
    tau = jnp.where(c > 0, softness * c, 1.0)
    z = 1.0 / (1.0 + jnp.exp(jnp.clip((d - c) / tau, -50.0, 50.0)))
    return jnp.where(c > 0, z, (d == 0).astype(jnp.float32))


def _gaussian_hm(shape_hw, pts: jax.Array, sigma: float) -> jax.Array:
    """Max-combined gaussian bumps at ``pts`` in [0, 1] — twin of the host
    ``make_gt`` (utils/helpers.py:252: exp(-4 ln2 r^2 / sigma^2))."""
    h, w = shape_hw
    xx = jnp.arange(w, dtype=jnp.float32)[None, :]
    yy = jnp.arange(h, dtype=jnp.float32)[:, None]
    px = pts[:, 0][:, None, None]
    py = pts[:, 1][:, None, None]
    r2 = (xx - px) ** 2 + (yy - py) ** 2
    return jnp.exp(-4.0 * jnp.log(2.0) * r2 / sigma**2).max(axis=0)


def _inv2x2(m: jax.Array) -> jax.Array:
    """Closed-form inverse of a 2x2 matrix."""
    a, b, c, d = m[0, 0], m[0, 1], m[1, 0], m[1, 1]
    det = a * d - b * c
    return jnp.array([[d, -b], [-c, a]]) / det


def _minmax_255(z: jax.Array) -> jax.Array:
    """Min-max normalize to [0, 1] then x255 — the host
    ``normalize_wt_map(.)*255`` rule (transforms.AddConfidenceMap)."""
    lo, hi = z.min(), z.max()
    return (z - lo) / (hi - lo + 1e-10) * 255.0


def _l1l2_map(shape_hw, pts: jax.Array, tau: float) -> jax.Array:
    """Skewed-axes L1+L2 confidence map — twin of the host
    ``generate_mv_l1l2_image_skewed_axes`` (data/guidance.py:248): affine
    (u, v) coordinates along the left->right / top->bottom chords, weight
    ``exp(-tau * (|u|+|v| + sqrt(u^2+v^2)) / 2)``."""
    h, w = shape_hw
    left, top, right, bottom = pts[0], pts[1], pts[2], pts[3]
    center = pts.mean(axis=0)
    a1 = (right - left) / 2.0
    a2 = (bottom - top) / 2.0
    A = jnp.stack([a1, a2], axis=1)  # columns are the axes
    A = jnp.where(jnp.abs(A[0, 0] * A[1, 1] - A[0, 1] * A[1, 0]) < 1e-6,
                  A + jnp.eye(2) * 1e-3, A)
    Ainv = _inv2x2(A)
    xx = jnp.arange(w, dtype=jnp.float32)[None, :]
    yy = jnp.arange(h, dtype=jnp.float32)[:, None]
    dx = xx - center[0]
    dy = yy - center[1]
    u = Ainv[0, 0] * dx + Ainv[0, 1] * dy
    v = Ainv[1, 0] * dx + Ainv[1, 1] * dy
    l1 = jnp.abs(u) + jnp.abs(v)
    l2 = jnp.sqrt(u * u + v * v)
    return jnp.exp(-tau * (l1 + l2) / 2.0)


def _mvgauss_map(mask: jax.Array, tau: float) -> jax.Array:
    """Multivariate-gaussian confidence map from the mask's pixel-cloud
    moments — twin of the host ``generate_mvgauss_image``
    (data/guidance.py:218).  Moments are masked sums over the static grid;
    covariance is the sample (ddof=1) covariance + 1e-3*I, isotropic unit
    for sub-2-pixel masks."""
    h, w = mask.shape
    fg = (mask > 0.5).astype(jnp.float32)
    n = fg.sum()
    xx = jnp.arange(w, dtype=jnp.float32)[None, :] * jnp.ones((h, 1))
    yy = jnp.arange(h, dtype=jnp.float32)[:, None] * jnp.ones((1, w))
    n_safe = jnp.maximum(n, 1.0)
    mx = (fg * xx).sum() / n_safe
    my = (fg * yy).sum() / n_safe
    dof = jnp.maximum(n - 1.0, 1.0)
    sxx = (fg * (xx - mx) ** 2).sum() / dof
    syy = (fg * (yy - my) ** 2).sum() / dof
    sxy = (fg * (xx - mx) * (yy - my)).sum() / dof
    cov = jnp.array([[sxx, sxy], [sxy, syy]]) + jnp.eye(2) * 1e-3
    cov = jnp.where(n < 2.0, jnp.eye(2), cov)
    icov = _inv2x2(cov)
    dx = xx - mx
    dy = yy - my
    m = (icov[0, 0] * dx * dx + (icov[0, 1] + icov[1, 0]) * dx * dy
         + icov[1, 1] * dy * dy)
    return jnp.exp(-0.5 * tau * m)


def guidance_map(
    mask: jax.Array,
    rng: jax.Array | None = None,
    family: str = "nellipse_gaussians",
    alpha: float = 0.6,
    sigma: float = 10.0,
    softness: float = 0.05,
    pert: int = 0,
    is_val: bool = False,
    tau: float = 1.0,
) -> jax.Array:
    """One (H, W) float32 guidance channel from a binary mask.

    Families and their scaling mirror the host transforms exactly:
    ``nellipse_gaussians`` — z1 + alpha*z2 rescaled to peak 255 (the live
    channel, transforms.NEllipseWithGaussians); ``nellipse`` — indicator x255;
    ``extreme_points`` — unscaled [0, 1] heatmap; ``confidence_l1l2`` /
    ``confidence_gaussian`` — min-max-normalized x255 (AddConfidenceMap,
    whose gaussian branch pins tau=0.5).  Degenerate masks zero the map:
    empty for the point families, empty-or-full for the confidence families
    (the host's ``len(np.unique(mask)) == 1`` rule).
    """
    if family not in FAMILIES:
        raise ValueError(f"family {family!r} not device-supported {FAMILIES}")
    shape = mask.shape
    if family == "confidence_gaussian":
        pts = None  # moments-only family
    elif is_val:
        pts = extreme_points_fixed(mask, pert)
    else:
        if rng is None:
            raise ValueError("training-mode guidance_map needs an rng")
        pts = extreme_points_random(mask, rng, pert)
    if family == "extreme_points":
        z = _gaussian_hm(shape, pts, sigma)
    elif family == "nellipse":
        z = _nellipse_z(shape, pts, softness) * 255.0
    elif family == "confidence_l1l2":
        z = _minmax_255(_l1l2_map(shape, pts, tau))
    elif family == "confidence_gaussian":
        z = _minmax_255(_mvgauss_map(mask, 0.5))
    else:
        z1 = _nellipse_z(shape, pts, softness)
        z2 = _gaussian_hm(shape, pts, sigma)
        z = z1 * 255.0 + z2 * (255.0 * alpha)
        z = jnp.clip(z * (255.0 / jnp.maximum(z.max(), 1e-12)), 0.0, 255.0)
    live = jnp.any(mask > 0.5)
    if family.startswith("confidence"):
        live = live & jnp.any(mask <= 0.5)
    return jnp.where(live, z, 0.0).astype(jnp.float32)


def make_device_guidance(
    family: str = "nellipse_gaussians",
    alpha: float = 0.6,
    sigma: float = 10.0,
    softness: float = 0.05,
    pert: int | None = None,
    is_val: bool = False,
    tau: float = 1.0,
) -> Callable[[Batch, jax.Array], dict]:
    """Build the ``(batch, rng) -> batch`` stage appending the guidance
    channel to ``concat`` from ``crop_gt``, per sample.

    ``pert=None`` picks each family's pipeline default
    (pipeline._guidance_stage: ``extreme_points`` and the confidence
    families train with 5 px of point jitter; the n-ellipse families use 0).
    Feed the host pipeline ``guidance='none'`` so ``concat`` arrives with
    the bare image channels.
    """
    if family not in FAMILIES:
        raise ValueError(f"family {family!r} not device-supported {FAMILIES}")
    if pert is None:
        jittered = family in ("extreme_points", "confidence_l1l2",
                              "confidence_gaussian")
        pert = 5 if (jittered and not is_val) else 0

    def stage(batch: Batch, rng: jax.Array) -> dict:
        x = batch["concat"]
        gt = batch["crop_gt"]
        gt2 = gt[..., 0] if gt.ndim == 4 else gt
        keys = jax.random.split(rng, x.shape[0])

        def one(mask, key):
            return guidance_map(mask, key, family=family, alpha=alpha,
                                sigma=sigma, softness=softness, pert=pert,
                                is_val=is_val, tau=tau)

        maps = jax.vmap(one)(gt2, keys)
        out = dict(batch)
        out["concat"] = jnp.concatenate(
            [x, maps[..., None].astype(x.dtype)], axis=-1)
        return out

    return stage
