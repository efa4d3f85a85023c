"""Host-side data-augmentation transforms over dict samples.

TPU-first re-design of the reference transform library
(/root/reference/custom_transforms.py, inventoried in SURVEY.md §2.3).  The
sample is a ``dict[str, np.ndarray]`` flowing through a ``Compose`` chain; the
stringly-typed key contract of the reference is kept on purpose (``image``,
``gt``, ``void_pixels``, ``crop_image``, ``crop_gt``, ``nellipseWithGaussians``,
``concat``, …) so a reference user finds the same pipeline vocabulary.

TPU-relevant design choices (SURVEY.md §7 hard parts a-c):

* everything here runs on **host** (numpy + OpenCV) — random geometric warps
  and mask-dependent crops are dynamic-shape control flow that would defeat
  XLA; the device only ever sees the fixed-shape output of ``FixedResize``.
* randomness is an explicit ``np.random.Generator`` passed to ``__call__`` —
  no global RNG, so per-sample seeds make the pipeline reproducible and safe
  to shard across hosts.
* the terminal transform is :class:`ToArray` (HWC float32), not a CHW
  ``ToTensor`` — NHWC is the TPU-native layout.

Keys named ``id``/``meta`` are metadata and never array-processed; ``bbox`` and
``crop_relax`` are coordinate payloads with their own rules (matching the
exemption lists at reference custom_transforms.py:108,166,482).
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from .. import imaging
from ..utils import helpers
from . import guidance

#: sample keys that are never treated as image arrays
META_KEYS = ("id", "meta")


def _is_meta(key: str) -> bool:
    # Exact-match on purpose: the reference's substring test (`'id' in elem`,
    # custom_transforms.py:108) silently matched 'vo*id*_pixels' and skipped it
    # in ToTensor — a latent quirk we do not reproduce.
    return key in META_KEYS


def _require_rng(rng: np.random.Generator | None) -> np.random.Generator:
    return rng if rng is not None else np.random.default_rng()


class Transform:
    """Base: ``__call__(sample, rng) -> sample``.  Deterministic transforms
    ignore ``rng``."""

    def __call__(self, sample: dict, rng: np.random.Generator | None = None) -> dict:
        raise NotImplementedError

    def __repr__(self):
        return f"{type(self).__name__}()"


class Compose(Transform):
    """Chain transforms, threading one RNG through the stochastic ones."""

    def __init__(self, transforms: Sequence[Transform]):
        self.transforms = list(transforms)

    def __call__(self, sample, rng=None):
        for t in self.transforms:
            sample = t(sample, rng)
        return sample

    def __repr__(self):
        inner = ", ".join(repr(t) for t in self.transforms)
        return f"Compose([{inner}])"


# ---------------------------------------------------------------------------
# geometric transforms
# ---------------------------------------------------------------------------

class RandomHorizontalFlip(Transform):
    """p=0.5 left-right flip of every array key (reference
    custom_transforms.py:202-218)."""

    def __init__(self, p: float = 0.5):
        self.p = p

    def __call__(self, sample, rng=None):
        rng = _require_rng(rng)
        if rng.random() < self.p:
            for key, val in sample.items():
                if not _is_meta(key):
                    sample[key] = imaging.flip_h(val)
        return sample

    def __repr__(self):
        return f"RandomHorizontalFlip(p={self.p})"


def _warp_interpolation(key: str, arr: np.ndarray, semseg: bool) -> int:
    """Reference rule (custom_transforms.py:117-122): nearest for arrays whose
    values are all in {0, 1, 255} (binary / void masks), nearest for gt under
    semantic-segmentation mode, cubic otherwise."""
    if ((arr == 0) | (arr == 1) | (arr == 255)).all():
        return imaging.NEAREST
    if semseg and "gt" in key:
        return imaging.NEAREST
    return imaging.CUBIC


class ScaleNRotate(Transform):
    """Random in-plane rotation + isotropic zoom about the image center.

    Behavior-compatible with reference custom_transforms.py:76-142: tuple args
    draw uniformly from the (symmetric) range, list args pick one entry;
    ``cv2.warpAffine`` on every array key with per-key interpolation and the
    reference's uint8 cast before warping (guidance/image values live in
    [0, 255] at this point in the pipeline); ``bb_mask`` keys warp with a 255
    border (outside-bbox convention).
    """

    def __init__(self, rots=(-30, 30), scales=(0.75, 1.25), semseg: bool = False):
        if isinstance(rots, tuple) != isinstance(scales, tuple):
            raise TypeError("rots and scales must both be ranges or both be lists")
        self.rots = rots
        self.scales = scales
        self.semseg = semseg

    def _draw(self, rng: np.random.Generator) -> tuple[float, float]:
        if isinstance(self.rots, tuple):
            rot = float(rng.uniform(self.rots[0], self.rots[1]))
            sc = float(rng.uniform(self.scales[0], self.scales[1]))
        else:
            rot = float(self.rots[rng.integers(0, len(self.rots))])
            sc = float(self.scales[rng.integers(0, len(self.scales))])
        return rot, sc

    def __call__(self, sample, rng=None):
        rng = _require_rng(rng)
        rot, sc = self._draw(rng)
        for key in list(sample.keys()):
            if _is_meta(key):
                continue
            arr = sample[key]
            h, w = arr.shape[:2]
            M = imaging.rotation_matrix((w / 2, h / 2), rot, sc)
            flag = _warp_interpolation(key, arr, self.semseg)
            # Border fill: 255 for bb_mask (outside-bbox convention) AND for
            # class-id gt under semseg — warped-out regions must become void
            # (ignore_index), not background, or the CE loss would supervise
            # synthetic class-0 pixels over black image padding.
            border = 255 if ("bb_mask" in key or
                             (self.semseg and "gt" in key)) else 0
            sample[key] = imaging.warp_affine(
                arr.astype(np.uint8), M, (h, w), flag, border
            )
        return sample

    def __repr__(self):
        return f"ScaleNRotate(rots={self.rots}, scales={self.scales})"


class FixedResize(Transform):
    """Resize each key to ``resolutions[key]``; prune keys not listed.

    Behavior-compatible with reference custom_transforms.py:145-199, including
    its two load-bearing quirks (SURVEY.md §2.3):

    * a key mapped to ``None`` passes through untouched — how the val pipeline
      keeps full-resolution ``gt``/``void_pixels`` for full-image evaluation;
    * **keys absent from ``resolutions`` are deleted** — how the sample's key
      set is pruned before batching (variable-size leftovers must not reach
      the collate step).

    ``bbox``/``crop_relax``/``meta`` are exempt; ``extreme_points_coord`` is
    rescaled by the bbox→resolution ratio rather than resized.
    """

    def __init__(
        self,
        resolutions: Mapping[str, tuple[int, int] | None] | None = None,
        flagvals: Mapping[str, int] | None = None,
    ):
        self.resolutions = resolutions
        self.flagvals = flagvals
        if flagvals is not None and resolutions is not None:
            assert set(flagvals) == set(resolutions)

    def __call__(self, sample, rng=None):
        if self.resolutions is None:
            return sample
        for key in list(sample.keys()):
            exempt = "meta" in key or "bbox" in key or "crop_relax" in key
            if exempt:
                continue
            if key == "extreme_points_coord":
                if key not in self.resolutions:
                    continue
                # This repo's bbox convention is an inclusive 4-tuple
                # (x_min, y_min, x_max, y_max) from helpers.get_bbox; points
                # are (x, y) pairs, resolutions are (H, W) — scale x by the
                # width ratio and y by the height ratio.
                bbox = sample["bbox"]
                crop_wh = np.array(
                    [bbox[2] - bbox[0] + 1, bbox[3] - bbox[1] + 1], dtype=np.float32
                )
                res_h, res_w = self.resolutions[key]
                scale = np.array([res_w, res_h], dtype=np.float32) / crop_wh
                sample[key] = np.round(sample[key] * scale).astype(np.int64)
                continue
            if key not in self.resolutions:
                del sample[key]
                continue
            res = self.resolutions[key]
            if res is None:
                continue
            flag = None if self.flagvals is None else self.flagvals[key]
            val = sample[key]
            if isinstance(val, list):
                # A list of per-channel crops: resize elementwise and stack on
                # a trailing axis (reference custom_transforms.py:177-188).
                resized = [helpers.fixed_resize(v, res, flagval=flag) for v in val]
                sample[key] = np.stack(resized, axis=-1).astype(np.float32)
            else:
                sample[key] = helpers.fixed_resize(val, res, flagval=flag)
        return sample

    def __repr__(self):
        return f"FixedResize({self.resolutions})"


# ---------------------------------------------------------------------------
# mask-driven crops
# ---------------------------------------------------------------------------

def _crop_one(img, mask, relax, zero_pad):
    if mask.max() == 0:
        return np.zeros(img.shape, dtype=img.dtype)
    return helpers.crop_from_mask(img, mask, relax=relax, zero_pad=zero_pad)


def _crop_elems(sample, crop_elems, mask_elem, relax, zero_pad):
    """Shared crop loop: for each element, crop against every channel of the
    mask element; single-channel masks produce an array, multi-channel masks a
    list of crops (reference custom_transforms.py:343-371)."""
    target = sample[mask_elem]
    if target.ndim == 2:
        target = target[..., np.newaxis]
    for elem in crop_elems:
        img = sample[elem]
        if elem == mask_elem and img.ndim == 2:
            img = img[..., np.newaxis]
        crops = []
        for k in range(target.shape[-1]):
            src = img[..., k] if elem == mask_elem else img
            crops.append(_crop_one(src, target[..., k], relax, zero_pad))
        sample["crop_" + elem] = crops[0] if len(crops) == 1 else crops
    return sample


class CropFromMaskStatic(Transform):
    """Crop listed elements to the gt bbox expanded by a fixed ``relax``
    border, zero-padding beyond image borders (reference
    custom_transforms.py:329-375; the live train/val path uses relax=50,
    zero_pad=True per train_pascal.py:126,137)."""

    def __init__(self, crop_elems=("image", "gt"), mask_elem="gt", relax=0, zero_pad=False):
        self.crop_elems = crop_elems
        self.mask_elem = mask_elem
        self.relax = relax
        self.zero_pad = zero_pad

    def __call__(self, sample, rng=None):
        sample = _crop_elems(sample, self.crop_elems, self.mask_elem,
                             self.relax, self.zero_pad)
        # Record the (relaxed) crop bbox: FixedResize rescales point
        # coordinates by it, and the evaluator's crop->fullmask paste-back can
        # reuse it instead of recomputing from the full-res gt.
        mask = sample[self.mask_elem]
        if mask.ndim == 3:
            mask = mask[..., 0]
        bbox = helpers.get_bbox(mask, pad=self.relax, zero_pad=self.zero_pad)
        if bbox is None:
            # Empty mask: the crop was a full-image passthrough of zeros;
            # record the full-image box so batches keep a consistent key set.
            bbox = (0, 0, mask.shape[1] - 1, mask.shape[0] - 1)
        sample["bbox"] = np.asarray(bbox, dtype=np.int64)
        return sample

    def __repr__(self):
        return (f"CropFromMaskStatic(elems={self.crop_elems}, relax={self.relax}, "
                f"zero_pad={self.zero_pad})")


class FusedCropResize(Transform):
    """``CropFromMaskStatic`` + ``FixedResize`` in one pass.

    A pipeline-level fusion, not a reference transform: each listed element
    is resized straight from its (relaxed, zero-padded) bbox window to
    ``size`` by the native ``crop_resize`` kernel, never materializing the
    intermediate crop — the two-stage pair's biggest allocation on the hot
    path.  Output contract matches the pair: ``crop_<elem>`` keys at
    ``size``, the recorded ``bbox``, FixedResize's pruning rule (keys not
    produced/kept are deleted; ``meta``/``bbox``/``crop_relax`` exempt),
    and the same per-element interpolation rule (nearest for binary /
    255-valued windows, cubic otherwise).

    Falls back to the two-stage path when the native library is absent.
    """

    def __init__(self, crop_elems=("image", "gt"), mask_elem="gt",
                 relax=0, zero_pad=False, size=(512, 512)):
        self.crop_elems = crop_elems
        self.mask_elem = mask_elem
        self.relax = relax
        self.zero_pad = zero_pad
        self.size = tuple(size)

    def _window_flag(self, arr: np.ndarray, bbox) -> int:
        """``helpers.resize_interp_flag`` evaluated on the in-image part of
        the window (the zero padding only adds 0s, which never change
        binary-ness)."""
        win = arr[max(bbox[1], 0): bbox[3] + 1, max(bbox[0], 0): bbox[2] + 1]
        return helpers.resize_interp_flag(win)

    def _two_stage(self, sample, rng):
        return Compose([
            CropFromMaskStatic(crop_elems=self.crop_elems,
                               mask_elem=self.mask_elem,
                               relax=self.relax, zero_pad=self.zero_pad),
            FixedResize(resolutions={
                "crop_" + e: self.size for e in self.crop_elems}),
        ])(sample, rng)

    def __call__(self, sample, rng=None):
        from .. import native_ops

        if not (native_ops.enabled() and native_ops.has_crop_resize()):
            return self._two_stage(sample, rng)
        if np.asarray(sample[self.mask_elem]).ndim != 2:
            # Multi-channel mask: the pair's contract is per-channel crop
            # LISTS (custom_transforms.py:350-370) which the fused kernel
            # does not reproduce — route through the exact two-stage path.
            return self._two_stage(sample, rng)

        mask = sample[self.mask_elem]
        bbox = helpers.get_bbox(mask, pad=self.relax, zero_pad=self.zero_pad)
        for elem in self.crop_elems:
            arr = sample[elem]
            if bbox is None:  # empty mask -> zeros at the output size
                shape = self.size + arr.shape[2:]
                sample["crop_" + elem] = np.zeros(shape, np.float32)
                continue
            sample["crop_" + elem] = native_ops.crop_resize(
                arr, bbox, self.size, self._window_flag(arr, bbox))
        if bbox is None:
            bbox = (0, 0, mask.shape[1] - 1, mask.shape[0] - 1)
        sample["bbox"] = np.asarray(bbox, dtype=np.int64)
        # FixedResize's pruning rule: everything not produced goes (with
        # FixedResize's own exemptions: meta/bbox/crop_relax AND the
        # extreme_points_coord payload it rescales rather than deletes).
        produced = {"crop_" + e for e in self.crop_elems}
        for key in list(sample.keys()):
            if key in produced or "meta" in key or "bbox" in key \
                    or "crop_relax" in key or key == "extreme_points_coord":
                continue
            del sample[key]
        return sample

    def __repr__(self):
        return (f"FusedCropResize(elems={self.crop_elems}, relax={self.relax},"
                f" zero_pad={self.zero_pad}, size={self.size})")


class CropFromMask(Transform):
    """Zoom-normalizing crop: pick the relax border so the object occupies a
    target fraction of the final ``d``×``d`` crop.

    Behavior-compatible with reference custom_transforms.py:377-452: at val the
    object's long side maps to ``sqrt(0.5)·d``; at train the target is drawn
    uniformly in [``sqrt(0.45)·d``, ``sqrt(0.6)·d``]; a floor keeps tiny
    objects from being zoomed past 4% of the crop area; the chosen border is
    recorded as ``sample['crop_relax']`` for paste-back.
    """

    def __init__(self, crop_elems=("image", "gt"), mask_elem="gt", zero_pad=False,
                 d: int = 512, is_val: bool = True):
        self.crop_elems = crop_elems
        self.mask_elem = mask_elem
        self.zero_pad = zero_pad
        self.d = d
        self.is_val = is_val
        dz_val = int(np.sqrt(d * d * 0.5))
        min_object_dim = d / 5
        self.floor = ((d - dz_val) * min_object_dim) / (2 * dz_val)
        self.dz_val = dz_val
        self.dz_train_range = (int(np.sqrt(d * d * 0.45)), int(np.sqrt(d * d * 0.6)))

    def __call__(self, sample, rng=None):
        target = sample[self.mask_elem]
        if len(np.unique(target)) == 1:
            # Degenerate mask: pass every crop element through uncropped, with
            # a zero relax so the batch key-set stays consistent.
            for elem in self.crop_elems:
                sample["crop_" + elem] = sample[elem]
            sample["crop_relax"] = 0
            return sample
        if self.is_val:
            dz = float(self.dz_val)
        else:
            rng = _require_rng(rng)
            dz = float(rng.integers(self.dz_train_range[0], self.dz_train_range[1]))
        t3 = target if target.ndim == 3 else target[..., np.newaxis]
        bbox = helpers.get_bbox(t3[..., 0])
        long_side = max(bbox[2] - bbox[0], bbox[3] - bbox[1])
        long_side = max(long_side, 1)
        zoom = dz / long_side
        relax = max((self.d - long_side * zoom) / (2 * zoom), self.floor)
        relax = int(np.ceil(relax))
        sample["crop_relax"] = relax
        return _crop_elems(sample, self.crop_elems, self.mask_elem, relax, self.zero_pad)

    def __repr__(self):
        return f"CropFromMask(d={self.d}, is_val={self.is_val})"


class CreateBBMask(Transform):
    """255-outside / 0-inside bounding-box mask of ``gt`` (reference
    custom_transforms.py:67-74)."""

    def __call__(self, sample, rng=None):
        mask = sample["gt"]
        bbox = helpers.get_bbox(mask)
        out = np.full(mask.shape, 255.0, dtype=np.float32)
        if bbox is not None:
            # get_bbox max coords are inclusive.
            out[bbox[1] : bbox[3] + 1, bbox[0] : bbox[2] + 1] = 0.0
        sample["bb_mask"] = out
        return sample


# ---------------------------------------------------------------------------
# guidance-channel transforms
# ---------------------------------------------------------------------------

def _pick_points(target, pert, is_val, rng):
    if is_val:
        return guidance.extreme_points_fixed(target, pert)
    return guidance.extreme_points(target, pert, rng=_require_rng(rng))


class NEllipse(Transform):
    """Rasterize the n-ellipse through the gt's extreme points into
    ``sample['nellipse']``, scaled to [0, 255] (reference
    custom_transforms.py:9-27)."""

    def __init__(self, is_val: bool = True):
        self.is_val = is_val

    def __call__(self, sample, rng=None):
        target = sample["crop_gt"]
        if target.max() == 0:
            sample["nellipse"] = np.zeros(target.shape, dtype=target.dtype)
            return sample
        pts = _pick_points(target, 0, self.is_val, rng)
        sample["nellipse"] = guidance.nellipse_map(target.shape[:2], pts)
        return sample


class NEllipseWithGaussians(Transform):
    """The live guidance channel (reference custom_transforms.py:30-51,
    consumed at train_pascal.py:131,142): n-ellipse plus gaussian bumps at the
    extreme points, combined ``z1 + alpha·z2`` and rescaled to peak at 255."""

    def __init__(self, alpha: float = 0.6, is_val: bool = True):
        self.alpha = alpha
        self.is_val = is_val

    def __call__(self, sample, rng=None):
        target = sample["crop_gt"]
        if target.max() == 0:
            sample["nellipseWithGaussians"] = np.zeros(target.shape, dtype=target.dtype)
            return sample
        pts = _pick_points(target, 0, self.is_val, rng)
        sample["nellipseWithGaussians"] = guidance.nellipse_gaussians_map(
            target.shape[:2], pts, alpha=self.alpha)
        return sample

    def __repr__(self):
        return f"NEllipseWithGaussians(alpha={self.alpha}, is_val={self.is_val})"


class ExtremePoints(Transform):
    """DEXTR-style guidance: gaussian heatmap (sigma, max-combined) at the 4
    perturbed extreme points of ``elem`` (reference
    custom_transforms.py:221-251)."""

    def __init__(self, sigma: float = 10, pert: int = 0, elem: str = "gt",
                 is_val: bool = True):
        self.sigma = sigma
        self.pert = pert
        self.elem = elem
        self.is_val = is_val

    def __call__(self, sample, rng=None):
        target = sample[self.elem]
        if target.ndim == 3:
            raise ValueError("ExtremePoints expects a single-object 2-D mask")
        if target.max() == 0:
            sample["extreme_points"] = np.zeros(target.shape, dtype=target.dtype)
            return sample
        pts = _pick_points(target, self.pert, self.is_val, rng)
        sample["extreme_points"] = guidance.extreme_points_map(
            target.shape[:2], pts, sigma=self.sigma)
        return sample


class AddConfidenceMap(Transform):
    """Alternative guidance: skewed-axes L1L2 or multivariate-gaussian
    confidence map appended as an extra channel -> ``sample['with_hm']``
    (reference custom_transforms.py:253-298; inactive in the live driver)."""

    def __init__(self, elem="crop_image", hm_type="l1l2", tau: float = 1.0,
                 pert: int = 0, is_val: bool = True):
        assert hm_type in ("l1l2", "gaussian")
        self.elem = elem
        self.hm_type = hm_type
        self.tau = tau
        self.pert = pert
        self.is_val = is_val

    def __call__(self, sample, rng=None):
        img = sample[self.elem]
        mask = sample["crop_gt"].astype(bool)
        if len(np.unique(mask)) == 1:
            hm = np.zeros(img.shape[:2], dtype=np.float32)
        elif self.hm_type == "l1l2":
            pts = _pick_points(mask, self.pert, self.is_val, rng)
            h_map, _, _ = guidance.generate_mv_l1l2_image_skewed_axes(
                mask, extreme_points=pts, FULL_IMAGE_WEIGHTS=1, d2_THRESH=None,
                tau=self.tau,
            )
            hm = guidance.normalize_wt_map(h_map) * 255.0
        else:
            h_map = guidance.generate_mvgauss_image(mask, FULL_IMAGE_WEIGHTS=1, tau=0.5)
            hm = guidance.normalize_wt_map(h_map) * 255.0
        sample["with_hm"] = np.concatenate(
            [np.atleast_3d(img), hm[..., np.newaxis]], axis=2
        ).astype(np.float32)
        return sample


# ---------------------------------------------------------------------------
# assembly / normalization
# ---------------------------------------------------------------------------

class ConcatInputs(Transform):
    """Channel-concatenate named elements into ``sample['concat']`` — the
    model's input assembly (reference custom_transforms.py:302-326; live use:
    image(3) + guidance heatmap(1) -> 4-channel input,
    train_pascal.py:133,144)."""

    def __init__(self, elems=("image", "point")):
        self.elems = elems

    def __call__(self, sample, rng=None):
        base = sample[self.elems[0]]
        parts = [np.atleast_3d(base)]
        for elem in self.elems[1:]:
            if sample[elem].shape[:2] != base.shape[:2]:
                raise ValueError(
                    f"ConcatInputs: {elem} spatial shape {sample[elem].shape[:2]} "
                    f"!= {self.elems[0]} {base.shape[:2]}"
                )
            parts.append(np.atleast_3d(sample[elem]))
        # Single element (the device_guidance config: the map is appended on
        # device): atleast_3d is a view — skip the pointless full-array copy
        # np.concatenate would make on the hot path.
        sample["concat"] = parts[0] if len(parts) == 1 \
            else np.concatenate(parts, axis=2)
        return sample

    def __repr__(self):
        return f"ConcatInputs({self.elems})"


class ToImage(Transform):
    """Min-max rescale element(s) to [0, custom_max] (reference
    custom_transforms.py:454-473)."""

    def __init__(self, norm_elem="image", custom_max: float = 255.0):
        self.norm_elem = norm_elem if isinstance(norm_elem, tuple) else (norm_elem,)
        self.custom_max = custom_max

    def __call__(self, sample, rng=None):
        for elem in self.norm_elem:
            v = sample[elem]
            sample[elem] = self.custom_max * (v - v.min()) / (v.max() - v.min() + 1e-10)
        return sample


class Duplicate(Transform):
    """Copy sample keys (``{src: dst}``) — e.g. preserving a full-res
    ``gt`` under a new name before a resize stage consumes the original."""

    def __init__(self, mapping: Mapping[str, str]):
        self.mapping = dict(mapping)

    def __call__(self, sample, rng=None):
        for src, dst in self.mapping.items():
            if src in sample:
                sample[dst] = sample[src]
        return sample

    def __repr__(self):
        return f"Duplicate({self.mapping})"


class Rename(Transform):
    """Rename sample keys (``{old: new}``) — adapter between pipelines with
    different key contracts (e.g. the semantic pipeline's per-image
    ``image``/``gt`` onto the step contract's ``concat``/``crop_gt``)."""

    def __init__(self, mapping: Mapping[str, str]):
        self.mapping = dict(mapping)

    def __call__(self, sample, rng=None):
        for old, new in self.mapping.items():
            if old in sample:
                sample[new] = sample.pop(old)
        return sample

    def __repr__(self):
        return f"Rename({self.mapping})"


class Keep(Transform):
    """Delete every sample key except the listed ones (``meta`` always
    survives) — the terminal pruning step for hot-path pipelines, so
    ``collate`` never stacks arrays nothing downstream consumes (the
    intermediate ``crop_image``/guidance maps are a ~4x memcpy tax per
    batch once ``concat`` is assembled)."""

    def __init__(self, keys: Sequence[str]):
        self.keys = tuple(keys)

    def __call__(self, sample, rng=None):
        for key in list(sample.keys()):
            if key not in self.keys and not _is_meta(key):
                del sample[key]
        return sample

    def __repr__(self):
        return f"Keep({self.keys})"


class ClampRange(Transform):
    """Clamp named elements into ``[lo, hi]``.

    Cubic resampling overshoots value ranges near edges; in the reference
    chain that was masked by ScaleNRotate's uint8 cast
    (custom_transforms.py:124-126) upstream of the resize.  When the
    geometric stage moves on-device (``build_train_transform(geom=False)``)
    the float image reaches ``FixedResize`` unquantized, so the [0,255]
    data contract (reference train_pascal.py:188) needs this explicit
    clamp."""

    def __init__(self, elems: Sequence[str], lo: float = 0.0,
                 hi: float = 255.0):
        self.elems = tuple(elems)
        self.lo, self.hi = lo, hi

    def __call__(self, sample, rng=None):
        for k in self.elems:
            if k in sample:
                sample[k] = np.clip(sample[k], self.lo, self.hi)
        return sample

    def __repr__(self):
        return f"ClampRange({self.elems}, {self.lo}, {self.hi})"


class ToArray(Transform):
    """Terminal transform: every array key -> float32 **HWC** numpy; 2-D
    arrays get a channel axis.

    This is the TPU-native counterpart of the reference's ``ToTensor``
    (custom_transforms.py:476-503): same float32 cast and channel-axis rule,
    but the layout stays HWC (NHWC batches are what XLA/TPU convolutions
    want) instead of transposing to CHW.  ``bbox`` converts without the
    channel rule; ``crop_relax``/meta pass through.

    ``uint8_passthrough`` keeps arrays that arrive as uint8 in uint8 (the
    wire format of ``data.uint8_transfer``: 4x fewer H2D bytes; the step
    dequantizes on device) — everything else still casts to float32.
    """

    def __init__(self, uint8_passthrough: bool = False):
        self.uint8_passthrough = uint8_passthrough

    def __call__(self, sample, rng=None):
        for key, val in sample.items():
            if _is_meta(key) or "crop_relax" in key:
                continue
            if "bbox" in key:
                sample[key] = np.asarray(val)
                continue
            arr = np.asarray(val)
            if not (self.uint8_passthrough and arr.dtype == np.uint8):
                # copy=False: already-float32 arrays pass through un-copied
                arr = arr.astype(np.float32, copy=False)
            if arr.ndim == 2:
                arr = arr[:, :, np.newaxis]
            sample[key] = arr
        return sample

    def __repr__(self):
        return f"ToArray(uint8_passthrough={self.uint8_passthrough})"


class PackBits(Transform):
    """Pack binary uint8 masks to 1 bit/pixel for the wire
    (``data.packbits_masks``).

    Runs after :class:`ToArray` on the uint8 fast path: a ``(H, W, 1)``
    uint8 {0,1} mask becomes a flat ``(ceil(H*W/8),)`` uint8 array
    (``np.packbits``, big-endian bit order — the device side's unpack in
    ``parallel.step`` mirrors it with MSB-first shifts).  An 8x wire/memcpy
    cut on the mask tensor, on top of uint8_transfer's 4x: worth it when
    H2D placement — not host or chip — bounds e2e.  Collate stacks the
    packed rows to ``(B, P)``; the compiled step unpacks with fused
    elementwise bit ops.
    """

    def __init__(self, elems=("crop_gt",)):
        self.elems = elems

    def __call__(self, sample, rng=None):
        for key in self.elems:
            arr = sample.get(key)
            if arr is None:
                continue
            arr = np.asarray(arr)
            if arr.dtype != np.uint8:
                raise TypeError(
                    f"PackBits({key!r}): expected a uint8 {{0,1}} mask "
                    f"(the data.uint8_transfer wire), got {arr.dtype}")
            if arr.max(initial=0) > 1:
                # np.packbits would silently coerce any nonzero byte to
                # bit 1, losing the "gt strictly binary" contract that the
                # plain wire's debug assert enforces — fail loudly instead
                raise ValueError(
                    f"PackBits({key!r}): mask has values > 1 "
                    f"(max {arr.max()}); only binary masks pack losslessly")
            sample[key] = np.packbits(arr.ravel())
        return sample

    def __repr__(self):
        return f"PackBits({self.elems})"
