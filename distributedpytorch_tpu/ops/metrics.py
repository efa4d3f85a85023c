"""Evaluation metrics: void-aware Jaccard (IoU) with threshold sweep.

The reference's quality metric (its ``calc_jaccard`` from the missing
``dataloaders.implementation`` module): per-sample IoU of the binarized
prediction vs ground truth, excluding void pixels, evaluated at thresholds
{0.3, 0.5, 0.8} with the best-threshold mean gating checkpoint saves
(reference train_pascal.py:281,291,298-304).

Two forms:

* device-side (:func:`jaccard`, :func:`batched_jaccard`,
  :func:`threshold_sweep_jaccard`) — jnp, fixed shapes, usable inside a jitted
  eval step on crop-space predictions;
* the full-resolution paste-back protocol (crop -> original image coords via
  ``utils.helpers.crop2fullmask``) is ragged-shape and stays host-side in the
  evaluator (``train.evaluate``), mirroring where the reference ran it (CPU,
  train_pascal.py:283-291).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: the reference's eval threshold sweep (train_pascal.py:281)
DEFAULT_THRESHOLDS = (0.3, 0.5, 0.8)


def jaccard(
    pred: jax.Array, gt: jax.Array, void: jax.Array | None = None
) -> jax.Array:
    """IoU of two binary masks, excluding void pixels.  Empty-union -> 1.0
    (an empty prediction of an empty ground truth is a perfect match)."""
    pred = pred.astype(jnp.bool_)
    gt = gt.astype(jnp.bool_)
    valid = (
        jnp.ones_like(gt) if void is None else jnp.logical_not(void.astype(jnp.bool_))
    )
    inter = jnp.sum(pred & gt & valid)
    union = jnp.sum((pred | gt) & valid)
    return jnp.where(union == 0, 1.0, inter / jnp.maximum(union, 1))


def batched_jaccard(
    pred: jax.Array, gt: jax.Array, void: jax.Array | None = None
) -> jax.Array:
    """Per-sample IoU over a leading batch axis: (B, ...) -> (B,)."""
    fn = jax.vmap(lambda p, g, v: jaccard(p, g, v))
    if void is None:
        void = jnp.zeros_like(gt)
    return fn(pred, gt, void)


def threshold_sweep_jaccard(
    probs: jax.Array,
    gt: jax.Array,
    void: jax.Array | None = None,
    thresholds: tuple[float, ...] = DEFAULT_THRESHOLDS,
) -> jax.Array:
    """IoU of ``probs > t`` for each threshold: (B, ...) -> (T, B)."""
    return jnp.stack(
        [batched_jaccard(probs > t, gt, void) for t in thresholds]
    )


def np_jaccard(pred: np.ndarray, gt: np.ndarray, void: np.ndarray | None = None) -> float:
    """Host-side (numpy) twin of :func:`jaccard` for the ragged full-res
    paste-back path — per-image sizes vary so this cannot be batched/jitted."""
    pred = pred.astype(bool)
    gt = gt.astype(bool)
    valid = np.ones_like(gt) if void is None else ~void.astype(bool)
    inter = int(np.sum(pred & gt & valid))
    union = int(np.sum((pred | gt) & valid))
    return 1.0 if union == 0 else inter / union


def np_jaccard_thresholds(
    prob: np.ndarray,
    thresholds,
    gt: np.ndarray,
    void: np.ndarray | None = None,
) -> np.ndarray:
    """Threshold-swept IoU in ONE pass over the image.

    The reference protocol scores ``prob > t`` for each t in {0.3, 0.5,
    0.8} (train_pascal.py:281-291); the naive form walks the full-res
    image once per threshold.  Digitizing ``prob`` against the sorted
    thresholds instead gives every threshold's intersection/union from two
    bin-counts via suffix sums — the host paste-back loop's scoring cost
    stops scaling with ``len(thresholds)``.

    Exact equality semantics match ``prob > t`` (strict): bin index k
    counts thresholds strictly below the value, so a pixel AT a threshold
    is not predicted positive for it.  Returns IoUs in the CALLER'S
    threshold order.
    """
    prob = np.asarray(prob)
    # thresholds must compare in PROB's dtype: ``prob > 0.3`` on float32
    # casts the scalar to float32 (0.3f != 0.3), so a float64 threshold
    # table here would flip at-threshold pixels relative to the naive form
    t = np.asarray(thresholds, dtype=prob.dtype if
                   np.issubdtype(prob.dtype, np.floating) else np.float64)
    order = np.argsort(t, kind="stable")
    ts = t[order]
    k = ts.size
    gt = gt.astype(bool).ravel()
    valid = np.ones_like(gt) if void is None \
        else ~np.asarray(void).astype(bool).ravel()
    # searchsorted 'left': #(ts < x); pred for threshold j  <=>  bin > j
    bins = np.searchsorted(ts, prob.ravel(), side="left")
    gt_counts = np.bincount(bins[gt & valid], minlength=k + 1)
    ngt_counts = np.bincount(bins[~gt & valid], minlength=k + 1)
    # suffix sums over bins j+1..k = counts where pred_j is True
    inter = np.cumsum(gt_counts[::-1])[::-1]        # inter[j+1..] summed
    pred_only = np.cumsum(ngt_counts[::-1])[::-1]
    n_gt_valid = int(gt_counts.sum())
    out = np.empty(k)
    for j in range(k):
        i = int(inter[j + 1])
        u = n_gt_valid + int(pred_only[j + 1])
        out[j] = 1.0 if u == 0 else i / u
    inv = np.empty(k, np.intp)
    inv[order] = np.arange(k)
    return out[inv]


# ---------------------------------------------------------------------------
# multi-class semantic metrics (the DeepLabV3 "val mIoU" of BASELINE.json)
# ---------------------------------------------------------------------------

def confusion_matrix(
    pred: jax.Array, label: jax.Array, nclass: int, ignore_index: int = 255
) -> jax.Array:
    """(C, C) confusion counts, rows = true class, cols = predicted class.

    ``pred``/``label``: int arrays of any (equal) shape; ``ignore_index``
    pixels are dropped (the in-band void convention of the semantic
    pipeline).  Jit-safe: one bincount over ``true * C + pred``.
    """
    pred = pred.reshape(-1).astype(jnp.int32)
    label = label.reshape(-1).astype(jnp.int32)
    valid = label != ignore_index
    idx = jnp.where(valid, label * nclass + pred, nclass * nclass)
    counts = jnp.bincount(idx, length=nclass * nclass + 1)[:-1]
    return counts.reshape(nclass, nclass)


def miou_from_confusion(conf) -> dict:
    """Per-class IoU / mean IoU / pixel accuracy from a (C, C) confusion.

    Classes absent from both prediction and ground truth (union == 0) are
    excluded from the mean, the standard VOC convention.
    """
    conf = np.asarray(conf, dtype=np.float64)
    inter = np.diag(conf)
    union = conf.sum(0) + conf.sum(1) - inter
    with np.errstate(divide="ignore", invalid="ignore"):
        iou = np.where(union > 0, inter / union, np.nan)
    miou = float(np.nanmean(iou)) if np.any(union > 0) else 0.0
    total = conf.sum()
    return {
        "miou": miou,
        "per_class_iou": [None if np.isnan(v) else float(v) for v in iou],
        "pixel_acc": float(inter.sum() / total) if total > 0 else 0.0,
    }
