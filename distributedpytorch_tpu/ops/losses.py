"""Segmentation losses.

Replaces the reference's external ``SegmentationMultiLosses`` (imported from a
missing ``layers.loss_weighted`` module at reference train_pascal.py:33 and
applied to the DANet 3-tuple output at train_pascal.py:119,199 — the
"wtd_loss" in its best-checkpoint filename, train_pascal.py:304).  All losses
are pure functions of logits — the sigmoid at reference train_pascal.py:262,284
lives in eval/vis code only, so training is from-logits and XLA fuses the
log-sum-exp into the preceding conv.

Void-pixel semantics: the reference zeroes 255-labeled pixels out of the
target (pascal.py:240-242) and excludes them from the metric
(train_pascal.py:291); here the loss itself also masks them, the from-logits
equivalent of ``ignore_index=255``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sigmoid_balanced_bce(
    logits: jax.Array,
    labels: jax.Array,
    void: jax.Array | None = None,
    balanced: bool = True,
) -> jax.Array:
    """Class-balanced binary cross-entropy from logits, void-aware.

    ``logits``/``labels``: (..., H, W[, 1]) broadcast-compatible; ``labels``
    binary {0,1}.  With ``balanced=True`` positive/negative pixels are
    reweighted by the opposite class's frequency (computed over valid pixels
    only) — the standard interactive-segmentation balancing for the extreme
    foreground/background skew of single-instance masks.  Returns a scalar.
    """
    logits = logits.astype(jnp.float32)
    labels = labels.astype(jnp.float32)
    valid = jnp.ones_like(labels) if void is None else (1.0 - void.astype(jnp.float32))
    # Stable BCE from logits: max(x,0) - x*z + log1p(exp(-|x|))
    per_pix = (
        jnp.maximum(logits, 0.0)
        - logits * labels
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )
    if balanced:
        n_valid = valid.sum()
        n_pos = (labels * valid).sum()
        w_pos = 1.0 - n_pos / jnp.maximum(n_valid, 1.0)
        weights = jnp.where(labels > 0.5, w_pos, 1.0 - w_pos) * valid
    else:
        weights = valid
    return (per_pix * weights).sum() / jnp.maximum(valid.sum(), 1.0)


def multi_output_loss(
    outputs: tuple[jax.Array, ...],
    labels: jax.Array,
    void: jax.Array | None = None,
    weights: tuple[float, ...] | None = None,
    balanced: bool = True,
) -> jax.Array:
    """Weighted sum of per-output losses over a multi-head model output.

    The ``SegmentationMultiLosses`` contract: the DANet head emits
    (fused, position-attention, channel-attention) predictions and all three
    are supervised against the same target (reference train_pascal.py:119,199).
    ``weights`` defaults to all-ones.
    """
    if weights is None:
        weights = (1.0,) * len(outputs)
    total = jnp.float32(0.0)
    for out, w in zip(outputs, weights):
        total = total + w * sigmoid_balanced_bce(out, labels, void, balanced)
    return total


def se_presence_loss(
    logits: jax.Array,
    labels: jax.Array,
    ignore_index: int = 255,
) -> jax.Array:
    """Semantic-encoding (SE) loss: per-image class-presence BCE.

    The EncNet training objective's auxiliary term (Zhang et al. CVPR'18,
    the PyTorch-Encoding package the reference pulls its models from —
    reference train_pascal.py:32): the context-encoding branch predicts
    which classes appear anywhere in the image, forcing the encoded global
    descriptor to carry scene-level semantics.  ``logits``: (B, C);
    ``labels``: int (B, H, W) with ``ignore_index`` void pixels excluded
    from the presence derivation.  Returns the mean BCE over (B, C).
    """
    c = logits.shape[-1]
    flat = labels.reshape(labels.shape[0], -1)
    valid = flat != ignore_index
    # presence[b, k] = any valid pixel of class k; the (B, N, C) compare
    # feeds straight into the any-reduce — XLA fuses it, nothing N*C-sized
    # is materialized.
    present = jnp.any(
        (flat[..., None] == jnp.arange(c)) & valid[..., None], axis=1
    ).astype(jnp.float32)
    x = logits.astype(jnp.float32)
    per = jnp.maximum(x, 0.0) - x * present + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return per.mean()


def softmax_xent_ignore(
    logits: jax.Array,
    labels: jax.Array,
    ignore_index: int = 255,
) -> jax.Array:
    """Multi-class softmax cross-entropy with ``ignore_index`` semantics.

    ``logits``: (..., C); ``labels``: int (...) with ``ignore_index`` marking
    void pixels (the reference's 255-labeled boundary pixels,
    pascal.py:240-242).  Ignored pixels contribute zero and are excluded
    from the mean — the multi-class loss for the DeepLabV3 semantic-
    segmentation configs of BASELINE.json.

    The label log-prob is selected with a compare-select-reduce over the
    class axis rather than ``take_along_axis``: XLA lowers the gather to a
    scalar per-element loop on TPU (measured 1.6 GiB/s, 28.9 ms per head at
    8x513x513x21 — 60% of the whole DeepLabV3 step, r4 profile
    ``prof_deeplab_b8.json``), while the select fuses into the surrounding
    elementwise work.  ``where`` (not one_hot multiply) keeps non-selected
    lanes exactly zero even for non-finite logits.
    """
    valid = (labels != ignore_index)
    safe_labels = jnp.where(valid, labels, 0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    klass = jax.lax.broadcasted_iota(
        safe_labels.dtype, logits.shape, logits.ndim - 1)
    gold = jnp.where(
        klass == safe_labels[..., None], logits, jnp.float32(0.0)
    ).sum(axis=-1)
    per_pix = (logz - gold) * valid
    return per_pix.sum() / jnp.maximum(valid.sum(), 1)


def next_token_xent(logits: jax.Array, tokens: jax.Array,
                    shift: int = 1) -> jax.Array:
    """Mean cross-entropy of ``logits[:, t]`` against ``tokens[:, t +
    shift]`` over the positions that have such a target, in float32.

    ``shift=1`` is the next-token loss; a multi-token-prediction head at
    depth ``k`` is scored with ``shift=k + 1``.  The sequence keeps its
    length (the targets are rolled and the tail masked), so the logits are
    never sliced to an unaligned size."""
    length = tokens.shape[1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    target = jnp.roll(tokens, -shift, axis=1)
    nll = -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
    valid = (jnp.arange(length) < length - shift)[None, :]
    return jnp.sum(jnp.where(valid, nll, 0.0)) / (
        tokens.shape[0] * (length - shift))


def weighted_token_xent(logits: jax.Array, tokens: jax.Array,
                        weights: jax.Array) -> jax.Array:
    """``(1 / (B·L)) Σ_i weights_i · (−log softmax(logits_i)[tokens_i])`` in
    float32, no shift: position ``i`` is scored against token ``i`` (a
    masked-diffusion objective: the weight is 0 on a token left clean and
    the inverse of its block's noise level on a masked one,
    ``ops/diffusion.py``)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    return jnp.sum(weights.astype(jnp.float32) * nll) / nll.size
