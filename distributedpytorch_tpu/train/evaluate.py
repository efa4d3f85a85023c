"""Validation: threshold-swept Jaccard with full-resolution paste-back.

The reference's val loop (train_pascal.py:233-308): per sample, sigmoid the
fused output, paste the 512² crop-space prediction back into full-image
coordinates (``crop2fullmask`` with the same bbox/relax the crop used),
binarize at thresholds {0.3, 0.5, 0.8} and score IoU against the *full-res*
ground truth with void-pixel exclusion; report the per-threshold means and
gate "best" on the max.

TPU split of labour: the model forward runs batched/jitted on device (the
reference ran val through ``DataParallel`` too, :245); the paste-back is
inherently ragged (every image has its own size, :286-291) so it stays
host-side numpy per sample — overlap comes from the loader's prefetch.

The reference's ``relaxes[jj]`` latent bug (indexing a 1-element list by
batch position, safe only because ``testBatch=1``, SURVEY.md §2.1) is not
reproduced: the relax is taken from the sample's own crop metadata.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.metrics import np_jaccard_thresholds
from ..parallel import INPUT_KEY, pad_to_multiple, shard_batch
from ..telemetry import span
from ..utils.helpers import crop2fullmask, get_bbox, tens2image


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-x))


def _local_rows(arr) -> np.ndarray:
    """Host-local rows of a (possibly globally-sharded) batch-dim array.

    Multi-host, the eval outputs are sharded over all processes and
    ``device_get`` of the global array would fail (not fully addressable);
    each host fetches exactly its own shard rows — which are the outputs for
    the samples its loader shard contributed, in order."""
    if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)
    return np.asarray(jax.device_get(arr))


def _as_list(v, n: int) -> list:
    """Batch entry -> per-sample list (stacked array or already a list)."""
    if isinstance(v, np.ndarray) and v.ndim >= 1 and v.shape[0] == n:
        return [v[i] for i in range(n)]
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n


def evaluate(
    eval_step: Callable,
    state,
    loader,
    thresholds: Sequence[float] = (0.3, 0.5, 0.8),
    relax: int = 50,
    zero_pad: bool = True,
    mesh=None,
    max_batches: int | None = None,
    debug_asserts: bool = False,
    packed_masks: bool = False,
    bf16_readback: bool = False,
) -> dict:
    """Run the full validation protocol; returns a metrics dict.

    ``loader`` yields batches with device keys (``concat``/``crop_gt``) plus
    host-side full-res ``gt``/``void_pixels`` (kept by the eval transform's
    ``None`` resolutions, reference train_pascal.py:138).

    ``debug_asserts`` re-enables the reference's per-batch data-contract
    checks in the val loop too (train_pascal.py:239-241 asserted in BOTH
    loops).
    """
    thresholds = tuple(thresholds)
    jac_sum = np.zeros(len(thresholds))
    n_samples = 0
    losses: list = []  # device scalars; ONE bulk readback at epoch end
    first_batch_vis = None
    t0 = time.perf_counter()

    n_dev = mesh.devices.size if mesh is not None else 1

    def forwarded():
        """One-batch look-ahead: dispatch batch i+1's forward BEFORE
        materializing batch i's outputs, so the per-sample host paste-back
        below overlaps the next forward's device compute (eval is
        dispatch-bound at the reference's bs=1 protocol).  ``eval_step``
        is async — holding its un-materialized outputs costs nothing."""
        prev = None
        for bi, batch in enumerate(loader):
            if max_batches is not None and bi >= max_batches:
                break
            if debug_asserts:
                batch_debug_asserts(batch, packed_masks=packed_masks)
            device_keys = {k: v for k, v in batch.items()
                           if k in (INPUT_KEY, "crop_gt", "crop_void")}
            padded, _ = pad_to_multiple(device_keys, n_dev)
            if mesh is not None:
                padded = shard_batch(mesh, padded)
            with span("eval/dispatch"):  # async: launch cost, not compute
                outputs, loss = eval_step(state, padded)
            # deferred: float(loss) here would add a host sync per val
            # batch on top of the outputs fetch — the same stall
            # train_epoch's bulk readback fixed
            losses.append(loss)
            if prev is not None:
                yield prev
            prev = (batch, outputs)
        if prev is not None:
            yield prev

    for batch, outputs in forwarded():
        n = batch[INPUT_KEY].shape[0]
        # primary head only; ragged paste-back per sample on host.
        # bf16_readback (eval_bf16_probs): cast the logit volume to bf16
        # ON DEVICE before the D2H fetch — half the val readback bytes
        # (same policy the semantic full-res path uses); threshold-level
        # effects are boundary-pixel rounding only (tested).
        raw = outputs[0]
        if bf16_readback and isinstance(raw, jax.Array):
            raw = raw.astype(jnp.bfloat16)
        probs = _sigmoid(
            _local_rows(raw)[:n].astype(np.float32, copy=False))
        if first_batch_vis is None:
            vis_batch = batch
            if packed_masks:
                # panels overlay crop_gt on the image; hand them the
                # unpacked mask, not the 1-bit wire row
                h, w = np.asarray(batch[INPUT_KEY]).shape[1:3]
                gt_bits = np.asarray(batch["crop_gt"])
                vis_batch = dict(batch)
                vis_batch["crop_gt"] = np.unpackbits(
                    gt_bits, axis=-1, count=h * w).reshape(n, h, w)
            first_batch_vis = {
                "batch": vis_batch,
                "outputs": [_local_rows(o)[:n] for o in outputs],
            }
        gts = _as_list(batch["gt"], n)
        voids = _as_list(batch.get("void_pixels", [None] * n), n)
        bboxes = _as_list(batch["bbox"], n) if "bbox" in batch else [None] * n
        # the ragged host half of the protocol, named in traces so a
        # paste-back-bound eval shows up as itself, not as device idle
        with span("eval/pasteback"):
            for j in range(n):
                gt = tens2image(np.asarray(gts[j]))
                void = None if voids[j] is None \
                    else tens2image(np.asarray(voids[j]))
                if gt.max() <= 0.5:  # empty gt: pred-empty is IoU 1, else 0
                    for ti, th in enumerate(thresholds):
                        jac_sum[ti] += float(not (probs[j] > th).any())
                    n_samples += 1
                    continue
                # Prefer the bbox the crop transform recorded for this
                # sample — guaranteed to be the exact box the crop was taken
                # from; only recompute (with this function's relax/zero_pad)
                # when absent.
                if bboxes[j] is not None:
                    bbox = tuple(int(v) for v in np.asarray(bboxes[j]))
                else:
                    bbox = get_bbox(gt > 0.5, pad=relax, zero_pad=zero_pad)
                pred = tens2image(probs[j])
                full = crop2fullmask(pred, bbox, gt.shape[:2],
                                     zero_pad=zero_pad, relax=relax)
                # all thresholds in one pass (digitize + bincount) — the
                # scoring half of the host paste-back no longer scales with
                # the threshold count
                jac_sum += np_jaccard_thresholds(full, thresholds,
                                                 gt > 0.5, void)
                n_samples += 1

    loss_sum = float(np.sum(jax.device_get(losses))) if losses else 0.0
    n_batches = len(losses)
    # Multi-host: every process evaluated only its loader shard; reduce the
    # raw sums across processes so all hosts hold identical global metrics —
    # the best-checkpoint gate must not diverge (the collective best-save
    # would deadlock if some hosts skipped it).
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        packed = np.concatenate([jac_sum,
                                 [n_samples, loss_sum, n_batches]])
        summed = np.asarray(
            multihost_utils.process_allgather(packed)).sum(axis=0)
        jac_sum = summed[:len(thresholds)]
        n_samples = int(summed[-3])
        loss_sum = float(summed[-2])
        n_batches = int(summed[-1])

    jac_avg = (jac_sum / max(n_samples, 1)).tolist()
    best_i = int(np.argmax(jac_avg))
    return {
        "loss": loss_sum / max(n_batches, 1),
        "jaccard_per_threshold": dict(zip(map(str, thresholds), jac_avg)),
        "jaccard": jac_avg[best_i],          # threshold-max mean IoU
        "best_threshold": thresholds[best_i],
        "n_samples": n_samples,
        "seconds": time.perf_counter() - t0,
        "_first_batch": first_batch_vis,     # for visualization panels
    }


def batch_debug_asserts(batch: Mapping[str, np.ndarray],
                        packed_masks: bool = False) -> None:
    """The reference's per-batch data-contract asserts
    (train_pascal.py:188-190), as an opt-in debug check rather than an
    always-on hot-loop cost: guidance/image channels within [0,255] and
    non-degenerate, gt strictly binary.

    With ``packed_masks`` (data.packbits_masks) the mask rides the wire at
    1 bit/pixel — binary by construction — so the gt check becomes
    structural: the packed row must be uint8 of exactly ceil(H*W/8) bytes
    for the batch's spatial shape."""
    x = np.asarray(batch[INPUT_KEY])
    assert x.min() >= 0.0 and x.max() <= 255.0, "input outside [0,255]"
    assert len(np.unique(x[..., :3])) > 2, "degenerate RGB channels"
    gt = np.asarray(batch["crop_gt"])
    if packed_masks:
        h, w = x.shape[1:3]
        expect = (h * w + 7) // 8
        assert gt.dtype == np.uint8 and gt.shape == (x.shape[0], expect), \
            f"packed gt shape/dtype off: {gt.shape} {gt.dtype}, " \
            f"expected ({x.shape[0]}, {expect}) uint8"
        return
    uniq = np.unique(gt)
    assert np.all(np.isin(uniq, (0.0, 1.0))), f"gt not binary: {uniq[:5]}"


def semantic_batch_debug_asserts(batch: Mapping[str, np.ndarray],
                                 nclass: int,
                                 ignore_index: int = 255) -> None:
    """Semantic-task counterpart of :func:`batch_debug_asserts`: image
    channels within [0,255] and non-degenerate, gt restricted to valid
    class ids plus the in-band void value."""
    x = np.asarray(batch[INPUT_KEY])
    assert x.min() >= 0.0 and x.max() <= 255.0, "input outside [0,255]"
    assert len(np.unique(x[..., :3])) > 2, "degenerate RGB channels"
    uniq = np.unique(np.asarray(batch["crop_gt"]))
    valid = np.concatenate([np.arange(nclass), [ignore_index]])
    assert np.all(np.isin(uniq, valid)), \
        f"gt ids outside 0..{nclass - 1} u {{{ignore_index}}}: {uniq[:8]}"


@functools.partial(jax.jit, static_argnums=(2, 3))
def _batch_confusion(outputs, labels, nclass: int, ignore_index: int):
    """argmax + confusion counts, compiled once per (nclass, ignore) pair
    (module-level so repeated eval epochs reuse the jit cache)."""
    import jax.numpy as jnp

    from ..ops.metrics import confusion_matrix

    pred = jnp.argmax(outputs, axis=-1)
    if labels.ndim == pred.ndim + 1:
        labels = labels[..., 0]
    return confusion_matrix(pred, labels, nclass, ignore_index)


def evaluate_semantic(
    eval_step: Callable,
    state,
    loader,
    nclass: int,
    ignore_index: int = 255,
    mesh=None,
    max_batches: int | None = None,
    tta_scales: tuple[float, ...] = (),
    tta_flip: bool = False,
    debug_asserts: bool = False,
    bf16_probs: bool = True,
    device_fullres: tuple[int, int] | None = None,
) -> dict:
    """Multi-class semantic validation: confusion-matrix mIoU.

    The metric for the DeepLabV3 configs of BASELINE.json ("val mIoU").  The
    argmax prediction and per-batch confusion counts are computed on device
    (one bincount — no NxC transfers); the (C, C) counts accumulate on host
    and reduce across processes, so the protocol is multi-host-safe the same
    way :func:`evaluate` is.

    ``tta_scales``/``tta_flip``: the standard DeepLab test-time-augmentation
    protocol — softmax probabilities averaged over the listed input scales
    (each a fixed shape, so each costs exactly one extra compiled program),
    with ``tta_flip`` adding the horizontal flip AT EVERY scale; argmax of
    the average.  The votes are exactly scales x flips as configured (a list
    omitting 1.0 does not vote the base pass); ``loss`` always reports the
    plain single-scale pass.  Empty/false = the plain protocol, on the
    unchanged fast path (device-side argmax, no NxC transfer).

    ``bf16_probs`` (config.eval_bf16_probs): the full-res and TTA protocols
    read whole softmax volumes back to the host — 22 MB/image in f32 at
    513²/21 classes.  bf16 on the wire halves that;
    probabilities are widened back to f32 on host before any resize/
    averaging arithmetic, so the only effect is one bf16 rounding of each
    probability — argmax-after-resize tie noise (tested against f32).

    ``device_fullres`` (config.eval_device_fullres; the (max_h, max_w) =
    ``data.val_max_im_size`` canvas when enabled): the non-TTA full-res
    protocol resizes per-sample to native size and argmaxes ON DEVICE
    (``ops.warp.fullres_argmax`` — a separable weight-matmul warp, no
    gathers) and ships only the uint8 class map: ~21x fewer D2H bytes
    than the bf16 probability volume and zero per-image host resizes
    (the host path's bound).
    Falls back to the host path per batch when an image exceeds the
    canvas, under TTA (the averaged probabilities already live on host),
    or multi-host.
    """
    import jax.numpy as jnp

    from .. import imaging
    from ..ops.metrics import miou_from_confusion
    from ..utils.helpers import fixed_resize

    def np_confusion(pred: np.ndarray, label: np.ndarray) -> np.ndarray:
        """Host-side (C, C) confusion, rows=true cols=pred — the ragged
        full-res twin of ops.metrics.confusion_matrix."""
        valid = label != ignore_index
        idx = label[valid].astype(np.int64) * nclass \
            + pred[valid].astype(np.int64)
        return np.bincount(idx, minlength=nclass * nclass) \
            .reshape(nclass, nclass)

    def fullres_confusion(probs: np.ndarray, gts_full: list) -> np.ndarray:
        """Per-sample: bilinear-resize class probabilities to the gt's
        native size, argmax, score — the standard DeepLab protocol (metric
        at ORIGINAL resolution, not the network's crop)."""
        out = np.zeros((nclass, nclass), np.int64)
        for j, gt in enumerate(gts_full):
            gt = np.asarray(gt)
            if gt.ndim == 3:
                gt = gt[..., 0]
            p = fixed_resize(probs[j], gt.shape[:2], flagval=imaging.LINEAR)
            out += np_confusion(np.argmax(p, axis=-1), gt)
        return out

    if len(set(tta_scales)) != len(tta_scales):
        raise ValueError(f"duplicate tta_scales {tta_scales} would "
                         "double-weight votes")
    n_dev = mesh.devices.size if mesh is not None else 1
    tta = bool(tta_flip or any(s != 1.0 for s in tta_scales))
    scale_list = list(tta_scales) if tta_scales else [1.0]
    conf = np.zeros((nclass, nclass), np.int64)
    confs: list = []   # device (C,C) counts; bulk-read at epoch end
    losses: list = []  # device scalars; same deferred-sync policy
    fullres_maps: list = []  # (device uint8 class maps, native gts);
    #                          scored host-side after the bulk readback
    n_samples = 0
    t0 = time.perf_counter()
    wire_dt = jnp.bfloat16 if bf16_probs else jnp.float32

    def read_probs(dev_probs) -> np.ndarray:
        """DEVICE softmax volume -> host f32, shipping ``wire_dt`` bytes.
        The cast must run ON DEVICE, before ``_local_rows`` does the
        device_get — casting the already-fetched numpy array would pay the
        bf16 rounding for zero wire savings."""
        host = _local_rows(dev_probs.astype(wire_dt))
        return host.astype(np.float32)

    def forward_probs(inp: np.ndarray, gt: np.ndarray):
        """One padded+sharded eval pass -> (softmax probs for the n real
        rows, loss).  Softmax runs on device; one D2H transfer."""
        padded, _ = pad_to_multiple({INPUT_KEY: inp, "crop_gt": gt}, n_dev)
        if mesh is not None:
            padded = shard_batch(mesh, padded)
        outputs, loss = eval_step(state, padded)
        probs = jax.nn.softmax(
            jnp.asarray(outputs[0]).astype(jnp.float32), axis=-1)
        return read_probs(probs)[: inp.shape[0]], loss

    for bi, batch in enumerate(loader):
        if max_batches is not None and bi >= max_batches:
            break
        if debug_asserts:
            semantic_batch_debug_asserts(batch, nclass, ignore_index)
        n = batch[INPUT_KEY].shape[0]
        n_samples += n
        if not tta:
            device_keys = {k: v for k, v in batch.items()
                           if k in (INPUT_KEY, "crop_gt")}
            padded, _ = pad_to_multiple(device_keys, n_dev)
            if mesh is not None:
                padded = shard_batch(mesh, padded)
            outputs, loss = eval_step(state, padded)
            losses.append(loss)
            # Padding repeats real samples; drop them from the counts by
            # scoring only the first n rows (host-local multi-host).
            if "gt_full" in batch:  # native-resolution protocol
                gts_full = [np.asarray(g) for g in
                            _as_list(batch["gt_full"], n)]
                hw = np.array([g.shape[:2] for g in gts_full], np.int32)
                # softmax on DEVICE either way (no host-side exp/sum over
                # B*H*W*C stalling the loop)
                probs_dev = jax.nn.softmax(
                    jnp.asarray(outputs[0]).astype(jnp.float32), axis=-1)
                if (device_fullres is not None
                        and jax.process_count() == 1
                        and hw[:, 0].max() <= device_fullres[0]
                        and hw[:, 1].max() <= device_fullres[1]):
                    # resize-to-native + argmax on device; only the uint8
                    # class map crosses the wire.  Padding rows get a 1x1
                    # target — never scored.
                    from ..ops.warp import fullres_argmax
                    hw_pad = np.ones((probs_dev.shape[0], 2), np.int32)
                    hw_pad[:n] = hw
                    # deferred: the uint8 maps stay on device until the
                    # epoch-end bulk readback (same policy as losses/confs)
                    # so the next batch's forward overlaps this one's warp
                    fullres_maps.append((fullres_argmax(
                        probs_dev, jnp.asarray(hw_pad),
                        tuple(device_fullres)), gts_full))
                else:
                    conf += fullres_confusion(read_probs(probs_dev)[:n],
                                              gts_full)
            elif jax.process_count() == 1:
                # crop-res fast path, single process: argmax + bincount on
                # DEVICE from the still-resident outputs — only the (C,C)
                # counts ever cross the wire.  (The previous _local_rows
                # round trip shipped the full B·H·W·C logits volume DOWN
                # and straight back UP per batch — 2×84 MB at 513²/21
                # classes, the measured 1 img/s semantic-val bound.)
                confs.append(_batch_confusion(
                    jnp.asarray(outputs[0])[:n],
                    jnp.asarray(padded["crop_gt"])[:n],
                    nclass, ignore_index))
            else:
                # multi-host: each process scores its own shard rows; the
                # (C,C) counts are allgather-summed at the end
                out0 = _local_rows(outputs[0])[:n]
                labels = _local_rows(padded["crop_gt"])[:n]
                confs.append(_batch_confusion(
                    jnp.asarray(out0), jnp.asarray(labels), nclass,
                    ignore_index))
            continue

        inp = np.asarray(batch[INPUT_KEY])
        gt = np.asarray(batch["crop_gt"])
        h, w = inp.shape[1:3]
        # the plain pass always runs — it is THE reported loss; it votes
        # only if 1.0 is a configured scale
        base_probs, loss = forward_probs(inp, gt)
        losses.append(loss)
        probs = np.zeros_like(base_probs)
        votes = 0
        for s in scale_list:
            if s == 1.0:
                inp_s, gt_s = inp, gt
                p = base_probs
            else:
                hs, ws = max(1, round(h * s)), max(1, round(w * s))
                inp_s = np.stack([
                    fixed_resize(im, (hs, ws), flagval=imaging.LINEAR)
                    for im in inp])
                gt_s = np.stack([
                    fixed_resize(g, (hs, ws), flagval=imaging.NEAREST)
                    for g in gt])
                p_s, _ = forward_probs(inp_s, gt_s)
                p = np.stack([
                    fixed_resize(pp, (h, w), flagval=imaging.LINEAR)
                    for pp in p_s])
            probs += p
            votes += 1
            if tta_flip:
                p_f, _ = forward_probs(inp_s[:, :, ::-1], gt_s[:, :, ::-1])
                p_f = p_f[:, :, ::-1]
                if s != 1.0:
                    p_f = np.stack([
                        fixed_resize(pp, (h, w), flagval=imaging.LINEAR)
                        for pp in p_f])
                probs += p_f
                votes += 1
        avg = probs / votes
        if "gt_full" in batch:  # TTA composes with the native-res protocol
            conf += fullres_confusion(avg, _as_list(batch["gt_full"], n))
        else:
            confs.append(_batch_confusion(
                jnp.asarray(avg), jnp.asarray(gt), nclass, ignore_index))

    with span("eval/readback"):  # the epoch-end bulk D2H sync, named
        if confs:  # one bulk readback for every deferred device value
            conf += np.sum(np.asarray(jax.device_get(confs), np.int64),
                           axis=0)
        for dev_maps, gts in fullres_maps:
            maps = np.asarray(jax.device_get(dev_maps))
            for j, g in enumerate(gts):
                if g.ndim == 3:
                    g = g[..., 0]
                conf += np_confusion(maps[j, :g.shape[0], :g.shape[1]], g)
        loss_sum = float(np.sum(jax.device_get(losses))) if losses else 0.0
    n_batches = len(losses)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        gathered = multihost_utils.process_allgather(
            jnp.asarray(conf, jnp.int64))
        conf = np.asarray(gathered).sum(axis=0)
        packed = np.array([loss_sum, n_batches, n_samples])
        summed = np.asarray(
            multihost_utils.process_allgather(packed)).sum(axis=0)
        loss_sum, n_batches = float(summed[0]), int(summed[1])
        n_samples = int(summed[2])

    out = miou_from_confusion(conf)
    out.update({
        "loss": loss_sum / max(n_batches, 1),
        "jaccard": out["miou"],        # uniform best-checkpoint gate key
        "n_samples": n_samples,
        "seconds": time.perf_counter() - t0,
    })
    return out
