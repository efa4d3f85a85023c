"""Input pipelines: transform composition + per-host sharded batching.

This is the framework's replacement for *both* ends of the reference's data
story:

* the transform stacks at reference train_pascal.py:123-145 (train: flip →
  scale/rotate → crop+relax → 512² resize → n-ellipse+gaussian guidance →
  concat; val: deterministic guidance and full-res gt/void passthrough for
  full-image evaluation);
* the ``DataLoader(..., num_workers=2, shuffle, drop_last)`` host parallelism
  (train_pascal.py:161-162) **and** the distributed sampler the reference only
  planned (train_pascal.py:3) — here every host reads only its
  ``process_index``-th shard of each epoch's permutation, so a multi-host TPU
  job feeds disjoint data with no coordination.

Batches are dicts of stacked NHWC float32 numpy arrays, ready for
``jax.device_put`` (or ``jax.make_array_from_process_local_data`` under a
mesh).  Decoding/augmentation runs in a thread pool — cv2/PIL release the GIL
for the heavy ops — with a bounded prefetch queue so host work overlaps device
steps.
"""

from __future__ import annotations

import concurrent.futures as cf
import queue
import threading
from typing import Iterator, Sequence

import numpy as np

from ..telemetry import feed
from ..telemetry.trace import annotation
from ..telemetry.scopes import INPUT_BATCH
from . import transforms as T

#: default guidance channel, matching the live reference pipeline
GUIDANCE_KEY = "nellipseWithGaussians"


def build_crop_stage(
    crop_size: tuple[int, int],
    relax: int,
    zero_pad: bool,
    fused: bool = False,
    clamp: bool = True,
) -> list[T.Transform]:
    """The deterministic crop front shared by the train pipeline and the
    prepared-sample cache (data.prepared_cache) — ONE definition, so the
    cached bytes can never silently diverge from the live pipeline's.

    ``fused`` collapses crop + resize into one native-kernel pass
    (transforms.FusedCropResize); ``clamp`` bounds cubic-resize overshoot
    back into the [0,255] contract (needed whenever no uint8 cast sits
    upstream — the fused kernel resizes in float32 always).
    """
    if fused:
        return [
            T.FusedCropResize(crop_elems=("image", "gt"), mask_elem="gt",
                              relax=relax, zero_pad=zero_pad,
                              size=crop_size),
            *([T.ClampRange(("crop_image",))] if clamp else []),
        ]
    return [
        T.CropFromMaskStatic(crop_elems=("image", "gt"), mask_elem="gt",
                             relax=relax, zero_pad=zero_pad),
        T.FixedResize(resolutions={"crop_image": crop_size,
                                   "crop_gt": crop_size}),
        *([T.ClampRange(("crop_image",))] if clamp else []),
    ]


def build_train_transform(
    crop_size: tuple[int, int] = (512, 512),
    relax: int = 50,
    zero_pad: bool = True,
    rots: tuple[float, float] = (-20, 20),
    scales: tuple[float, float] = (0.75, 1.25),
    alpha: float = 0.6,
    guidance: str = "nellipse_gaussians",
    flip: bool = True,
    geom: bool = True,
    fused_crop_resize: bool = False,
) -> T.Compose:
    """The training augmentation stack (reference train_pascal.py:123-134).

    ``flip=False`` drops the host-side horizontal flip — used when the
    on-device augmentation stage (ops.augment) owns flipping instead;
    ``geom=False`` likewise drops the host ScaleNRotate when the device
    stage owns rotation/scale (ops.augment.random_scale_rotate — note the
    device form rotates the fixed-size crop rather than the full image).
    ``fused_crop_resize`` collapses the crop + resize pair into one native
    kernel pass (transforms.FusedCropResize) — same output contract, no
    materialized intermediate crop.
    """
    chain: list[T.Transform] = [
        *([T.RandomHorizontalFlip()] if flip else []),
        *([T.ScaleNRotate(rots=rots, scales=scales)] if geom else []),
        # with ScaleNRotate upstream its uint8 cast already bounds values,
        # so the non-fused path only clamps when geom is off
        *build_crop_stage(crop_size, relax, zero_pad,
                          fused=fused_crop_resize,
                          clamp=fused_crop_resize or not geom),
    ]
    chain += _guidance_stage(guidance, alpha, is_val=False)
    chain.append(T.ToArray())
    return T.Compose(chain)


def build_prepared_post_transform(
    rots: tuple[float, float] = (-20, 20),
    scales: tuple[float, float] = (0.75, 1.25),
    alpha: float = 0.6,
    guidance: str = "nellipse_gaussians",
    flip: bool = True,
    geom: bool = True,
    uint8_wire: bool = False,
    packbits: bool = False,
) -> T.Compose:
    """The per-epoch random stage downstream of the prepared-sample cache
    (data.prepared_cache): the cache already holds the deterministic
    decode→crop→resize output (``crop_image``/``crop_gt``), so only the
    random transforms run here — flip, scale/rotate *on the crop* (the
    device_augment_geom semantics: the warp sees the fixed-size crop, not
    the pre-crop full image), guidance synthesis, concat.  ``flip``/``geom``
    gate the host stages exactly like :func:`build_train_transform` when the
    on-device augmentation owns them instead.

    ``uint8_wire`` (data.uint8_transfer) keeps uint8 arrays uint8 through
    ``ToArray`` — with the uint8 cache upstream, ``concat``/``crop_gt``
    ship to the device at a quarter of the float32 bytes.  The terminal
    ``Keep`` prunes everything the step doesn't consume so ``collate``
    stops memcpy'ing dead intermediates.  ``packbits``
    (data.packbits_masks) additionally ships ``crop_gt`` at 1 bit/pixel
    (see :class:`~..data.transforms.PackBits`); the compiled step unpacks.
    """
    return T.Compose([
        *([T.RandomHorizontalFlip()] if flip else []),
        *([T.ScaleNRotate(rots=rots, scales=scales)] if geom else []),
        *_guidance_stage(guidance, alpha, is_val=False),
        T.ToArray(uint8_passthrough=uint8_wire),
        T.Keep(("concat", "crop_gt")),
        *([T.PackBits(("crop_gt",))] if packbits else []),
    ])


def build_prepared_eval_post_transform(
    alpha: float = 0.6,
    guidance: str = "nellipse_gaussians",
    uint8_wire: bool = False,
    packbits: bool = False,
) -> T.Compose:
    """Per-access stage downstream of the prepared EVAL cache
    (data.val_prepared): deterministic guidance (``is_val`` semantics,
    reference train_pascal.py:135-145) + concat + array conversion.  No
    random stages and no pruning — the cache itself appends the host-side
    metric keys (full-res ``gt``/``void_pixels``, ``bbox``) afterwards.

    With ``guidance='none'`` (the device-guidance fast path) ``concat`` is
    the bare uint8 image channels and the jitted eval step synthesizes the
    4th channel on device from ``crop_gt`` (ops.guidance_device,
    ``is_val=True`` — bit-exact vs the host at pert=0).

    The terminal ``Keep`` prunes the pre-concat intermediates (crop_image,
    the guidance map) so ``collate`` stops memcpy'ing them per batch; the
    cache appends its host-side metric keys AFTER this stage, so they are
    never at risk here."""
    return T.Compose([
        *_guidance_stage(guidance, alpha, is_val=True),
        T.ToArray(uint8_passthrough=uint8_wire),
        T.Keep(("concat", "crop_gt", "meta")),
        # data.packbits_masks: the binary crop_gt is 25% of the 3-channel
        # uint8 val batch; ship it at 1 bit/pixel (the eval step unpacks)
        *([T.PackBits(("crop_gt",))] if packbits else []),
    ])


def build_prepared_semantic_eval_post_transform(
    uint8_wire: bool = False,
) -> T.Compose:
    """Downstream of the prepared semantic cache at VAL: the cache already
    holds the entire deterministic crop-res eval protocol (resize image
    cubic + gt nearest + clamp, matching build_semantic_eval_transform up
    to the cache's uint8 rounding of the image — class ids stay exact), so
    only the contract rename remains."""
    return T.Compose([
        T.Rename({"image": "concat", "gt": "crop_gt"}),
        T.ToArray(uint8_passthrough=uint8_wire),
        T.Keep(("concat", "crop_gt", "meta")),
    ])


def build_eval_transform(
    crop_size: tuple[int, int] = (512, 512),
    relax: int = 50,
    zero_pad: bool = True,
    alpha: float = 0.6,
    guidance: str = "nellipse_gaussians",
    keep_fullres: bool = True,
) -> T.Compose:
    """The validation stack (reference train_pascal.py:135-145): deterministic
    guidance; ``gt``/``void_pixels`` kept at full resolution (``None`` in the
    resize map) so the evaluator can paste predictions back and score against
    the original-size mask."""
    resolutions = {"crop_image": crop_size, "crop_gt": crop_size}
    if keep_fullres:
        resolutions.update({"gt": None, "void_pixels": None})
    chain: list[T.Transform] = [
        T.CropFromMaskStatic(crop_elems=("image", "gt"), mask_elem="gt",
                             relax=relax, zero_pad=zero_pad),
        T.FixedResize(resolutions=resolutions),
        # the val stack has no uint8 cast upstream of the cubic resize, so
        # the [0,255] input contract (reference train_pascal.py:239-241
        # asserts it in the val loop too) needs an explicit clamp
        T.ClampRange(("crop_image",)),
    ]
    chain += _guidance_stage(guidance, alpha, is_val=True)
    chain.append(T.ToArray())
    return T.Compose(chain)


def _guidance_stage(guidance: str, alpha: float, is_val: bool) -> list[T.Transform]:
    """Guidance channel family selector; 'nellipse_gaussians' is the live
    reference path, the others are its inventoried alternatives."""
    if guidance == "nellipse_gaussians":
        return [
            T.NEllipseWithGaussians(alpha=alpha, is_val=is_val),
            T.ConcatInputs(elems=("crop_image", GUIDANCE_KEY)),
        ]
    if guidance == "nellipse":
        return [
            T.NEllipse(is_val=is_val),
            T.ConcatInputs(elems=("crop_image", "nellipse")),
        ]
    if guidance == "extreme_points":
        return [
            T.ExtremePoints(sigma=10, pert=0 if is_val else 5, elem="crop_gt",
                            is_val=is_val),
            T.ConcatInputs(elems=("crop_image", "extreme_points")),
        ]
    if guidance in ("confidence_l1l2", "confidence_gaussian"):
        # The reference's commented confidence-map alternative
        # (custom_transforms.py:253-298, driver lines 132/143): the transform
        # appends the map to the image itself -> rename onto the contract.
        return [
            T.AddConfidenceMap(elem="crop_image",
                               hm_type=guidance.removeprefix("confidence_"),
                               pert=0 if is_val else 5, is_val=is_val),
            T.Rename({"with_hm": "concat"}),
        ]
    if guidance == "none":
        return [T.ConcatInputs(elems=("crop_image",))]
    raise ValueError(f"unknown guidance family: {guidance}")


def build_semantic_train_transform(
    crop_size: tuple[int, int] = (513, 513),
    rots: tuple[float, float] = (-10, 10),
    scales: tuple[float, float] = (0.5, 2.0),
    flip: bool = True,
    geom: bool = True,
) -> T.Compose:
    """Multi-class semantic pipeline (the DeepLabV3 configs of BASELINE.json):
    flip -> scale/rotate with nearest-warped class ids (``semseg=True``) ->
    fixed resize (gt nearest, 255 void preserved in-band) -> rename onto the
    step contract (``concat``/``crop_gt``).

    ``flip=False`` drops the host flip when the on-device augmentation
    stage owns it (``data.device_augment``); ``geom=False`` likewise drops
    the host ScaleNRotate for ``data.device_augment_geom``.
    """
    return T.Compose([
        *([T.RandomHorizontalFlip()] if flip else []),
        *([T.ScaleNRotate(rots=rots, scales=scales, semseg=True)]
          if geom else []),
        T.FixedResize(resolutions={"image": crop_size, "gt": crop_size},
                      flagvals={"image": None, "gt": 0}),
        # cubic resize overshoots at contrast edges; the [0,255] input
        # contract (and its debug assert) needs the explicit clamp here
        # just like the instance chains
        T.ClampRange(("image",)),
        T.Rename({"image": "concat", "gt": "crop_gt"}),
        T.ToArray(),
    ])


def build_prepared_semantic_post_transform(
    rots: tuple[float, float] = (-10, 10),
    scales: tuple[float, float] = (0.5, 2.0),
    flip: bool = True,
    geom: bool = True,
    uint8_wire: bool = False,
) -> T.Compose:
    """Per-epoch random stage downstream of the semantic prepared cache:
    flip + scale/rotate on the already-resized arrays (nearest-warped class
    ids, 255-void border), renamed onto the step contract.  Mirrors
    :func:`build_prepared_post_transform` for the semantic task."""
    return T.Compose([
        *([T.RandomHorizontalFlip()] if flip else []),
        *([T.ScaleNRotate(rots=rots, scales=scales, semseg=True)]
          if geom else []),
        T.Rename({"image": "concat", "gt": "crop_gt"}),
        T.ToArray(uint8_passthrough=uint8_wire),
        T.Keep(("concat", "crop_gt")),
    ])


def build_semantic_eval_transform(
    crop_size: tuple[int, int] = (513, 513),
    keep_fullres: bool = False,
) -> T.Compose:
    """Deterministic semantic eval: fixed resize only (gt nearest so class
    ids and 255-void stay exact), renamed onto the step contract.

    ``keep_fullres`` preserves the ORIGINAL-resolution gt as ``gt_full``
    (ragged, host-side) so the evaluator can score mIoU at each image's
    native size — the standard DeepLab protocol — instead of at the
    resized crop (the instance pipeline keeps full-res gt the same way,
    reference train_pascal.py:138)."""
    res: dict = {"image": crop_size, "gt": crop_size}
    flags: dict = {"image": None, "gt": 0}
    chain: list[T.Transform] = []
    if keep_fullres:
        chain.append(T.Duplicate({"gt": "gt_full"}))
        res["gt_full"] = None   # passthrough, survives the pruning rule
        flags["gt_full"] = 0
    chain += [
        T.FixedResize(resolutions=res, flagvals=flags),
        T.ClampRange(("image",)),  # cubic-overshoot clamp, as in train
        T.Rename({"image": "concat", "gt": "crop_gt"}),
        T.ToArray(),
    ]
    return T.Compose(chain)


# ---------------------------------------------------------------------------
# batching / sharding
# ---------------------------------------------------------------------------

#: keys that stay python lists in a batch (metadata; exact match — a substring
#: test would wrongly catch 'vo*id*_pixels', see transforms._is_meta)
_NO_STACK_KEYS = ("meta", "id", "crop_relax")


def sample_rng(seed: int, epoch: int, index: int) -> np.random.Generator:
    """THE per-sample RNG policy: ``default_rng((seed, epoch, index))``.

    Single source of truth — both this module's ``DataLoader`` and the
    grain loader derive sample randomness here, which is what makes their
    samples bit-identical regardless of worker/host count."""
    return np.random.default_rng((seed, epoch, int(index)))


def collate(samples: Sequence[dict]) -> dict:
    """Stack a list of dict samples into a dict batch.

    Fixed-shape keys stack on a new leading batch axis; ragged keys (full-res
    ``gt``/``void_pixels`` at val, whose size varies per image) and metadata
    stay as lists — they are consumed host-side by the evaluator, never
    shipped to the device.
    """
    out: dict = {}
    for key in samples[0]:
        vals = [s[key] for s in samples]
        if key in _NO_STACK_KEYS:
            out[key] = vals
            continue
        shapes = {np.asarray(v).shape for v in vals}
        if len(shapes) == 1:
            out[key] = np.stack([np.asarray(v) for v in vals])
        else:
            out[key] = vals
    return out


class DataLoader:
    """Sharded, shuffling, prefetching batch iterator over a random-access
    dataset.

    One instance per host: with ``num_shards = jax.process_count()`` and
    ``shard_index = jax.process_index()``, each host walks only its slice of
    the epoch permutation — the "distributed loader sampler" item of the
    reference's DDP checklist (train_pascal.py:3), done the JAX way.

    Every sample's RNG is ``default_rng((seed, epoch, index))``; shuffling is
    ``default_rng((seed, epoch))`` over the global index set — identical data
    order regardless of worker count or host count.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        shuffle: bool = False,
        drop_last: bool = False,
        seed: int = 0,
        num_workers: int = 2,
        shard_index: int = 0,
        num_shards: int = 1,
        prefetch: int = 2,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.seed = seed
        self.num_workers = max(0, num_workers)
        self.shard_index = shard_index
        self.num_shards = num_shards
        self.prefetch = prefetch
        self.epoch = 0
        self.start_batch = 0

    def set_epoch(self, epoch: int, start_batch: int = 0) -> None:
        """Position the loader; ``start_batch`` skips that many batches of
        the epoch's (deterministic) order — the exact-mid-epoch-resume hook
        (a resumed run continues where the preempted one stopped instead of
        replaying the epoch).  ``__len__`` still reports the full epoch so
        schedules and resume math are unaffected."""
        self.epoch = epoch
        self.start_batch = start_batch

    def _epoch_indices(self, epoch: int | None = None) -> np.ndarray:
        epoch = self.epoch if epoch is None else int(epoch)
        n = len(self.dataset)
        order = np.arange(n)
        if self.shuffle:
            np.random.default_rng((self.seed, epoch)).shuffle(order)
        if self.num_shards > 1:
            # Pad the permutation (wrap-around) to a multiple of num_shards so
            # every sample lands in some shard and all shards are equal-length
            # — uneven shards would desynchronize collective step counts, and
            # truncation would silently drop the tail from evaluation.
            per_shard = -(-n // self.num_shards)
            total = per_shard * self.num_shards
            if total > n:
                order = np.concatenate([order, order[: total - n]])
            order = order[self.shard_index * per_shard : (self.shard_index + 1) * per_shard]
        return order

    def _num_batches(self, n_indices: int) -> int:
        if self.drop_last:
            return n_indices // self.batch_size
        return (n_indices + self.batch_size - 1) // self.batch_size

    def batch_sample_indices(self, batch_index: int,
                             epoch: int | None = None) -> np.ndarray:
        """Dataset indices of batch ``batch_index`` in ``epoch``'s
        deterministic order (the current epoch when None) — the O(1)
        batch -> samples resolution the sentinel's quarantine ledger and
        the packed-source ``seek`` integration use.  Indexes the FULL
        epoch order: ``start_batch`` offsets never shift it, so a batch
        index quarantined mid-run names the same samples on replay.
        Pure function of ``epoch`` — never mutates loader state, so it
        is safe while a prefetch producer is mid-epoch."""
        order = self._epoch_indices(epoch)
        lo = int(batch_index) * self.batch_size
        return order[lo:lo + self.batch_size]

    def __len__(self) -> int:
        return self._num_batches(len(self._epoch_indices()))

    def _load_one(self, index: int) -> dict:
        rng = sample_rng(self.seed, self.epoch, index)
        return self.dataset.__getitem__(int(index), rng=rng)

    def __iter__(self) -> Iterator[dict]:
        order = self._epoch_indices()
        nb = self._num_batches(len(order))
        batches = [order[i * self.batch_size : (i + 1) * self.batch_size] for i in range(nb)]
        if self.start_batch:
            # index-level skip: the skipped batches cost nothing (no decode)
            batches = batches[self.start_batch:]
        if self.num_workers == 0:
            for b, idxs in enumerate(batches, self.start_batch):
                # no producer: every batch is built while the consumer waits
                feed.COUNTS.batch += 1
                with annotation(INPUT_BATCH, batch=b):
                    batch = collate([self._load_one(i) for i in idxs])
                yield batch
            return
        yield from self._iter_prefetched(batches)

    def _iter_prefetched(self, batches: list[np.ndarray]) -> Iterator[dict]:
        # The queue itself is unbounded; the prefetch bound is enforced
        # below against the LIVE ``self.prefetch`` so the feed governor's
        # hot resize (data/governor.py rung 1) takes effect mid-epoch:
        # growing admits more batches immediately, shrinking just waits
        # for the consumer to drain below the new bound — a shrink can
        # never strand an already-full queue (queue.Queue's maxsize is
        # fixed at construction, which is exactly why it isn't used as
        # the bound here).
        out_q: queue.Queue = queue.Queue()
        sentinel = object()
        stop = threading.Event()
        # admission is condition-notified, not polled: the consumer's get
        # wakes the producer the instant a slot drains (the latency a
        # timed poll would add lands straight in input_wait); the wait
        # timeout only backstops a bound grown by the governor while the
        # consumer sits idle (no get, so no notify)
        room = threading.Condition()

        def put_bounded(item) -> bool:
            """Bounded put that gives up when the consumer is gone — an
            abandoned iterator (early break / exception in the train loop)
            must not leave the producer blocked forever at the bound."""
            with room:
                while not stop.is_set():
                    if out_q.qsize() < max(1, int(self.prefetch)):
                        # single producer: qsize only shrinks
                        # concurrently, so the bound check cannot
                        # over-admit.  out_q is UNbounded (the condvar
                        # IS the bound), so the put cannot block:
                        out_q.put(item)  # jaxrace: disable=JR004
                        return True
                    room.wait(0.1)
            return False

        def producer():
            with cf.ThreadPoolExecutor(max_workers=self.num_workers) as pool:
                try:
                    for b, idxs in enumerate(batches, self.start_batch):
                        if stop.is_set():
                            return
                        # the feed's time busy: first sample to collate,
                        # not the wait for room in the queue after it
                        with annotation(INPUT_BATCH, batch=b):
                            batch = collate(
                                list(pool.map(self._load_one, idxs)))
                        if not put_bounded(batch):
                            return
                except BaseException as e:  # surface worker errors to consumer
                    # UNbounded put: an error must reach the consumer
                    # promptly even when the queue sits at the prefetch
                    # bound — waiting for drain here is how a producer
                    # death turns into a consumer deadlock
                    out_q.put(e)
                finally:
                    out_q.put(sentinel)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                # whom the loop waits on: a queue found empty here is a
                # batch the producer had not built when its consumer came
                ready = not out_q.empty()
                item = out_q.get()
                with room:
                    room.notify()
                if item is sentinel:
                    break
                if isinstance(item, BaseException):
                    raise item
                feed.COUNTS.batch += 1
                feed.COUNTS.batch_ready += ready
                yield item
        finally:
            stop.set()
            with room:
                room.notify()
            t.join()
