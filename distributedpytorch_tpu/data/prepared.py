"""Prepared-sample disk cache: decode→crop→resize stored once, mmap-read after.

LEGACY prepared format: the packed data plane (``data/packed.py``,
``dptpu-pack``) is the ONE prepared format going forward — it
pre-decodes the whole source (not just the crop front), checksums every
record, shards reads by host and gives the governor/sentinel O(1) seek.
Configs setting ``data.prepared_cache`` get a loud migration pointer at
trainer build.  These wrappers still work — and compose OVER a packed
source (``data.source=packed`` + ``prepared_cache``) when caching the
deterministic crop stage on top is still wanted.

The end-to-end bound on a weak host is the deterministic front of the train
pipeline — JPEG/PNG decode, mask-bbox crop, fixed resize (~19
fresh imgs/s e2e vs a ~65 imgs/s chip, 2026-07 session).  That front is *identical every
epoch*: given the sample and the crop config it has no randomness.  So run
it once, store the result compactly on disk, and serve every later epoch
from an ``np.memmap`` read — the FFCV recipe (PAPERS.md) applied to the
reference's host pipeline (/root/reference/train_pascal.py:123-134,
pascal.py:232-263).

What is cached per sample (all fixed-shape):

* ``crop_image`` — (H, W, 3) uint8 (the [0,255] contract of reference
  train_pascal.py:188 makes uint8 lossless up to rounding);
* ``crop_gt``   — H·W bits, ``np.packbits`` of the binary mask (32 KB for a
  512² crop instead of 1 MB float32);
* ``bbox``      — the (relaxed) crop box, for eval-style paste-back;
* ``im_size``   — the source image's (H, W), reconstructing ``meta``.

Randomness is *not* cached: flip / scale-rotate / guidance synthesis run
per epoch downstream of the cache (``post_transform``), so augmentation
stays fresh.  Consequence, stated plainly: the random geometric stage
operates on the fixed-size *crop* rather than the pre-crop full image —
the same semantics as the on-device augmentation path
(``data.device_augment_geom``); the flip commutes with the crop exactly
(zero-padded boxes are symmetric), the rotation does not (pixels that a
full-image rotation would bring into the crop window are zeros here).

Cache identity: a fingerprint over the dataset identity and every config
knob that changes the cached bytes (crop size, relax, zero_pad, fused
kernel, imaging backend).  Each fingerprint gets its own subdirectory, so
changing the config *invalidates by construction* — a new config simply
builds a new cache and never reads stale rows.

Concurrency: rows are written at distinct offsets (one row per sample
index) with a ``valid`` byte flipped after the row lands; racing fillers
(loader threads, grain worker processes) recompute the same deterministic
bytes, so last-writer-wins is idempotent.  The memmaps are reopened after
pickling (grain workers) rather than shipped.
"""

from __future__ import annotations

import fcntl
import hashlib
import json
import os

import numpy as np

from .. import imaging
from . import transforms as T

#: bump when the cached layout/semantics change
_FORMAT_VERSION = 1


def _content_stamp(dataset) -> list:
    """Cheap content probe of the underlying files: (path, size, mtime_ns)
    of a handful of the dataset's image AND label files.  Catches a dataset
    *regenerated in place* with the same name/split/count (same ``str`` and
    ``len``) but different pixels/labels — which the identity fields alone
    would silently alias to stale cached rows."""
    if hasattr(dataset, "datasets"):  # CombinedDataset: walk constituents
        return [s for ds in dataset.datasets for s in _content_stamp(ds)]
    stamp = []
    # every file-list attribute the dataset classes expose: images, the
    # instance/semantic label files (masks/categories/labels)
    for attr in ("images", "masks", "categories", "labels"):
        paths = getattr(dataset, attr, None)
        if not isinstance(paths, list) or not paths \
                or not isinstance(paths[0], str):
            continue
        for p in {paths[0], paths[len(paths) // 2], paths[-1]}:
            try:
                st = os.stat(p)
                stamp.append([p, st.st_size, st.st_mtime_ns])
            except OSError:
                stamp.append([p, -1, -1])
    return sorted(stamp)


def cache_fingerprint(dataset, crop_size, relax: int, zero_pad: bool,
                      fused_crop_resize: bool) -> str:
    """Identity of the cached bytes: dataset + every knob that changes them.

    ``str(dataset)`` covers splits/area-thres (VOC/SBD ``__str__`` encode
    them); ``len`` catches a changed instance list under the same name; the
    content stamp catches same-name same-count regenerated files; the
    imaging backend matters because cv2 and the native kernels differ in
    the last ulp of cubic taps.
    """
    ident = json.dumps({
        "format": _FORMAT_VERSION,
        "dataset": str(dataset),
        "n": len(dataset),
        "content": _content_stamp(dataset),
        "crop_size": list(crop_size),
        "relax": int(relax),
        "zero_pad": bool(zero_pad),
        "fused_crop_resize": bool(fused_crop_resize),
        "imaging_backend": imaging.backend(),
    }, sort_keys=True)
    return hashlib.sha256(ident.encode()).hexdigest()[:16]


def _needs_init(meta_path: str, expect_meta: dict) -> bool:
    """True when the cache layout must be (re)created: meta.json missing,
    unreadable, or describing a different layout than ``expect_meta``."""
    if not os.path.isfile(meta_path):
        return True
    try:
        with open(meta_path) as f:
            return json.load(f) != expect_meta
    except (ValueError, OSError):
        return True


def _open_maps(cache_dir: str, expect_meta: dict, layout) -> dict:
    """Open (or create/reset) the cache's memmaps under ``cache_dir``.

    ``expect_meta`` mismatching the stored meta.json resets every file —
    and the valid map is (re)created LAST so a half-written images file
    from a crashed builder is never trusted.

    Creation is serialized across processes with an exclusive ``flock``:
    two racing openers (grain workers, concurrent runs) that both observe a
    missing/stale meta.json would otherwise both recreate the files with
    ``mode='w+'``, each truncating rows the other had already written —
    including a window where one process's valid byte survives a zeroed
    data file.  The second opener re-checks freshness *under the lock* and
    finds the first's meta.json already landed.  ``flock`` (not O_EXCL) so
    a crashed creator's lock is released by the kernel, never left stale.
    """
    os.makedirs(cache_dir, exist_ok=True)
    meta_path = os.path.join(cache_dir, "meta.json")
    if _needs_init(meta_path, expect_meta):
        lock_fd = os.open(os.path.join(cache_dir, ".init.lock"),
                          os.O_CREAT | os.O_RDWR, 0o644)
        try:
            fcntl.flock(lock_fd, fcntl.LOCK_EX)
            if _needs_init(meta_path, expect_meta):  # lost the race?
                for name, shape, dtype in layout:
                    mm = np.memmap(os.path.join(cache_dir, name), mode="w+",
                                   dtype=dtype, shape=shape)
                    del mm  # creation (ftruncate to size) is all needed
                with open(meta_path + ".tmp", "w") as f:
                    json.dump(expect_meta, f)
                os.replace(meta_path + ".tmp", meta_path)
        finally:
            fcntl.flock(lock_fd, fcntl.LOCK_UN)
            os.close(lock_fd)
    return {
        name: np.memmap(os.path.join(cache_dir, name), mode="r+",
                        dtype=dtype, shape=shape)
        for name, shape, dtype in layout
    }


class _PreparedCacheBase:
    """Shared machinery of the prepared caches: pickling (grain process
    workers reopen the memmaps rather than ship them), row counting, eager
    prebuild, and the ordered crash-safe flush.  Subclasses define
    ``_open_or_create``/``_fill``/``__getitem__`` over their own layout."""

    # the files are the shared state, not the handles
    def __getstate__(self):
        state = self.__dict__.copy()
        state.pop("_maps")
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._open_or_create()

    def __len__(self) -> int:
        return len(self.dataset)

    def sample_image_id(self, index: int) -> str:
        return self.dataset.sample_image_id(index)

    @property
    def n_prepared(self) -> int:
        """Rows already cached (diagnostic / test hook)."""
        return int(np.count_nonzero(self._maps["valid.u8"]))

    def prebuild(self, num_workers: int = 0) -> None:
        """Eagerly fill every missing row (optional — training's first epoch
        does the same lazily)."""
        missing = np.flatnonzero(self._maps["valid.u8"] == 0)
        if num_workers > 0:
            import concurrent.futures as cf
            with cf.ThreadPoolExecutor(max_workers=num_workers) as pool:
                list(pool.map(self._fill, missing.tolist()))
        else:
            for i in missing.tolist():
                self._fill(i)
        self.flush()

    def flush(self) -> None:
        """msync the maps — durability for readers in other processes/runs.

        Data maps flush BEFORE the valid map: a host crash mid-writeback
        must never persist a valid byte whose row bytes didn't land (the
        page cache orders nothing on its own)."""
        for name, mm in self._maps.items():
            if name != "valid.u8":
                mm.flush()
        self._maps["valid.u8"].flush()


class PreparedInstanceDataset(_PreparedCacheBase):
    """Wrap an instance dataset with a prepared-sample disk cache.

    ``dataset`` must be constructed with ``transform=None`` (this class owns
    the whole transform story: the deterministic crop stage feeds the cache,
    ``post_transform`` runs per epoch on the cached crop).  Any source with
    the instance sample contract works — VOC, SBD, ``CombinedDataset``.

    First access of an index computes decode→crop→resize, writes the row,
    and marks it valid; every later access (any epoch, any process) is a
    memmap read.  A full first epoch therefore fills the cache as a side
    effect of training — no separate build pass needed (``prebuild()``
    exists for warming explicitly).
    """

    def __init__(self, dataset, cache_dir: str,
                 crop_size=(512, 512), relax: int = 50,
                 zero_pad: bool = True, fused_crop_resize: bool = False,
                 post_transform=None, uint8_arrays: bool = False,
                 eval_protocol: bool = False,
                 max_im_size=(512, 512)):
        if getattr(dataset, "transform", None) is not None:
            raise ValueError(
                "PreparedInstanceDataset wraps the *untransformed* dataset "
                "(construct it with transform=None); the crop stage it would "
                "run is exactly what this cache replaces")
        self.dataset = dataset
        self.crop_size = tuple(int(v) for v in crop_size)
        self.relax = int(relax)
        self.zero_pad = bool(zero_pad)
        self.fused_crop_resize = bool(fused_crop_resize)
        self.post_transform = post_transform
        #: serve uint8 crop arrays as-is (the data.uint8_transfer wire
        #: format — skips two full-array float casts per sample; all host
        #: transforms downstream are uint8-safe: flip, the uint8-casting
        #: warp, guidance-from-binary-mask)
        self.uint8_arrays = bool(uint8_arrays)
        #: eval mode (data.val_prepared): additionally cache the FULL-RES
        #: gt and void masks as packed bits (1 bit/pixel, padded rows of
        #: ceil(max_h*max_w/8) bytes) so the threshold-swept paste-back
        #: metric (reference train_pascal.py:280-291) never re-decodes the
        #: source PNGs; __getitem__ then emits the evaluator's host-side
        #: keys (``gt``/``void_pixels``/``bbox``) alongside the wire keys.
        self.eval_protocol = bool(eval_protocol)
        self.max_im_size = tuple(int(v) for v in max_im_size)

        # THE shared crop front (pipeline.build_crop_stage): one definition
        # keeps the cached bytes from diverging from the live pipeline.
        from .pipeline import build_crop_stage
        self._stage1 = T.Compose(build_crop_stage(
            self.crop_size, relax, zero_pad, fused=fused_crop_resize,
            clamp=True))

        self.fingerprint = cache_fingerprint(
            dataset, self.crop_size, relax, zero_pad, fused_crop_resize)
        # eval caches live beside the train cache, never aliased: same
        # fingerprint inputs but an extra layout (full-res bit rows)
        suffix = "-eval" if self.eval_protocol else ""
        self.cache_dir = os.path.join(cache_dir, self.fingerprint + suffix)
        self._open_or_create()

    # -- cache files ---------------------------------------------------------

    def _open_or_create(self) -> None:
        n = len(self.dataset)
        h, w = self.crop_size
        self._npack = (h * w + 7) // 8
        mh, mw = self.max_im_size
        self._npack_full = (mh * mw + 7) // 8
        meta = {"format": _FORMAT_VERSION, "fingerprint": self.fingerprint,
                "n": n, "crop_size": [h, w]}
        if self.eval_protocol:
            meta["eval"] = True
            meta["max_im_size"] = [mh, mw]
        self._maps = _open_maps(self.cache_dir, meta, self._layout(n, h, w))

    def _layout(self, n, h, w):
        layout = [
            ("images.u8", (n, h, w, 3), np.uint8),
            ("masks.u8", (n, self._npack), np.uint8),
            ("bboxes.i64", (n, 4), np.int64),
            ("sizes.i32", (n, 2), np.int32),
            ("valid.u8", (n,), np.uint8),
        ]
        if self.eval_protocol:
            layout += [
                ("fullgt.u8", (n, self._npack_full), np.uint8),
                ("fullvoid.u8", (n, self._npack_full), np.uint8),
            ]
        return layout

    # -- dataset protocol: pickling/len/ids/prebuild/flush in the base ------

    def _fill(self, index: int) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                         tuple[int, int]]:
        raw = self.dataset.__getitem__(index)
        sample = self._stage1(dict(raw), None)
        h, w = self.crop_size
        img8 = np.rint(np.asarray(sample["crop_image"],
                                  np.float32)).astype(np.uint8)
        gt = np.asarray(sample["crop_gt"], np.float32)
        if gt.ndim == 3:
            gt = gt[..., 0]
        bits = np.packbits(gt.reshape(-1) > 0.5)
        bbox = np.asarray(sample["bbox"], np.int64)
        im_size = raw["meta"]["im_size"] if "meta" in raw \
            else raw["image"].shape[:2]
        if self.eval_protocol:
            fh, fw = (int(v) for v in im_size)
            if fh * fw > self.max_im_size[0] * self.max_im_size[1]:
                raise ValueError(
                    f"source image {fh}x{fw} exceeds the eval cache's "
                    f"max_im_size {self.max_im_size}; raise max_im_size "
                    "(row bytes scale with it)")
            for key, src in (("fullgt.u8", raw["gt"]),
                             ("fullvoid.u8", raw.get("void_pixels"))):
                row = np.zeros(self._npack_full, np.uint8)
                if src is not None:
                    packed = np.packbits(
                        np.asarray(src).reshape(-1) > 0.5)
                    row[:packed.size] = packed
                self._maps[key][index] = row
        self._maps["images.u8"][index] = img8
        self._maps["masks.u8"][index] = bits
        self._maps["bboxes.i64"][index] = bbox
        self._maps["sizes.i32"][index] = im_size
        self._maps["valid.u8"][index] = 1
        return img8, bits, bbox, tuple(int(v) for v in im_size)

    def __getitem__(self, index: int,
                    rng: np.random.Generator | None = None) -> dict:
        index = int(index)
        h, w = self.crop_size
        if self._maps["valid.u8"][index]:
            img8 = np.asarray(self._maps["images.u8"][index])
            bits = np.asarray(self._maps["masks.u8"][index])
            bbox = np.asarray(self._maps["bboxes.i64"][index]).copy()
            im_size = tuple(int(v) for v in self._maps["sizes.i32"][index])
            if not (img8.any() and bits.any()
                    and bbox.any()
                    and bbox[2] >= bbox[0] and bbox[3] >= bbox[1]
                    and im_size[0] > 0 and im_size[1] > 0
                    # eval rows: full-res gt always has object pixels
                    # (area filter); fullvoid may legitimately be empty
                    and (not self.eval_protocol
                         or self._maps["fullgt.u8"][index].any())):
                # Torn write from a crashed filler: the valid byte landed
                # but a row is still zeros — and each array lives in its own
                # file whose dirty pages persist independently, so ANY row
                # (image, mask, bbox, size) can be the torn one.  A real
                # sample always has object pixels (area filter), a non-black
                # crop, a non-degenerate bbox, and a positive source size;
                # refill (idempotent).  bbox coords are INCLUSIVE
                # (helpers.get_bbox): a thin object at relax=0 legitimately
                # has x_max == x_min, so extent is checked with >= and the
                # all-zeros torn row is caught by .any().
                img8, bits, bbox, im_size = self._fill(index)
        else:
            img8, bits, bbox, im_size = self._fill(index)
        gt = np.unpackbits(bits, count=h * w).reshape(h, w)
        if self.uint8_arrays:
            # .copy(), NOT a view: img8 may alias the writable (r+) memmap
            # row — an in-place mutation downstream would silently corrupt
            # the on-disk cache forever (gt is already fresh via unpackbits)
            sample = {"crop_image": img8.copy(), "crop_gt": gt}
        else:
            sample = {"crop_image": img8.astype(np.float32),
                      "crop_gt": gt.astype(np.float32)}
        sample["meta"] = self._meta(index, im_size)
        if self.post_transform is not None:
            sample = self.post_transform(sample, rng)
        # bbox joins AFTER the random stage: flip/rotate iterate every array
        # key and would mangle a 4-vector of coordinates (in the uncached
        # pipeline the crop — and hence bbox — comes after them).
        sample["bbox"] = bbox
        if self.eval_protocol:
            # host-side metric keys (never shipped): full-res masks from
            # the packed rows.  uint8 0/1 — np_jaccard bools them and the
            # paste-back only thresholds, so the cheap dtype is exact.
            fh, fw = im_size
            for key, src in (("gt", "fullgt.u8"),
                             ("void_pixels", "fullvoid.u8")):
                sample[key] = np.unpackbits(
                    np.asarray(self._maps[src][index]),
                    count=fh * fw).reshape(fh, fw)
        return sample

    def _meta(self, index: int, im_size: tuple[int, int]) -> dict:
        """Rebuild the sample's ``meta`` without touching the image bytes.

        A ``CombinedDataset`` wrapper (the sbd_root merge) is unwrapped to
        the constituent that owns the sample, so the meta schema stays
        identical to the uncached pipeline's (image/object/category/
        im_size) regardless of nesting."""
        ds, local = self.dataset, index
        while hasattr(ds, "datasets") and hasattr(ds, "index"):
            di, local = ds.index[local]
            ds = ds.datasets[di]
        meta = {"image": ds.sample_image_id(local), "im_size": im_size}
        obj_list = getattr(ds, "obj_list", None)
        if obj_list is not None:
            im_ii, obj_ii = obj_list[local]
            meta["object"] = str(obj_ii)
            meta["category"] = ds.obj_dict[ds.im_ids[im_ii]][obj_ii]
        return meta

    def __str__(self) -> str:
        kind = "PreparedEval" if self.eval_protocol else "Prepared"
        return (f"{kind}({self.dataset},crop={self.crop_size},"
                f"relax={self.relax},fp={self.fingerprint})")


class PreparedSemanticDataset(_PreparedCacheBase):
    """Prepared-sample cache for the semantic pipeline.

    The semantic task's deterministic front is smaller than the instance
    task's — decode → fixed resize (no mask-dependent crop) — but on a weak
    host decode still dominates.  Cached per sample: the resized image as
    uint8 and the class-id mask as uint8 (ids 0..20 plus in-band 255 void —
    exact by construction).  Flip / scale-rotate run per epoch downstream
    on the resized arrays, i.e. post-resize rather than the uncached
    pipeline's pre-resize order (the same semantics shift the instance
    cache documents; the warp's uint8 cast and nearest-gt rule are
    unchanged).
    """

    def __init__(self, dataset, cache_dir: str, crop_size=(513, 513),
                 post_transform=None, uint8_arrays: bool = False,
                 keep_fullres: bool = False, max_im_size=(512, 512)):
        if getattr(dataset, "transform", None) is not None:
            raise ValueError(
                "PreparedSemanticDataset wraps the *untransformed* dataset "
                "(construct it with transform=None)")
        self.dataset = dataset
        self.crop_size = tuple(int(v) for v in crop_size)
        self.post_transform = post_transform
        self.uint8_arrays = bool(uint8_arrays)
        #: eval_full_res protocol (data.val_prepared): additionally cache
        #: the NATIVE-resolution class-id mask (uint8 ids + in-band 255
        #: void — exact) in padded rows, emitted as ``gt_full`` so the
        #: evaluator scores mIoU at each image's original size without
        #: re-decoding the label PNG every epoch
        self.keep_fullres = bool(keep_fullres)
        self.max_im_size = tuple(int(v) for v in max_im_size)
        self._stage1 = T.Compose([
            T.FixedResize(resolutions={"image": self.crop_size,
                                       "gt": self.crop_size},
                          flagvals={"image": None, "gt": 0}),
            T.ClampRange(("image",)),
        ])
        # relax/zero_pad/fused have no semantic analogue; pinned values
        # keep the fingerprint function shared with the instance cache
        self.fingerprint = cache_fingerprint(
            dataset, self.crop_size, relax=0, zero_pad=False,
            fused_crop_resize=False)
        suffix = "-fullres" if self.keep_fullres else ""
        self.cache_dir = os.path.join(cache_dir, self.fingerprint + suffix)
        self._open_or_create()

    def _layout(self, n, h, w):
        layout = [
            ("images.u8", (n, h, w, 3), np.uint8),
            ("gts.u8", (n, h, w), np.uint8),
            ("sizes.i32", (n, 2), np.int32),
            ("valid.u8", (n,), np.uint8),
        ]
        if self.keep_fullres:
            mh, mw = self.max_im_size
            layout.append(("gtfull.u8", (n, mh * mw), np.uint8))
        return layout

    def _open_or_create(self) -> None:
        h, w = self.crop_size
        meta = {"format": _FORMAT_VERSION, "fingerprint": self.fingerprint,
                "n": len(self.dataset), "crop_size": [h, w],
                "kind": "semantic"}
        if self.keep_fullres:
            meta["fullres"] = True
            meta["max_im_size"] = list(self.max_im_size)
        self._maps = _open_maps(
            self.cache_dir, meta,
            self._layout(len(self.dataset), h, w))

    def _fill(self, index: int):
        raw = self.dataset.__getitem__(index)
        sample = self._stage1(dict(raw), None)
        img8 = np.rint(np.asarray(sample["image"],
                                  np.float32)).astype(np.uint8)
        gt8 = np.rint(np.asarray(sample["gt"], np.float32)).astype(np.uint8)
        im_size = raw["meta"]["im_size"] if "meta" in raw \
            else raw["image"].shape[:2]
        if self.keep_fullres:
            fh, fw = (int(v) for v in im_size)
            if fh * fw > self.max_im_size[0] * self.max_im_size[1]:
                raise ValueError(
                    f"source image {fh}x{fw} exceeds the fullres cache's "
                    f"max_im_size {self.max_im_size}; raise "
                    "data.val_max_im_size (row bytes scale with it)")
            row = np.zeros(self.max_im_size[0] * self.max_im_size[1],
                           np.uint8)
            full = np.rint(np.asarray(raw["gt"], np.float32)
                           ).astype(np.uint8).reshape(-1)
            row[:full.size] = full
            self._maps["gtfull.u8"][index] = row
        self._maps["images.u8"][index] = img8
        self._maps["gts.u8"][index] = gt8
        self._maps["sizes.i32"][index] = im_size
        self._maps["valid.u8"][index] = 1
        return img8, gt8, tuple(int(v) for v in im_size)

    def __getitem__(self, index: int,
                    rng: np.random.Generator | None = None) -> dict:
        index = int(index)
        if self._maps["valid.u8"][index]:
            img8 = np.asarray(self._maps["images.u8"][index])
            gt8 = np.asarray(self._maps["gts.u8"][index])
            im_size = tuple(int(v) for v in self._maps["sizes.i32"][index])
            if not (img8.any() and gt8.any()
                    and im_size[0] > 0 and im_size[1] > 0
                    # fullres rows: a VOC-style semantic mask is never
                    # all-background (objects + 255 void boundary)
                    and (not self.keep_fullres
                         or self._maps["gtfull.u8"][index].any())):
                # torn write from a crashed filler: pages persist in
                # arbitrary order per file, so ANY row (image, gt, size) can
                # be zeros while valid=1 — a real photo is never all-black,
                # a VOC segmentation mask never all-background (objects +
                # 255 void boundary), and a source size is positive; refill
                # (idempotent) rather than serve silent wrong labels
                img8, gt8, im_size = self._fill(index)
        else:
            img8, gt8, im_size = self._fill(index)
        if self.uint8_arrays:
            # copies, not views of the writable memmap rows (see the
            # instance cache): downstream in-place math must never be able
            # to corrupt the on-disk cache
            sample = {"image": img8.copy(), "gt": gt8.copy()}
        else:
            sample = {"image": img8.astype(np.float32),
                      "gt": gt8.astype(np.float32)}
        sample["meta"] = {"image": self.dataset.sample_image_id(index),
                          "im_size": im_size}
        if self.post_transform is not None:
            sample = self.post_transform(sample, rng)
        if self.keep_fullres:
            fh, fw = im_size
            # ragged host-side metric key (never shipped); uint8 ids
            # exact.  .copy(), not a view: the slice shares the writable
            # r+ memmap buffer and a consumer's in-place edit (e.g. a void
            # remap) would silently rewrite the cached labels on disk.
            sample["gt_full"] = np.asarray(
                self._maps["gtfull.u8"][index][:fh * fw]
            ).reshape(fh, fw).copy()
        return sample

    def __str__(self) -> str:
        kind = "PreparedSemanticFullres" if self.keep_fullres \
            else "PreparedSemantic"
        return (f"{kind}({self.dataset},crop={self.crop_size},"
                f"fp={self.fingerprint})")
