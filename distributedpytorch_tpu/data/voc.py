"""Instance-level Pascal VOC 2012 dataset.

TPU-native re-design of the reference dataset (/root/reference/pascal.py,
SURVEY.md §2.2): one example per (image, object) pair — *instance-level*, not
per-image — with void-pixel handling and a one-time JSON preprocess cache of
per-object categories filtered by an area threshold.

Differences from the reference, by design:

* a plain random-access source (``__getitem__``/``__len__``) with **no torch
  dependency** — batching/sharding live in :mod:`.pipeline`;
* the dataset root is an explicit argument (the reference hid it in a
  machine-specific ``mypath`` registry, pascal.py:13,33) — config owns paths;
* the tar download/MD5 path is kept behind ``download=True`` but integrity of
  an already-extracted tree is checked structurally (directories present)
  rather than by re-hashing a 2 GB tar on every construction.
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import json
import os
import shutil
import tarfile
import tempfile
import threading
import urllib.request

import numpy as np
from PIL import Image

#: canonical VOC2012 trainval archive (reference pascal.py:21-23)
URL = "http://host.robots.ox.ac.uk/pascal/VOC/voc2012/VOCtrainval_11-May-2012.tar"
FILE = "VOCtrainval_11-May-2012.tar"
MD5 = "6cd6e144f989b92b3379bac3b3de84fd"
BASE_DIR = "VOCdevkit/VOC2012"

CATEGORY_NAMES = [
    "background",
    "aeroplane", "bicycle", "bird", "boat", "bottle",
    "bus", "car", "cat", "chair", "cow",
    "diningtable", "dog", "horse", "motorbike", "person",
    "pottedplant", "sheep", "sofa", "train", "tvmonitor",
]

# Probed ONCE at import: os.umask() can only be read by setting it, which
# mutates process-global state — doing that per-write raced loader/build
# worker threads (a thread could briefly run with umask 0, or a cache file
# could publish with the wrong mode).  Import happens before any workers.
_UMASK = os.umask(0)
os.umask(_UMASK)


def ensure_voc(root: str, download: bool = False) -> str:
    """Ensure an extracted VOC2012 tree under ``root``; returns its path.

    With ``download=True`` and no tree present, fetches the trainval tar and
    **MD5-verifies it before extracting** — a truncated/tampered download
    must never leave a half-extracted tree that the dir-exists check would
    then trust forever.  Multi-process: call on process 0 only, then
    barrier (the Trainer does this).
    """
    if not root:
        raise ValueError(
            "data root is empty — set data.root to the directory that holds "
            "(or should receive) the VOCdevkit tree")
    voc_root = os.path.join(root, BASE_DIR)
    if os.path.isdir(voc_root):
        return voc_root
    if not download:
        raise RuntimeError(
            f"VOC tree not found under {voc_root}; pass download=True or "
            "point root at an extracted VOCdevkit.")
    os.makedirs(root, exist_ok=True)
    fpath = os.path.join(root, FILE)
    if not (os.path.isfile(fpath) and _md5(fpath) == MD5):
        urllib.request.urlretrieve(URL, fpath)
        got = _md5(fpath)
        if got != MD5:
            raise RuntimeError(
                f"downloaded {FILE} is corrupt: md5 {got} != {MD5}")
    # Extract to a scratch dir and rename the finished tree into place: an
    # interrupted extractall must never leave a partial VOC2012 that the
    # dir-exists fast path above would then trust forever.
    tmp_dir = os.path.join(root, ".voc_extract.tmp")
    shutil.rmtree(tmp_dir, ignore_errors=True)
    os.makedirs(tmp_dir)
    with tarfile.open(fpath) as tar:
        tar.extractall(tmp_dir, filter="data")
    os.makedirs(os.path.dirname(voc_root), exist_ok=True)
    os.rename(os.path.join(tmp_dir, BASE_DIR), voc_root)
    shutil.rmtree(tmp_dir, ignore_errors=True)
    return voc_root


def load_obj_cache(path: str, im_ids: list[str]) -> dict | None:
    """Read a JSON instance cache; valid iff its key set matches ``im_ids``
    exactly (reference pascal.py:154-161).  Tolerates a concurrently
    half-written file (treated as absent) — see :func:`write_obj_cache`."""
    if not os.path.isfile(path):
        return None
    try:
        with open(path) as f:
            obj = json.load(f)
    # ValueError covers JSONDecodeError AND UnicodeDecodeError (binary junk)
    except (ValueError, OSError):
        return None
    if not isinstance(obj, dict):
        return None
    return obj if sorted(obj.keys()) == sorted(im_ids) else None


def write_obj_cache(path: str, obj_dict: dict) -> None:
    """Atomic JSON cache write: temp file + rename, so concurrent builders
    (every process of a multi-host run scans on first use) can never leave
    a truncated cache for a reader to crash on — last writer wins whole."""
    # mkstemp, not a pid-suffixed name: pids collide across the hosts of a
    # multi-host run sharing the dataset root over NFS/fuse.
    fd, tmp = tempfile.mkstemp(
        prefix=os.path.basename(path) + ".tmp.",
        dir=os.path.dirname(path) or ".")
    try:
        # mkstemp creates 0600; publish with umask-honoring permissions so
        # other users of a shared dataset root can read the cache (umask
        # cached at import — see _UMASK above).
        os.fchmod(fd, 0o666 & ~_UMASK)
        with os.fdopen(fd, "w") as f:
            json.dump(obj_dict, f, indent=1)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.remove(tmp)
        raise


class _DecodeCache:
    """Thread-safe LRU of decoded images keyed by image index.

    FFCV-style decode-once (PAPERS.md: FFCV; Mohan et al. data-loading
    study): JPEG/PNG decode dominates per-sample host time, and the
    instance dataset revisits the same image for every one of its objects
    plus every epoch.  Values are stored pre-float (uint8 RGB, raw mask —
    ~0.7 MB per VOC image vs ~2.8 MB as float32); callers copy-convert so
    cached arrays are never mutated.
    """

    def __init__(self, max_items: int):
        self.max_items = max_items
        self._d: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()

    def get(self, key, load):
        with self._lock:
            if key in self._d:
                self._d.move_to_end(key)
                return self._d[key]
        val = load()  # decode outside the lock: loader threads overlap
        with self._lock:
            self._d[key] = val
            self._d.move_to_end(key)
            while len(self._d) > self.max_items:
                self._d.popitem(last=False)
        return val

    # Process workers (the grain loader) pickle the dataset; locks don't
    # pickle and cached bytes shouldn't ship either — each worker process
    # rebuilds an empty, independent cache.
    def __getstate__(self):
        return {"max_items": self.max_items}

    def __setstate__(self, state):
        self.__init__(state["max_items"])


class VOCInstanceSegmentation:
    """Random-access source of (image, single-object mask, void mask) samples.

    Each index addresses one *object instance*: ``obj_list[i] = (image_idx,
    object_idx)``, built from the per-image category cache and skipping
    objects filtered out by ``area_thres`` (reference pascal.py:107-116).

    ``__getitem__`` returns the reference's sample contract
    (pascal.py:122-137)::

        {'image':       float32 (H, W, 3) RGB,
         'gt':          float32 (H, W) binary mask of ONE object,
         'void_pixels': float32 (H, W) mask of 255-labelled pixels,
         'meta':        {'image', 'object', 'category', 'im_size'}}   # retname

    A ``transform`` (see :mod:`.transforms`) is applied if given; stochastic
    transforms receive the ``rng`` passed to ``__getitem__`` — the loader
    derives it from (seed, epoch, index) so every sample is reproducible.
    """

    def __init__(
        self,
        root: str,
        split="val",
        transform=None,
        download: bool = False,
        preprocess: bool = False,
        area_thres: int = 0,
        retname: bool = True,
        suppress_void_pixels: bool = True,
        default: bool = False,
        decode_cache: int = 0,
    ):
        self.root = root
        self.transform = transform
        self.area_thres = area_thres
        self.retname = retname
        self.suppress_void_pixels = suppress_void_pixels
        self.default = default
        #: decode-once LRU over ``decode_cache`` images (0 = off); see
        #: :class:`_DecodeCache`
        self._cache = _DecodeCache(decode_cache) if decode_cache > 0 else None
        self.split = sorted([split] if isinstance(split, str) else list(split))

        voc_root = os.path.join(root, BASE_DIR)
        self._image_dir = os.path.join(voc_root, "JPEGImages")
        self._mask_dir = os.path.join(voc_root, "SegmentationObject")
        self._cat_dir = os.path.join(voc_root, "SegmentationClass")
        splits_dir = os.path.join(voc_root, "ImageSets", "Segmentation")

        ensure_voc(root, download=download)

        area_suffix = f"_area_thres-{area_thres}" if area_thres else ""
        self.obj_list_file = os.path.join(
            splits_dir, "_".join(self.split) + "_instances" + area_suffix + ".txt"
        )

        self.im_ids: list[str] = []
        self.images: list[str] = []
        self.masks: list[str] = []
        self.categories: list[str] = []
        for splt in self.split:
            with open(os.path.join(splits_dir, splt + ".txt")) as f:
                ids = f.read().splitlines()
            for line in ids:
                paths = (
                    os.path.join(self._image_dir, line + ".jpg"),
                    os.path.join(self._cat_dir, line + ".png"),
                    os.path.join(self._mask_dir, line + ".png"),
                )
                for p in paths:
                    if not os.path.isfile(p):
                        raise FileNotFoundError(p)
                self.im_ids.append(line)
                self.images.append(paths[0])
                self.categories.append(paths[1])
                self.masks.append(paths[2])

        if preprocess or not self._load_obj_cache():
            self._preprocess()

        # One entry per surviving object instance.
        self.obj_list: list[tuple[int, int]] = []
        n_images_used = 0
        for ii, im_id in enumerate(self.im_ids):
            cats = self.obj_dict[im_id]
            live = [(ii, jj) for jj, cat in enumerate(cats) if cat != -1]
            self.obj_list.extend(live)
            n_images_used += bool(live)
        self.num_images = n_images_used

    # -- construction helpers ------------------------------------------------

    def _load_obj_cache(self) -> bool:
        obj = load_obj_cache(self.obj_list_file, self.im_ids)
        if obj is None:
            return False
        self.obj_dict = obj
        return True

    def _preprocess(self) -> None:
        """One-time scan: decode every instance + class PNG, area-filter each
        object, cache image id -> [category or -1, ...] as JSON (reference
        pascal.py:163-195)."""
        self.obj_dict = {}
        for ii, im_id in enumerate(self.im_ids):
            inst = np.array(Image.open(self.masks[ii]))
            ids = np.unique(inst)
            n_obj = int(ids[-2] if ids[-1] == 255 else ids[-1])
            cats = np.array(Image.open(self.categories[ii]))
            cat_ids = []
            for jj in range(n_obj):
                rows, cols = np.where(inst == jj + 1)
                if rows.size > self.area_thres:
                    cat_ids.append(int(cats[rows[0], cols[0]]))
                else:
                    cat_ids.append(-1)
            self.obj_dict[im_id] = cat_ids
        write_obj_cache(self.obj_list_file, self.obj_dict)

    # -- sample access -------------------------------------------------------

    def __len__(self) -> int:
        return len(self.obj_list)

    def sample_image_id(self, index: int) -> str:
        """Image id owning sample ``index`` (CombinedDataset exclusion key)."""
        return self.im_ids[self.obj_list[index][0]]

    def __getitem__(self, index: int, rng: np.random.Generator | None = None) -> dict:
        im_ii, obj_ii = self.obj_list[index]
        img, target, void = self._load_instance(im_ii, obj_ii)
        sample = {"image": img, "gt": target, "void_pixels": void}
        if self.retname:
            sample["meta"] = {
                "image": self.im_ids[im_ii],
                "object": str(obj_ii),
                "category": self.obj_dict[self.im_ids[im_ii]][obj_ii],
                "im_size": (img.shape[0], img.shape[1]),
            }
        if self.transform is not None:
            sample = self.transform(sample, rng)
        return sample

    def decode_raw(self, im_ii: int) -> tuple[np.ndarray, np.ndarray]:
        """The decoded pair for image ``im_ii`` — (uint8 RGB, raw
        instance mask), exactly the arrays the sample math consumes.
        Public because the packer (data/packed.py) stores these bytes
        and re-runs ``__getitem__``'s arithmetic on them, which is what
        makes packed samples bit-identical to this class's."""
        def decode():
            return (np.array(Image.open(self.images[im_ii]).convert("RGB"),
                             np.uint8),
                    np.array(Image.open(self.masks[im_ii])))

        return (self._cache.get(im_ii, decode)
                if self._cache is not None else decode())

    def _load_instance(self, im_ii: int, obj_ii: int):
        """Decode one (image, object) pair (reference pascal.py:232-263;
        the computed-but-discarded other-class masks are not reproduced)."""
        img8, inst_raw = self.decode_raw(im_ii)
        # astype COPIES, so the cached uint8 arrays are never mutated by the
        # void-suppression below or by downstream transforms.
        img = img8.astype(np.float32)
        inst = inst_raw.astype(np.float32)
        void = inst == 255
        if self.suppress_void_pixels:
            inst[void] = 0
        if self.default:
            target = inst
        else:
            target = (inst == obj_ii + 1).astype(np.float32)
        return img, target, void.astype(np.float32)

    def __str__(self) -> str:
        return f"VOC2012(split={self.split},area_thres={self.area_thres})"


class VOCSemanticSegmentation:
    """Per-image semantic VOC2012: class-id masks from ``SegmentationClass``.

    The multi-class counterpart of :class:`VOCInstanceSegmentation` for the
    DeepLabV3 semantic configs of BASELINE.json (configs 1 and 4).  The
    reference never trained this mode — its dataset is instance-level — but
    its class PNGs are read for the category cache (reference
    pascal.py:171-176), and this class exposes them directly:

        {'image': float32 (H, W, 3) RGB,
         'gt':    float32 (H, W) class ids 0..20, void pixels = 255,
         'meta':  {'image', 'im_size'}}                            # retname

    Void stays *in-band* as 255 (torchvision convention): the softmax CE loss
    masks it via ``ignore_index`` (ops.losses.softmax_xent_ignore) and the
    mIoU metric drops those pixels, so no separate void channel is needed.
    """

    def __init__(self, root: str, split="val", transform=None,
                 retname: bool = True, download: bool = False,
                 decode_cache: int = 0):
        self.root = root
        self.transform = transform
        self.retname = retname
        self.split = sorted([split] if isinstance(split, str) else list(split))
        self.nclass = len(CATEGORY_NAMES)
        self._cache = _DecodeCache(decode_cache) if decode_cache > 0 else None

        voc_root = os.path.join(root, BASE_DIR)
        image_dir = os.path.join(voc_root, "JPEGImages")
        cat_dir = os.path.join(voc_root, "SegmentationClass")
        splits_dir = os.path.join(voc_root, "ImageSets", "Segmentation")
        ensure_voc(root, download=download)

        self.im_ids: list[str] = []
        self.images: list[str] = []
        self.categories: list[str] = []
        for splt in self.split:
            with open(os.path.join(splits_dir, splt + ".txt")) as f:
                ids = f.read().splitlines()
            for line in ids:
                img = os.path.join(image_dir, line + ".jpg")
                cat = os.path.join(cat_dir, line + ".png")
                for p in (img, cat):
                    if not os.path.isfile(p):
                        raise FileNotFoundError(p)
                self.im_ids.append(line)
                self.images.append(img)
                self.categories.append(cat)

    def __len__(self) -> int:
        return len(self.im_ids)

    def sample_image_id(self, index: int) -> str:
        """Image id of sample ``index`` (CombinedDataset exclusion key)."""
        return self.im_ids[index]

    def decode_raw(self, index: int) -> tuple[np.ndarray, np.ndarray]:
        """Decoded (uint8 RGB, raw class-id mask) for image ``index`` —
        the packer's source bytes (see the instance class's
        ``decode_raw``)."""
        def decode():
            return (np.array(Image.open(self.images[index]).convert("RGB"),
                             np.uint8),
                    np.array(Image.open(self.categories[index])))

        return (self._cache.get(index, decode)
                if self._cache is not None else decode())

    def __getitem__(self, index: int,
                    rng: np.random.Generator | None = None) -> dict:
        img8, gt_raw = self.decode_raw(index)
        img = img8.astype(np.float32)  # astype copies; cache never mutated
        gt = gt_raw.astype(np.float32)
        sample = {"image": img, "gt": gt}
        if self.retname:
            sample["meta"] = {"image": self.im_ids[index],
                              "im_size": (img.shape[0], img.shape[1])}
        if self.transform is not None:
            sample = self.transform(sample, rng)
        return sample

    def __str__(self) -> str:
        return f"VOC2012Semantic(split={self.split})"


def _md5(path: str) -> str:
    h = hashlib.md5()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()
