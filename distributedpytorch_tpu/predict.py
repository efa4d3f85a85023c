"""Interactive-segmentation inference: extreme-point clicks -> full-res mask.

The reference trains a click-guided (DEXTR-style) binary segmenter but ships
no inference entry point — its val loop (reference train_pascal.py:233-308)
is the only consumer of the trained model.  This module completes that user
story: given an RGB image and the 4 extreme points of the object (the same
guidance the model was trained on, reference custom_transforms.py:30-51), it
runs the full preprocessing -> model -> paste-back chain and returns a
full-resolution probability mask.

The preprocessing mirrors the *val* transform pipeline exactly
(reference train_pascal.py:135-145), with the clicked points standing in for
the gt-derived deterministic extreme points:

    points -> relax-padded bbox        (CropFromMaskStatic semantics, relax=50)
           -> zero-padded crop         (helpers.crop_from_mask)
           -> fixed resize             (FixedResize, cubic, 512x512)
           -> n-ellipse + gaussians    (NEllipseWithGaussians, z1 + alpha*z2,
                                        rescaled to peak 255)
           -> RGB(3) + guidance(1)     (ConcatInputs -> 'concat', [0,255])

and the postprocessing mirrors the val metric path (train_pascal.py:283-290):
sigmoid of the fused head, ``crop2fullmask`` paste-back with the relax border
shaved.

Device work is one jitted forward at a fixed (resolution, 4) shape, so every
click/image after the first reuses the same compiled program — the
interactive-latency design point.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import imaging
from .data import guidance as guidance_lib
from .utils.helpers import crop2fullmask, crop_from_bbox, get_bbox


#: guidance families computable from the 4 clicks alone — the ones
#: click-based inference can serve (confidence maps need the gt mask,
#: 'none' has no channel).  Single source of truth lives in
#: data/guidance.py (``POINT_GUIDANCE``), shared with session-log replay
#: (data/sessions.py) so serve-time and replay-time guidance are one
#: implementation; the pre-restore guards in ``Predictor.from_run``/
#: ``from_torch`` AND ``guidance_from_points``' dispatch both read it,
#: so a family cannot be accepted at construction yet unknown at
#: predict time.
_POINT_GUIDANCE = guidance_lib.POINT_GUIDANCE

#: re-export: the dispatch moved to data/guidance.py (numpy-only, so the
#: flywheel's replay reader can use it without importing jax); the public
#: name here is unchanged.
guidance_from_points = guidance_lib.guidance_from_points


def prepare_input(
    image: np.ndarray,
    points: np.ndarray,
    relax: int = 50,
    zero_pad: bool = True,
    resolution: tuple[int, int] = (512, 512),
    alpha: float = 0.6,
    guidance: str = "nellipse_gaussians",
) -> tuple[np.ndarray, tuple[int, int, int, int]]:
    """Image + clicks -> (``concat`` (H, W, 4) float32, crop bbox).

    ``image`` is (H, W, 3) RGB, any dtype, values in [0, 255]; ``points`` is
    (4, 2) xy in full-image coordinates.  Returns the network input at
    ``resolution`` and the (relax-padded) bbox needed to paste the prediction
    back with :func:`predict` / ``crop2fullmask``.  ``guidance`` must match
    the family the checkpoint was trained with (see
    :func:`guidance_from_points`).
    """
    image = np.asarray(image, np.float32)
    if image.ndim != 3 or image.shape[-1] != 3:
        raise ValueError(f"expected (H, W, 3) RGB image, got {image.shape}")
    points = np.asarray(points, np.float64)
    if points.shape != (4, 2):
        raise ValueError(f"expected 4 xy extreme points, got {points.shape}")
    h, w = image.shape[:2]
    if (points[:, 0].max() >= w or points[:, 1].max() >= h
            or points.min() < 0):
        raise ValueError(f"points {points.tolist()} outside image {w}x{h}")

    # get_bbox only reads .shape when points are given; a broadcast stub
    # avoids allocating an image-sized array per click.
    shape_stub = np.broadcast_to(np.uint8(0), (h, w))
    bbox = get_bbox(shape_stub, points=points, pad=relax, zero_pad=zero_pad)
    crop = crop_from_bbox(image, bbox, zero_pad=zero_pad)
    res_h, res_w = resolution
    crop = imaging.resize(crop, (res_h, res_w), imaging.CUBIC)
    # Points into resized-crop coordinates + guidance synthesis, through
    # the shared seam (data/guidance.py:crop_point_guidance) — the same
    # call session-log replay makes, pinning bit-identity.
    heat = guidance_lib.crop_point_guidance(
        points, bbox, (res_h, res_w), alpha=alpha, family=guidance)
    concat = np.concatenate(
        [np.clip(crop, 0.0, 255.0), heat[..., None]], axis=-1)
    return concat.astype(np.float32), bbox


def load_run_config(run_dir: str):
    """The run's saved ``Config`` (cheap — no checkpoint IO), so callers can
    validate task/guidance compatibility before paying for the restore."""
    from .train import config as config_lib

    return config_lib.from_json(os.path.join(run_dir, "config.json"))


def model_from_config(cfg):
    """Rebuild the model exactly as the Trainer did, minus mesh couplings:
    ring PAM needs a sequence-parallel mesh, so inference falls back to the
    numerically identical einsum form, and the bucketed-reduce run's
    cross-replica BN stays off (train-time only; inference never computes
    batch stats).  The moe_* options shape the param tree and MUST match
    or checkpoint restore fails.  train.precision carries over: a
    bf16-trained run serves bf16 (master params are f32 either way, so
    restore is dtype-independent)."""
    from .models import build_from_config
    from .train.config import ModelConfig
    from .train.precision import precision_policy

    policy = precision_policy(
        getattr(getattr(cfg, "train", None), "precision", None))
    # a config saved before a field existed carries that field's default
    mcfg = ModelConfig(**{
        f.name: getattr(cfg.model, f.name)
        for f in dataclasses.fields(ModelConfig)
        if hasattr(cfg.model, f.name)})
    if mcfg.pam_impl == "ring":
        mcfg.pam_impl = "einsum"
    return build_from_config(
        mcfg, dtype=policy.compute_dtype if policy else mcfg.dtype)


def load_run(run_dir: str, best: bool = True, cfg=None):
    """Load ``(cfg, model, state)`` from a training run directory.

    ``cfg``: pass the run's already-loaded config (from
    :func:`load_run_config`) to skip re-reading it.

    Restores the best-metric checkpoint (falling back to latest) onto an
    abstract ``eval_shape`` template — Orbax restores onto
    ShapeDtypeStructs, so no throwaway second copy of the params is ever
    materialized.
    """
    from .parallel import create_train_state
    from .train.checkpoint import CheckpointManager
    from .train.optim import make_optimizer

    if cfg is None:
        cfg = load_run_config(run_dir)
    model = model_from_config(cfg)
    h, w = cfg.data.crop_size
    # The template's opt_state tree must match what the run saved, so the
    # optimizer comes from the run's own config (total_steps only shapes
    # the schedule, not the state tree).
    tx, _ = make_optimizer(cfg.optim, total_steps=1)
    template = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), model, tx,
                                   (1, h, w, cfg.model.in_channels)))
    # Pin every leaf to THIS process's device 0: Orbax needs concrete
    # shardings on the abstract target whenever the checkpoint's own saved
    # layout isn't reconstructible here (e.g. a run trained on an 8-device
    # mesh, loaded in a 1-device export/predict process) — and a single
    # device is exactly where inference wants the weights anyway.
    one_dev = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
    template = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_dev),
        template)
    mgr = CheckpointManager(os.path.join(run_dir, "checkpoints"),
                            async_save=False)
    try:
        try:
            state, _ = mgr.restore(template, best=best)
        except FileNotFoundError:
            if not best:
                raise
            state, _ = mgr.restore(template, best=False)  # no best slot yet
    finally:
        mgr.close()
    return cfg, model, state


def _apply_with_normalize(model, variables, mean, std, x):
    """Optional mean/std normalization + model apply — the shared first
    half of both predictors' compiled forwards."""
    if mean is not None or std is not None:
        from .ops.augment import normalize
        x = normalize({"concat": x}, mean or (0.0,),
                      std or (255.0,))["concat"]
    return model.apply(variables, x, train=False)


def _split_channel_stats(vals, n_channels: int):
    """Split per-channel normalization stats into (rgb, guidance) parts.

    The encode/decode split normalizes each part inside its own stage;
    slicing here keeps that bitwise identical to normalizing the concat
    and then splitting.  A broadcast scalar applies to both parts;
    per-channel stats must cover every channel or the guidance lane
    would silently reuse an RGB constant.
    """
    if vals is None:
        return None, None
    vals = tuple(vals)
    if len(vals) == 1:
        return vals, vals
    if len(vals) != n_channels:
        raise ValueError(
            f"normalization stats have {len(vals)} entries for "
            f"{n_channels} input channels — pass 1 (broadcast) or "
            f"{n_channels} (per-channel incl. guidance)")
    return vals[:-1], vals[-1:]


def _click_kwargs_from_cfg(cfg, kwargs: dict) -> dict:
    """Default the click-predictor constructor kwargs from a run config."""
    kwargs.setdefault("resolution", tuple(cfg.data.crop_size))
    kwargs.setdefault("relax", cfg.data.relax)
    kwargs.setdefault("zero_pad", cfg.data.zero_pad)
    kwargs.setdefault("alpha", cfg.data.guidance_alpha)
    kwargs.setdefault("guidance", cfg.data.guidance)
    kwargs.setdefault("in_channels", cfg.model.in_channels)
    return kwargs


class _AotDispatch:
    """Route concrete-batch calls to an installed AOT executable
    (serve/aot.py), everything else to the underlying jitted callable.

    The pre-compiled executables a warm-cache serve boot deserializes
    (``jax.experimental.serialize_executable``) are ``jax.stages
    .Compiled`` objects outside the jit dispatch cache, so the predictor
    needs its own per-shape table.  The wrapper is transparent to every
    other consumer: tracing callers (``jax.eval_shape``, the jaxaudit
    lowering cache's ``fn.trace``/``fn.lower``) see the jit function —
    a Tracer argument, or any attribute access, falls straight through —
    and with an empty table the call overhead is one truthiness check.
    """

    # __weakref__: jax.eval_shape (feature_struct) weak-caches the callable
    __slots__ = ("_fn", "_table", "_key_of", "__weakref__")

    def __init__(self, fn, table: dict, key_of):
        self._fn = fn
        self._table = table
        self._key_of = key_of

    def __call__(self, *args):
        if self._table:
            x = args[0]
            shape = getattr(x, "shape", None)
            if shape is not None and not isinstance(x, jax.core.Tracer):
                exe = self._table.get(self._key_of(tuple(shape)))
                if exe is not None:
                    return exe(*args)
        return self._fn(*args)

    def __getattr__(self, name):
        # .trace / .lower / .__name__ / ... — the jit fn's own surface
        return getattr(object.__getattribute__(self, "_fn"), name)


class Predictor:
    """Reusable click-to-mask inference on one model + checkpoint.

    >>> p = Predictor.from_run("work/run_0")          # config.json + ckpt
    >>> prob = p.predict(image, points)               # (H, W) in [0, 1]
    >>> mask = prob > 0.5

    One compiled forward per (resolution, channels) shape; subsequent calls
    are dispatch-only.
    """

    def __init__(self, model, params, batch_stats,
                 resolution: tuple[int, int] = (512, 512),
                 relax: int = 50, zero_pad: bool = True,
                 alpha: float = 0.6,
                 guidance: str = "nellipse_gaussians",
                 mean: Sequence[float] | None = None,
                 std: Sequence[float] | None = None,
                 mesh=None, in_channels: int = 4):
        self.model = model
        self.resolution = tuple(resolution)
        #: network input channel count (RGB + guidance = 4 for the
        #: reference stem; exotic stems differ) — flax infers it lazily
        #: from the first call, so shape-building consumers (the serve
        #: warmup) read it here instead of guessing
        self.in_channels = in_channels
        self.relax = relax
        self.zero_pad = zero_pad
        self.alpha = alpha
        self.guidance = guidance
        self.mesh = mesh
        #: the served weights, as handed in — the hot-swap path
        #: (serve/swap.load_swap_predictor) and tests read them back;
        #: the compiled forwards close over this exact tree
        self.params = params
        self.batch_stats = batch_stats
        # NOTE: params may hold serve/quantize.QTensor leaves (int8
        # kernels + scales).  Nothing here special-cases them: flax's
        # dtype promotion calls ``jnp.asarray`` on every kernel at use,
        # which triggers QTensor.__jax_array__ — the dequantization is
        # traced INSIDE whichever jitted forward consumes the kernel,
        # and only the kernels a program actually uses enter its trace.
        variables = {"params": params, "batch_stats": batch_stats}
        #: per-shape AOT executables (serve/aot.py) — empty unless a
        #: warm-cache serve boot installed pre-compiled programs
        self._aot_execs: dict = {}

        def forward(x):
            outputs = _apply_with_normalize(model, variables, mean, std, x)
            # Fused (primary) head only — the tuple's first element, the one
            # the reference's metric consumes (train_pascal.py:283).
            return jax.nn.sigmoid(outputs[0].astype(jnp.float32))

        #: guidance_inject='head' models split into two separately-jitted
        #: stages: ``encode_jitted`` (RGB crop -> backbone features, the
        #: session-invariant ~90% of the FLOPs) and ``decode_jitted``
        #: (features + guidance -> probability maps).  Sessions are
        #: single-device (the feature cache pins one device's HBM), so a
        #: mesh predictor keeps the whole-forward jit and no stages.
        self.supports_sessions = (
            getattr(model, "guidance_inject", "stem") == "head"
            and mesh is None)
        self.encode_jitted = None
        self.decode_jitted = None
        if self.supports_sessions:
            from .ops.augment import normalize as _normalize

            rgb_mean, g_mean = _split_channel_stats(mean, in_channels)
            rgb_std, g_std = _split_channel_stats(std, in_channels)

            def _norm(x, m, s):
                if m is None and s is None:
                    return x
                return _normalize({"concat": x}, m or (0.0,),
                                  s or (255.0,))["concat"]

            def encode_forward(rgb):
                return model.apply(variables, _norm(rgb, rgb_mean, rgb_std),
                                   train=False, stage="encode")

            def decode_forward(feats, guidance):
                outs = model.apply(
                    variables, (feats, _norm(guidance, g_mean, g_std)),
                    train=False, stage="decode",
                    out_size=self.resolution)
                return jax.nn.sigmoid(outs[0].astype(jnp.float32))

            self.encode_jitted = _AotDispatch(
                jax.jit(encode_forward), self._aot_execs,
                lambda s: ("encode", s[0]))
            self.decode_jitted = _AotDispatch(
                jax.jit(decode_forward), self._aot_execs,
                lambda s: ("decode", s[0]))

            def staged_forward(x):
                # THE forward of a split predictor IS the composition, so
                # the stateless path and the session path (cached feats ->
                # decode) run the exact same two compiled programs — warm
                # and cold clicks are bitwise identical by construction.
                return self.decode_jitted(self.encode_jitted(x[..., :-1]),
                                          x[..., -1:])

            self._forward = staged_forward
        elif mesh is None:
            self._forward = _AotDispatch(jax.jit(forward), self._aot_execs,
                                         lambda s: ("forward", s))
        else:
            # Distributed inference: crops shard over the mesh's data axis
            # (GSPMD partitions the forward, same as the train step); the
            # probability maps come back replicated for the host paste-back.
            # Single-process only: shard_batch's multi-process branch treats
            # the input as a per-host shard, which would duplicate the whole
            # crop batch on every host here.
            if jax.process_count() > 1:
                raise ValueError(
                    "Predictor(mesh=...) is single-process (all local "
                    "devices); multi-host serving should shard requests "
                    "across processes instead")
            from .parallel.mesh import (
                batch_sharding,
                replicated_sharding,
                traced_on,
            )
            self._forward = jax.jit(
                traced_on(mesh, forward), in_shardings=batch_sharding(mesh),
                out_shardings=replicated_sharding(mesh))

    @property
    def forward_jitted(self):
        """The exact forward this predictor dispatches — the callable the
        serve audit hooks and jaxaudit contracts trace (``analysis.ir``);
        one compiled program per batch shape.  For a split predictor
        (``supports_sessions``) this is the encode∘decode COMPOSITION
        (plain Python, not itself traceable) — audit the stages via
        ``encode_jitted``/``decode_jitted`` instead."""
        return self._forward

    def install_aot(self, key: tuple, executable) -> None:
        """Install a pre-compiled executable for one program shape.

        ``key``: ``("forward", (B, H, W, C))`` for a whole-forward
        predictor, ``("encode", bucket)`` / ``("decode", bucket)`` for a
        split one — the keys ``serve.aot.AotCache`` hands the warm-boot
        loader.  Dispatches at that exact shape then run the installed
        executable instead of the jit cache (zero compiles on a
        warm-cache boot); every other shape, and every tracing consumer,
        keeps the ordinary jitted path.
        """
        if self.mesh is not None:
            raise ValueError(
                "install_aot: mesh predictors compile GSPMD programs "
                "bound to this process's device assignment — the AOT "
                "cache serves single-device replicas")
        kind = key[0]
        valid = ({"encode", "decode"} if self.supports_sessions
                 else {"forward"})
        if kind not in valid:
            raise ValueError(
                f"install_aot: key kind {kind!r} does not match this "
                f"predictor's programs ({sorted(valid)})")
        self._aot_execs[key] = executable

    @property
    def aot_programs(self) -> list:
        """Keys of the installed AOT executables (ops surface)."""
        return sorted(self._aot_execs, key=str)

    def feature_struct(self, batch: int = 1):
        """ShapeDtypeStruct of one encoded-feature batch — the session
        cache entry's shape/dtype (and the byte cost the HBM budget
        charges), computed without dispatching."""
        if not self.supports_sessions:
            raise ValueError("feature_struct: this predictor has no "
                             "encode stage (guidance_inject='stem' or "
                             "mesh-sharded)")
        h, w = self.resolution
        rgb = jax.ShapeDtypeStruct((batch, h, w, self.in_channels - 1),
                                   jnp.float32)
        return jax.eval_shape(self.encode_jitted, rgb)

    def prepare_guidance(self, points: Any,
                         bbox: tuple[int, int, int, int]) -> np.ndarray:
        """Warm-click guidance: new clicks mapped into an EXISTING crop.

        A session's first click established ``bbox`` (and the cached
        backbone features of that crop); refinement clicks re-synthesize
        only the guidance channel in the same crop coordinates — the
        FixedResize point-scaling rule of :func:`prepare_input`, with the
        bbox held fixed.  Returns (H, W, 1) float32 at ``resolution``.
        """
        points = np.asarray(points, np.float64)
        if points.shape != (4, 2):
            raise ValueError(f"expected 4 xy extreme points, got "
                             f"{points.shape}")
        heat = guidance_lib.crop_point_guidance(
            points, bbox, self.resolution, alpha=self.alpha,
            family=self.guidance)
        return heat.astype(np.float32)[..., None]

    @classmethod
    def from_run(cls, run_dir: str, best: bool = True, cfg=None,
                 **kwargs) -> "Predictor":
        """Build from a training run directory (``config.json`` +
        ``checkpoints/``), restoring the best-metric checkpoint by default
        (falls back to latest when no best exists).  ``cfg`` skips
        re-reading an already-loaded run config."""
        if cfg is None:
            cfg = load_run_config(run_dir)
        if cfg.task != "instance":
            raise ValueError(
                f"Predictor is the click-guided instance path; this run was "
                f"trained with task={cfg.task!r} (use SemanticPredictor)")
        if cfg.data.guidance not in _POINT_GUIDANCE:
            raise ValueError(
                f"this run's guidance family ({cfg.data.guidance!r}) is not "
                "derivable from clicks alone (confidence maps need the gt "
                "mask; 'none' has no channel) — click-based prediction does "
                "not apply to it")
        cfg, model, state = load_run(run_dir, best=best, cfg=cfg)
        return cls(model, state.params, state.batch_stats,
                   **_click_kwargs_from_cfg(cfg, kwargs))

    @classmethod
    def from_torch(cls, path: str, cfg=None, partial: bool = False,
                   rename=None, **kwargs) -> "Predictor":
        """Serve a torch ``.pth`` state_dict directly — no training run
        needed.  The reference's own accumulated checkpoints (it always
        warm-started from one, train_pascal.py:103) become TPU predictors
        in one call.

        ``cfg`` defaults to :class:`train.Config`'s reference hyperparameter
        point (DANet-R101, 4-channel 512² input) — the architecture the
        reference's checkpoints were trained on.  ``rename`` maps foreign
        key naming onto this framework's (see utils.torch_interop);
        ``partial=True`` tolerates missing/extra keys (e.g. a re-sized
        head), keeping fresh-init values for the gaps.
        """
        from .train.config import Config
        from .utils.torch_interop import (
            load_torch_file,
            torch_state_dict_to_params,
        )

        cfg = cfg or Config()
        if cfg.task != "instance":
            raise ValueError("Predictor.from_torch serves the click-guided "
                             f"instance path; got task={cfg.task!r}")
        if cfg.data.guidance not in _POINT_GUIDANCE:
            raise ValueError(
                f"cfg's guidance family ({cfg.data.guidance!r}) is not "
                "derivable from clicks alone; click-based prediction does "
                "not apply to it")
        model = model_from_config(cfg)
        h, w = cfg.data.crop_size
        variables = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, h, w, cfg.model.in_channels), jnp.float32),
            train=False)
        init_params = variables["params"]
        init_stats = variables.get("batch_stats", {})

        # Shape-only templates so imported-vs-kept is distinguishable
        # (a concrete template leaf and a kept leaf would look identical).
        as_struct = lambda t: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
        params, stats = torch_state_dict_to_params(
            load_torch_file(path), as_struct(init_params),
            as_struct(init_stats), rename=rename,
            allow_missing=partial, allow_unused=partial)

        imported = [0, 0]  # [from checkpoint, kept fresh-init]

        def place(new, old):
            if isinstance(new, jax.ShapeDtypeStruct):
                imported[1] += 1
                return old
            imported[0] += 1
            return jnp.asarray(new)

        params = jax.tree.map(place, params, init_params)
        stats = jax.tree.map(place, stats, init_stats)
        if imported[0] == 0:
            raise ValueError(
                f"warm start from {path} imported 0 of {imported[1]} "
                "leaves — checkpoint keys do not match this model; check "
                "the architecture/naming (or pass a rename callable)")
        return cls(model, params, stats,
                   **_click_kwargs_from_cfg(cfg, kwargs))

    def prepare(self, image: np.ndarray,
                points: Any) -> tuple[np.ndarray, tuple[int, int, int, int]]:
        """:func:`prepare_input` with this predictor's settings: image +
        clicks -> (network input at ``self.resolution``, paste-back bbox).
        Pure host-side numpy — safe to run concurrently from many client
        threads (the serve front door does exactly that)."""
        return prepare_input(image, points, relax=self.relax,
                             zero_pad=self.zero_pad,
                             resolution=self.resolution,
                             alpha=self.alpha, guidance=self.guidance)

    def forward_prepared(self, concat: np.ndarray) -> np.ndarray:
        """(B, H, W, C) prepared crops -> (B, H, W) float32 probability
        maps — the raw batched compiled forward.

        The single code path under :meth:`predict_batch` AND the serve
        micro-batcher (serve/service.py): one compile per distinct leading
        batch dimension, every later call at that B is dispatch-only.  A
        single (H, W, C) crop is accepted and treated as B=1.  Per-lane
        results are independent of the other lanes' CONTENT (eval-mode
        BN, per-sample attention) — at a fixed batch shape a lane is
        bitwise reproducible whatever rides alongside it, which is what
        lets the serve batcher pad with dead lanes at no numerical cost.
        With a ``mesh``, the batch additionally pads/shards over the data
        axis (the jit's in_shardings owns device placement).
        """
        concat = np.asarray(concat, np.float32)
        if concat.ndim == 3:
            concat = concat[None]
        if self.mesh is not None:
            # Pad to the data-axis extent only (a model axis does not shard
            # the batch); the jit's in_shardings owns the device placement.
            from .parallel.mesh import DATA_AXIS, pad_to_multiple
            padded, n = pad_to_multiple({"concat": concat},
                                        self.mesh.shape[DATA_AXIS])
            return np.asarray(self._forward(padded["concat"]))[:n, ..., 0]
        return np.asarray(self._forward(concat))[..., 0]

    def paste_back(self, prob: np.ndarray, bbox: tuple[int, int, int, int],
                   shape_hw: tuple[int, int]) -> np.ndarray:
        """One crop-space probability map -> full-image coordinates with
        the relax border shaved (the val metric's mask_relax paste-back,
        reference train_pascal.py:290)."""
        return np.clip(crop2fullmask(prob, bbox, shape_hw,
                                     zero_pad=self.zero_pad,
                                     relax=self.relax),
                       0.0, 1.0)

    def predict(self, image: np.ndarray, points: Any) -> np.ndarray:
        """(H, W, 3) image + (4, 2) xy clicks -> (H, W) float32 probability
        mask in full-image coordinates (relax border shaved, as in the val
        metric path, reference train_pascal.py:290)."""
        return self.predict_batch(image, [points])[0]

    def predict_batch(self, image: np.ndarray,
                      points_list: Sequence[Any]) -> list[np.ndarray]:
        """Segment N objects of one image in a single device dispatch.

        ``points_list``: N click sets -> list of N full-res probability
        masks (same contract as :meth:`predict`).  All N crops go through
        one batched forward — the all-objects-of-an-image labeling case at
        1/N the dispatch overhead.  One compile per distinct N; reuse the
        same N (padding with repeats if needed) to stay dispatch-only, or
        use ``serve.InferenceService`` which pads to power-of-two buckets
        for you.  With a ``mesh``, the crop batch shards over the data
        axis (padded to its extent) — multi-chip inference with no other
        changes.
        """
        if len(points_list) == 0:  # not `not points_list`: ndarray-safe
            return []
        prepared = [self.prepare(image, pts) for pts in points_list]
        probs = self.forward_prepared(np.stack([c for c, _ in prepared]))
        return [self.paste_back(probs[i], bbox, image.shape[:2])
                for i, (_, bbox) in enumerate(prepared)]


class SemanticPredictor:
    """Whole-image multi-class inference for ``task='semantic'`` runs.

    Mirrors the semantic eval pipeline (pipeline.py:
    build_semantic_eval_transform): fixed resize to the training crop size,
    forward, per-pixel argmax of the primary head, nearest-resize of the
    class map back to the input size (class ids must stay exact).

    >>> p = SemanticPredictor.from_run("work/run_0")
    >>> classes = p.predict(image)       # (H, W) uint8 class ids
    """

    def __init__(self, model, params, batch_stats,
                 resolution: tuple[int, int] = (513, 513),
                 mean: Sequence[float] | None = None,
                 std: Sequence[float] | None = None):
        self.model = model
        self.resolution = tuple(resolution)
        variables = {"params": params, "batch_stats": batch_stats}

        def forward(x):
            outputs = _apply_with_normalize(model, variables, mean, std, x)
            # Argmax on device: one (H, W) int map crosses the wire, not
            # the (H, W, C) logits.
            return jnp.argmax(outputs[0], axis=-1).astype(jnp.int32)

        def forward_probs(x):
            outputs = _apply_with_normalize(model, variables, mean, std, x)
            return jax.nn.softmax(outputs[0].astype(jnp.float32), axis=-1)

        self._forward = jax.jit(forward)
        self._forward_probs = jax.jit(forward_probs)

    @classmethod
    def from_run(cls, run_dir: str, best: bool = True, cfg=None,
                 **kwargs) -> "SemanticPredictor":
        if cfg is None:
            cfg = load_run_config(run_dir)
        if cfg.task != "semantic":
            raise ValueError(
                f"SemanticPredictor is the whole-image multi-class path; "
                f"this run was trained with task={cfg.task!r} (use "
                f"Predictor for click-guided instance runs)")
        cfg, model, state = load_run(run_dir, best=best, cfg=cfg)
        kwargs.setdefault("resolution", tuple(cfg.data.crop_size))
        return cls(model, state.params, state.batch_stats, **kwargs)

    def predict(self, image: np.ndarray, mode: str = "resize",
                overlap: float = 0.5) -> np.ndarray:
        """(H, W, 3) RGB in [0, 255] -> (H, W) class-id map.

        ``mode='resize'`` (default): squeeze the whole image to the training
        resolution and nearest-resize the class map back — the eval
        pipeline's protocol, one forward.  ``mode='slide'``: tile the image
        at native resolution with training-crop-sized windows (stride =
        ``(1 - overlap) * crop``), average the softmax probabilities where
        windows overlap, argmax once — the standard full-resolution protocol
        for images larger than the crop.  Every window is the same fixed
        shape, so sliding costs ONE compiled program regardless of image
        size.

        uint8 when the model's class count fits (the PNG-writable common
        case); int32 otherwise — never a silent modulo-256 wrap."""
        image = np.asarray(image, np.float32)
        if image.ndim != 3 or image.shape[-1] != 3:
            raise ValueError(f"expected (H, W, 3) RGB image, got "
                             f"{image.shape}")
        dtype = np.uint8 if self.model.nclass <= 256 else np.int32
        if mode == "resize":
            resized = imaging.resize(np.clip(image, 0.0, 255.0),
                                     self.resolution, imaging.CUBIC)
            classes = np.asarray(self._forward(resized[None]))[0]
            full = imaging.resize(classes.astype(np.float32),
                                  image.shape[:2], imaging.NEAREST)
            return full.astype(dtype)
        if mode != "slide":
            raise ValueError(f"unknown mode {mode!r} (resize | slide)")
        if not 0.0 <= overlap < 1.0:
            raise ValueError(f"overlap must be in [0, 1), got {overlap}")
        ch, cw = self.resolution
        h, w = image.shape[:2]
        hp, wp = max(h, ch), max(w, cw)
        padded = np.zeros((hp, wp, 3), np.float32)
        padded[:h, :w] = np.clip(image, 0.0, 255.0)

        def starts(full: int, crop: int, stride: int) -> list[int]:
            s = list(range(0, full - crop + 1, stride))
            if s[-1] != full - crop:  # final window flush to the edge
                s.append(full - crop)
            return s

        sh = max(1, int(ch * (1.0 - overlap)))
        sw = max(1, int(cw * (1.0 - overlap)))
        probs = np.zeros((hp, wp, self.model.nclass), np.float32)
        for y in starts(hp, ch, sh):
            for x in starts(wp, cw, sw):
                win = padded[y:y + ch, x:x + cw]
                p = np.asarray(self._forward_probs(win[None]))[0]
                probs[y:y + ch, x:x + cw] += p
        # summed probs suffice: the per-pixel hit count is a positive scalar
        # across the class axis, so dividing by it cannot change the argmax
        classes = np.argmax(probs, axis=-1)
        return classes[:h, :w].astype(dtype)


def parse_points(spec: str) -> np.ndarray:
    """CLI point syntax: ``"x1,y1 x2,y2 x3,y3 x4,y4"`` (or ;-separated)."""
    parts = spec.replace(";", " ").split()
    try:
        pts = np.array([[float(v) for v in p.split(",")] for p in parts])
    except ValueError as e:
        raise ValueError(f"bad --points {spec!r}: {e}") from e
    if pts.shape != (4, 2):
        raise ValueError(
            f"--points needs exactly 4 x,y pairs, got shape {pts.shape}")
    return pts


def predict_cli(run_dir: str, image_path: str, points_spec: str | None,
                out_path: str, threshold: float | None = None,
                overlay_path: str | None = None,
                slide: bool = False) -> dict:
    """The ``--predict`` CLI body; dispatches on the run's task.

    Instance runs need ``points_spec`` (the 4 clicks) and write a binary
    mask PNG (``threshold`` defaults to 0.5); semantic runs take the whole
    image and write a class-id PNG — passing clicks or a threshold to one
    is an error, not a silent drop.  Returns a small summary dict.
    """
    from PIL import Image

    from .utils.helpers import overlay_mask

    cfg = load_run_config(run_dir)
    image = np.asarray(Image.open(image_path).convert("RGB"))

    def write_overlay(mask: np.ndarray) -> None:
        if overlay_path:
            over = overlay_mask(image.astype(np.float32) / 255.0,
                                mask.astype(np.float32))
            Image.fromarray((np.clip(over, 0, 1) * 255).astype(np.uint8)
                            ).save(overlay_path)

    if cfg.task == "semantic":
        if points_spec or threshold is not None:
            raise ValueError(
                "this run is task='semantic' (whole-image class map): "
                "--points/--threshold do not apply")
        classes = SemanticPredictor.from_run(run_dir, cfg=cfg).predict(
            image, mode="slide" if slide else "resize")
        Image.fromarray(classes).save(out_path)
        write_overlay(classes > 0)
        present = {int(c): int(n) for c, n in
                   zip(*np.unique(classes, return_counts=True))}
        return {"task": "semantic", "classes": present, "out": out_path,
                "mode": "slide" if slide else "resize"}

    if slide:
        raise ValueError("this run is task='instance' (click-guided crop "
                         "inference): --slide does not apply")
    if not points_spec:
        raise ValueError("this run is task='instance': --points (the 4 "
                         "extreme-point clicks) is required")
    threshold = 0.5 if threshold is None else threshold
    prob = Predictor.from_run(run_dir, cfg=cfg).predict(
        image, parse_points(points_spec))
    mask = prob > threshold
    Image.fromarray((mask * 255).astype(np.uint8)).save(out_path)
    write_overlay(mask)
    return {"task": "instance", "pixels": int(mask.sum()),
            "threshold": threshold, "max_prob": float(prob.max()),
            "out": out_path}


# ---------------------------------------------------------------------------
# Serialized compiled inference (jax.export / StableHLO)
# ---------------------------------------------------------------------------

def export_serialized(predictor, path: str, batch: int | None = None,
                      channels: int | None = None,
                      platforms: Sequence[str] = ("cpu", "tpu")) -> dict:
    """Serialize a predictor's compiled forward as a portable StableHLO
    artifact (``jax.export``) — the deployment-artifact story the torch
    ecosystem gets from TorchScript/ONNX export, done the XLA-native way.

    The artifact freezes weights + graph at the predictor's resolution and
    channel count and runs WITHOUT this package (any process with jax can
    :func:`load_serialized` it), on every platform in ``platforms``
    (multi-platform lowering: one file serves cpu and tpu).

    ``batch=None`` exports with a SYMBOLIC batch dimension — one artifact
    serves any batch size; pass a concrete int to pin it instead (smaller
    artifact, and the fallback when a model's ops reject polymorphism).

    Works for both :class:`Predictor` (output: sigmoid probability maps)
    and :class:`SemanticPredictor` (output: int32 class-id maps); mesh-
    sharded predictors are refused — GSPMD shardings are a property of
    this process's mesh, not of a portable artifact.
    """
    from jax import export as jax_export

    if getattr(predictor, "mesh", None) is not None:
        raise ValueError(
            "export_serialized: predictor was built with mesh=...; "
            "sharded inference is process-local — build an unsharded "
            "Predictor for export")
    ch = channels
    if ch is None:
        # the click path feeds RGB + one guidance channel; the semantic
        # path plain RGB (pipeline contract, prepare_input /
        # build_semantic_eval_transform) — exotic stems pass channels=
        ch = 4 if isinstance(predictor, Predictor) else 3
    if batch is None:
        (b,) = jax_export.symbolic_shape("b")
    else:
        b = int(batch)
    spec = jax.ShapeDtypeStruct((b, *predictor.resolution, ch),
                                jnp.float32)
    exported = jax_export.export(
        predictor._forward, platforms=list(platforms))(spec)
    blob = exported.serialize()
    with open(path, "wb") as f:
        f.write(blob)
    return {"path": path, "bytes": len(blob),
            "input_shape": tuple(str(d) for d in spec.shape),
            "platforms": tuple(platforms)}


def load_serialized(path: str):
    """Load an :func:`export_serialized` artifact into a callable.

    Pure jax on the consumer side — none of this package's model or config
    code runs; weights live inside the artifact.  The call is jitted, so
    repeat invocations at one shape are dispatch-only.
    """
    from jax import export as jax_export

    with open(path, "rb") as f:
        exported = jax_export.deserialize(f.read())
    return jax.jit(exported.call)
