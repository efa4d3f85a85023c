"""What a task is: one object handed to the loop.

``Config.task`` names a :class:`Task` in :data:`TASKS`; the trainer looks it
up once (:func:`get`) and from then on asks the object, never the name.  A
task is everything the loop needs and nothing the loop decides: the config
checks that are the task's, its datasets, the batch it ships, the loss type
of its step, its device stage, its evaluation and the metric that gates a
best save.  ``models.MODEL_TASKS`` stays the model -> tasks table.

Adding a task is one more object here (docs/DESIGN.md, "Adding a task or a
model family"); the trainer is not edited.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data import (
    CombinedDataset,
    PackedTokens,
    PreparedInstanceDataset,
    PreparedSemanticDataset,
    SBDInstanceSegmentation,
    SBDSemanticSegmentation,
    SyntheticTokens,
    VOCInstanceSegmentation,
    VOCSemanticSegmentation,
    build_eval_transform,
    build_prepared_post_transform,
    build_prepared_semantic_post_transform,
    build_semantic_eval_transform,
    build_semantic_train_transform,
    build_train_transform,
)
from ..data.pipeline import (
    build_prepared_eval_post_transform,
    build_prepared_semantic_eval_post_transform,
)
from ..models import build_from_config
from ..parallel import DEVICE_KEYS, NEXT_TOKEN, TOKENS_KEY, prefetch_to_device
from .evaluate import (
    batch_debug_asserts,
    evaluate,
    evaluate_semantic,
    semantic_batch_debug_asserts,
)


class DataContext(NamedTuple):
    """The little of the trainer a task's ``datasets`` reads."""
    root: str             # the resolved dataset root (fake fixtures included)
    open_pack: Callable   # (dataset_name, splits, transform, quarantine=())


class ValWire(NamedTuple):
    """What the prepared val wire ships that the eval step must undo."""
    device_guidance: bool = False   # 3-channel batches: it adds guidance
    packbits: bool = False          # 1-bit crop_gt: the eval step unpacks

    def preprocess(self, cfg):
        """The eval step's input stage, or None."""
        if not self.device_guidance:
            return None
        # prepared val ships bare image channels; append the guidance
        # channel on device with the DETERMINISTIC val semantics
        # (extreme_points_fixed — bit-exact vs the host at pert=0).
        # The rng argument is never consumed at is_val.
        from ..ops.guidance_device import make_device_guidance
        gstage = make_device_guidance(
            family=cfg.data.guidance, alpha=cfg.data.guidance_alpha,
            is_val=True)
        fixed_key = jax.random.PRNGKey(0)
        return lambda b: gstage(b, fixed_key)


@dataclasses.dataclass(frozen=True)
class Task:
    name: str
    loss_type: str          # handed to make_train_step / make_eval_step
    device_keys: tuple      # the batch keys placed on the device
    check: Callable         # (cfg): the task's own config checks
    datasets: Callable      # (cfg, ctx) -> (train_set, val_set, ValWire)
    init_input: Callable    # (cfg) -> create_train_state's dummy batch
    memory_inputs: Callable  # (cfg, model, state_struct) -> for auto_plan
    evaluate: Callable      # (eval_step, state, loader, cfg, mesh, val_wire)
    best: Callable          # (metrics) -> (name, value), higher is better
    best_init: float | None = None  # None: checkpoint.best_metric_init
    pack_kind: str | None = None    # the dptpu-pack kind; None: no pack
    pack_area_thres: Callable = lambda cfg: None
    batch_asserts: Callable = lambda batch, cfg: None   # debug_asserts
    #: what a governor flip would move besides augmentation
    guidance_on_device: Callable = lambda cfg: False
    #: (cfg, *, flip, geom, guidance) -> the host transform stack, given
    #: what runs on the device
    train_transform: Callable | None = None
    #: (cfg, device_augment, device_guidance) -> the step's fused stage or
    #: None; no member at all: no device stage, and no flip to one
    device_stage: Callable | None = None
    #: (task, cfg) -> the task as this configuration has it, where the
    #: model's configuration decides part of it (:func:`get` with ``cfg``)
    for_config: Callable | None = None


# ------------------------------------------------------------ image tasks
def _host_stages(cfg) -> dict:
    """What of the train augmentation the host keeps, given what the
    config put on the device."""
    d = cfg.data
    return {"flip": not d.device_augment,
            "geom": not (d.device_augment and d.device_augment_geom)}


def _host_guidance(cfg) -> str:
    # device guidance: the host ships bare image channels as 'concat'
    return "none" if cfg.data.device_guidance else cfg.data.guidance


def _non_semantic_check(cfg) -> None:
    if cfg.eval_tta_scales or cfg.eval_tta_flip:
        raise ValueError(
            "eval_tta_scales/eval_tta_flip apply to the semantic task "
            "only (the instance protocol is the reference's fixed "
            "threshold sweep)")
    if cfg.eval_full_res:
        raise ValueError(
            "eval_full_res applies to the semantic task only (the "
            "instance protocol already scores at full resolution via "
            "crop2fullmask paste-back)")


_PACKBITS_NEEDS = (
    "data.packbits_masks packs the BINARY instance mask for "
    "the uint8 wire — it requires task=instance (semantic gt "
    "is class ids, not bits) and data.uint8_transfer (the "
    "packed row rides the uint8 fast path)")


def _non_instance_check(cfg) -> None:
    if cfg.data.packbits_masks:
        raise ValueError(_PACKBITS_NEEDS)
    if cfg.data.device_guidance:
        raise ValueError("data.device_guidance applies to the "
                         "instance task only (semantic has no "
                         "guidance channel)")


def _instance_check(cfg) -> None:
    if cfg.model.nclass != 1:
        # The instance protocol is binary by construction (sigmoid
        # prediction pasted back per object, reference
        # train_pascal.py:262,283-291); a multi-channel head would fail
        # opaquely inside the evaluator's paste-back.
        raise ValueError(
            f"task='instance' requires model.nclass=1 (binary sigmoid "
            f"head), got {cfg.model.nclass}; use task='semantic' for "
            "multi-class")
    _non_semantic_check(cfg)
    if cfg.data.packbits_masks and not cfg.data.uint8_transfer:
        raise ValueError(_PACKBITS_NEEDS)
    if cfg.data.uint8_transfer and not (cfg.data.device_guidance
                                        or cfg.data.guidance == "none"):
        raise ValueError(
            "data.uint8_transfer with HOST-side guidance is a no-op on "
            "the dominant tensor: concatenating the float guidance map "
            "promotes 'concat' back to float32, so the advertised 4x "
            "wire saving never happens — set data.device_guidance=true "
            "(the map is synthesized on device from the uint8 crop_gt) "
            "or data.guidance=none")
    if cfg.data.device_guidance:
        from ..ops.guidance_device import FAMILIES as _DEV_FAM
        if cfg.data.guidance not in _DEV_FAM:
            raise ValueError(
                f"data.device_guidance supports {_DEV_FAM}, not "
                f"{cfg.data.guidance!r}")


def _tokens_check(cfg) -> None:
    _non_semantic_check(cfg)
    _non_instance_check(cfg)


def _instance_train_transform(cfg, *, flip, geom, guidance):
    return build_train_transform(
        crop_size=cfg.data.crop_size, relax=cfg.data.relax,
        zero_pad=cfg.data.zero_pad, rots=cfg.data.rots,
        scales=cfg.data.scales, alpha=cfg.data.guidance_alpha,
        guidance=guidance, flip=flip, geom=geom,
        fused_crop_resize=cfg.data.fused_crop_resize)


def _semantic_train_transform(cfg, *, flip, geom, guidance=None):
    return build_semantic_train_transform(
        crop_size=cfg.data.crop_size, rots=cfg.data.rots,
        scales=cfg.data.scales, flip=flip, geom=geom)


def _source(cfg, ctx: DataContext, fs_cls, dataset: str, split, transform,
            quarantine=(), **fs_kw):
    """One (dataset, split) of the run's source: the pack's pre-decoded
    mmap records (data/packed.py: no dataset walk, no per-sample decode —
    samples bit-identical to the fs classes by construction), or the fs
    class (a requested download already happened, gated+barriered)."""
    if cfg.data.source == "packed":
        return ctx.open_pack(
            dataset, split if isinstance(split, list) else [split],
            transform, quarantine=quarantine)
    return fs_cls(cfg.data.sbd_root if dataset == "sbd" else ctx.root,
                  split=split, transform=transform, **fs_kw)


def _instance_datasets(cfg, ctx: DataContext) -> tuple:
    prepared = bool(cfg.data.prepared_cache)
    # Prepared cache owns the deterministic crop stage itself; the
    # wrapped dataset must stay untransformed.
    train_tf = None if prepared else _instance_train_transform(
        cfg, guidance=_host_guidance(cfg), **_host_stages(cfg))
    #: val fast path (data.val_prepared): eval is deterministic end
    #: to end, so the whole per-epoch val front caches — decode,
    #: crop, resize, full-res metric masks; with device_guidance
    #: the wire also drops to 3-channel uint8 and the jitted eval
    #: step appends the guidance channel (is_val semantics).
    val_prep = prepared and cfg.data.val_prepared
    val_wire = ValWire(
        device_guidance=val_prep and cfg.data.device_guidance,
        packbits=val_prep and cfg.data.packbits_masks)
    val_tf = None if val_prep else build_eval_transform(
        crop_size=cfg.data.crop_size, relax=cfg.data.relax,
        zero_pad=cfg.data.zero_pad, alpha=cfg.data.guidance_alpha,
        guidance=cfg.data.guidance)
    fs_kw = dict(preprocess=True,  # SBD: same always-rebuild as VOC
                 area_thres=cfg.data.area_thres,
                 decode_cache=cfg.data.decode_cache)
    train_set = _source(cfg, ctx, VOCInstanceSegmentation, "voc",
                        cfg.data.train_split, train_tf,
                        quarantine=cfg.data.pack_quarantine, **fs_kw)
    val_set = _source(cfg, ctx, VOCInstanceSegmentation, "voc",
                      cfg.data.val_split, val_tf, **fs_kw)
    prep_kw = dict(crop_size=cfg.data.crop_size, relax=cfg.data.relax,
                   zero_pad=cfg.data.zero_pad,
                   fused_crop_resize=cfg.data.fused_crop_resize,
                   uint8_arrays=cfg.data.uint8_transfer)
    if val_prep:
        val_set = PreparedInstanceDataset(
            val_set, cfg.data.prepared_cache, **prep_kw,
            eval_protocol=True, max_im_size=cfg.data.val_max_im_size,
            post_transform=build_prepared_eval_post_transform(
                alpha=cfg.data.guidance_alpha,
                guidance=_host_guidance(cfg),
                uint8_wire=cfg.data.uint8_transfer,
                packbits=cfg.data.packbits_masks))
    if cfg.data.sbd_root:
        # the reference's use_sbd recipe (train_pascal.py:150-154),
        # live: merge SBD train+val, drop its VOC-val overlap
        sbd = _source(cfg, ctx, SBDInstanceSegmentation, "sbd",
                      ["train", "val"], train_tf, **fs_kw)
        train_set = CombinedDataset([train_set, sbd], excluded=[val_set])
    if cfg.data.session_log:
        # flywheel: serve session logs as training data
        # (data/sessions.py).  session_only replays the EXACT
        # serving inputs (the continuous mode's incremental
        # fits); otherwise the log joins the VOC(+SBD) mix as a
        # sampled source under the standard transform stack.
        if prepared:
            raise ValueError(
                "data.session_log does not compose with "
                "data.prepared_cache — the session log already "
                "IS a pre-decoded, pre-cropped source; drop one "
                "of the two")
        from ..data.sessions import SessionLogDataset
        if cfg.data.session_only:
            sessions = SessionLogDataset(
                cfg.data.session_log, mode="replay",
                quarantine=cfg.data.session_quarantine)
            if tuple(sessions.resolution) != tuple(cfg.data.crop_size):
                raise ValueError(
                    f"session log {cfg.data.session_log} was "
                    f"captured at resolution "
                    f"{sessions.resolution} but this run trains "
                    f"at data.crop_size={cfg.data.crop_size} — "
                    "replay feeds the serving inputs verbatim, "
                    "so the two must match")
            train_set = sessions
        else:
            sessions = SessionLogDataset(
                cfg.data.session_log, mode="sample",
                transform=train_tf,
                quarantine=cfg.data.session_quarantine)
            train_set = CombinedDataset(
                [train_set, sessions], excluded=[val_set])
    elif cfg.data.session_only:
        raise ValueError(
            "data.session_only requires data.session_log")
    if prepared:
        train_set = PreparedInstanceDataset(
            train_set, cfg.data.prepared_cache, **prep_kw,
            post_transform=build_prepared_post_transform(
                rots=cfg.data.rots, scales=cfg.data.scales,
                alpha=cfg.data.guidance_alpha,
                guidance=_host_guidance(cfg), **_host_stages(cfg),
                uint8_wire=cfg.data.uint8_transfer,
                packbits=cfg.data.packbits_masks))
    return train_set, val_set, val_wire


def _semantic_datasets(cfg, ctx: DataContext) -> tuple:
    prepared = bool(cfg.data.prepared_cache)
    train_tf = None if prepared else _semantic_train_transform(
        cfg, **_host_stages(cfg))
    train_set = _source(cfg, ctx, VOCSemanticSegmentation, "voc",
                        cfg.data.train_split, train_tf,
                        quarantine=cfg.data.pack_quarantine,
                        decode_cache=cfg.data.decode_cache)
    # Val has no decode cache (one sample per image, scanned
    # sequentially — an LRU smaller than the split gets zero hits).
    # Built before the SBD merge so the merge can exclude its
    # overlap (SBD train covers most of VOC val — the standard
    # "train_aug" recipe needs the exclusion).
    #
    # val fast path (data.val_prepared): the semantic val front
    # (decode → resize → clamp) is deterministic and identical to
    # the prepared cache's stage1, so serve val from a prepared
    # cache too — with uint8_transfer the 25 MB f32 val batches
    # drop to uint8.  The full-res protocol composes: its
    # native-resolution gt caches as padded uint8 id rows,
    # emitted ragged as ``gt_full``.
    val_prep = prepared and cfg.data.val_prepared
    val_tf = None if val_prep else build_semantic_eval_transform(
        crop_size=cfg.data.crop_size, keep_fullres=cfg.eval_full_res)
    val_set = _source(cfg, ctx, VOCSemanticSegmentation, "voc",
                      cfg.data.val_split, val_tf)
    prep_kw = dict(crop_size=cfg.data.crop_size,
                   uint8_arrays=cfg.data.uint8_transfer)
    if val_prep:
        val_set = PreparedSemanticDataset(
            val_set, cfg.data.prepared_cache, **prep_kw,
            keep_fullres=cfg.eval_full_res,
            max_im_size=cfg.data.val_max_im_size,
            post_transform=build_prepared_semantic_eval_post_transform(
                uint8_wire=cfg.data.uint8_transfer))
    if cfg.data.sbd_root:
        sbd = _source(cfg, ctx, SBDSemanticSegmentation, "sbd",
                      ["train", "val"], train_tf,
                      decode_cache=cfg.data.decode_cache)
        train_set = CombinedDataset([train_set, sbd], excluded=[val_set])
    if prepared:
        train_set = PreparedSemanticDataset(
            train_set, cfg.data.prepared_cache, **prep_kw,
            post_transform=build_prepared_semantic_post_transform(
                rots=cfg.data.rots, scales=cfg.data.scales,
                **_host_stages(cfg),
                uint8_wire=cfg.data.uint8_transfer))
    return train_set, val_set, ValWire()


def _image_init_input(cfg) -> dict:
    h, w = cfg.data.crop_size
    return {"input_shape": (1, h, w, cfg.model.in_channels)}


def _image_memory_inputs(cfg, model, state_struct) -> tuple:
    h, w = cfg.data.crop_size
    # device-bound train tensors, f32 on device (the uint8 wire
    # dequantizes inside the step): concat + crop_gt (+void)
    return state_struct, (cfg.data.train_batch * h * w
                          * (cfg.model.in_channels + 2) * 4)


def _image_device_stage(cfg, device_augment: bool, device_guidance: bool,
                        *, semantic: bool):
    """The fused on-device augmentation (+ guidance synthesis) stage
    for the compiled step, or None when both are off.  The ONE
    constructor shared by the config path (build time) and the
    governor's rung-2 flip — a config-enabled run and a
    governor-flipped run must train through the identical stage."""
    if not (device_augment or device_guidance):
        return None
    from ..ops.augment import make_device_augment

    guidance_fn = None
    if device_guidance:  # instance task only (validated at build)
        from ..ops.guidance_device import make_device_guidance
        guidance_fn = make_device_guidance(
            family=cfg.data.guidance, alpha=cfg.data.guidance_alpha)
    return make_device_augment(  # host flip (+geom) disabled
        hflip=device_augment,
        scale_rotate=device_augment and cfg.data.device_augment_geom,
        rots=cfg.data.rots, scales=cfg.data.scales,
        semantic=semantic, guidance_fn=guidance_fn)


def _instance_evaluate(eval_step, state, loader, cfg, mesh, val_wire):
    return evaluate(
        eval_step, state, loader, thresholds=cfg.eval_thresholds,
        relax=cfg.data.relax, zero_pad=cfg.data.zero_pad, mesh=mesh,
        debug_asserts=cfg.debug_asserts, packed_masks=val_wire.packbits,
        bf16_readback=cfg.eval_bf16_probs)


def _semantic_evaluate(eval_step, state, loader, cfg, mesh, val_wire):
    return evaluate_semantic(
        eval_step, state, loader, nclass=cfg.model.nclass, mesh=mesh,
        tta_scales=cfg.eval_tta_scales, tta_flip=cfg.eval_tta_flip,
        debug_asserts=cfg.debug_asserts, bf16_probs=cfg.eval_bf16_probs,
        device_fullres=(tuple(cfg.data.val_max_im_size)
                        if cfg.eval_device_fullres else None))


def _jaccard(metrics: dict) -> tuple:
    # the semantic evaluator files its mIoU under the same key
    return "jaccard", metrics["jaccard"]


# ------------------------------------------------------------- token task
def _token_model(cfg):
    """The configuration's token model: a description until it is
    initialised, so building it to ask it something is free."""
    return build_from_config(cfg.model, dtype=cfg.model.dtype)


def _tokens_for_config(task: "Task", cfg) -> "Task":
    """The token task as the model's configuration has it: the loss type
    the model trains under (next-token unless it says otherwise) and the
    device stage its loss needs (a block-diffusion model's noise,
    ``ops/diffusion.py``: the step's first on-device data stage)."""
    model = _token_model(cfg)
    loss_type = getattr(model, "loss_type", task.loss_type)
    stage = getattr(model, "device_stage", None)
    if loss_type == task.loss_type and stage is None:
        return task  # the model states nothing of its own
    return dataclasses.replace(
        task, loss_type=loss_type,
        device_stage=stage and (lambda cfg, augment, guidance: stage))


def _tokens_datasets(cfg, ctx: DataContext) -> tuple:
    """(train, val) token sources (data/tokens.py): the packed uint32
    file when ``data.token_file`` names one — its last
    ``token_val_samples`` windows are the val split — else the seeded
    synthetic source."""
    # the ids a source may draw are the model's to say
    vocab = _token_model(cfg).vocab_size
    n_val = cfg.data.token_val_samples
    if cfg.data.token_file:
        whole = PackedTokens(cfg.data.token_file, cfg.data.seq_len)
        if len(whole) <= n_val:
            raise ValueError(
                f"{whole} holds {len(whole)} sequences, not more than "
                f"data.token_val_samples={n_val}: nothing to train on")
        return (PackedTokens(cfg.data.token_file, cfg.data.seq_len,
                             vocab, count=len(whole) - n_val),
                PackedTokens(cfg.data.token_file, cfg.data.seq_len,
                             vocab, first=-n_val), ValWire())
    return (SyntheticTokens(cfg.data.token_samples, cfg.data.seq_len,
                            vocab, seed=cfg.seed),
            SyntheticTokens(n_val, cfg.data.seq_len, vocab,
                            seed=cfg.seed + 1), ValWire())


def _tokens_memory_inputs(cfg, model, state_struct) -> tuple:
    # ids are a few KB: the input-bytes rule of the image nets
    # would cost the activations at nothing.  The model knows its
    # own (block inputs kept, one block live, the logits); the
    # batch shards over at most every device.
    per_device = max(1, cfg.data.train_batch // len(jax.devices()))
    return (state_struct, cfg.data.train_batch * cfg.data.seq_len * 4,
            model.activation_bytes(per_device, cfg.data.seq_len))


def _tokens_evaluate(eval_step, state, loader, cfg, mesh, val_wire):
    # mean of the token task's loss over the val sequences (the loader
    # wrap-pads its last batch: every sequence is scored)
    losses = [eval_step(state, b)[1] for b in prefetch_to_device(
        iter(loader), mesh, size=cfg.data.device_prefetch,
        keys=(TOKENS_KEY,))]
    loss = float(np.mean(jax.device_get(losses)))
    return {"loss": loss, "perplexity": float(np.exp(loss))}


# --------------------------------------------------------------- registry
INSTANCE = Task(
    name="instance", loss_type="multi_sigmoid", device_keys=DEVICE_KEYS,
    check=_instance_check, datasets=_instance_datasets,
    init_input=_image_init_input, memory_inputs=_image_memory_inputs,
    evaluate=_instance_evaluate, best=_jaccard,
    pack_kind="instance", pack_area_thres=lambda cfg: cfg.data.area_thres,
    batch_asserts=lambda batch, cfg: batch_debug_asserts(
        batch, packed_masks=cfg.data.packbits_masks),
    # guidance synthesis is the expensive host stage
    guidance_on_device=lambda cfg: cfg.data.guidance != "none",
    train_transform=_instance_train_transform,
    device_stage=functools.partial(_image_device_stage, semantic=False))

SEMANTIC = Task(
    name="semantic", loss_type="multi_softmax", device_keys=DEVICE_KEYS,
    check=_non_instance_check, datasets=_semantic_datasets,
    init_input=_image_init_input, memory_inputs=_image_memory_inputs,
    evaluate=_semantic_evaluate, best=_jaccard,
    pack_kind="semantic",
    batch_asserts=lambda batch, cfg: semantic_batch_debug_asserts(
        batch, cfg.model.nclass),
    train_transform=_semantic_train_transform,
    device_stage=functools.partial(_image_device_stage, semantic=True))

#: training of a token model under the token task's loss: another batch
#: ({tokens}), another loss, no BatchNorm statistics.  The loss type and the
#: device stage are the model's configuration's to say (``for_config``):
#: next-token and none, unless the model states its own
TOKENS = Task(
    name="tokens", loss_type=NEXT_TOKEN, device_keys=(TOKENS_KEY,),
    for_config=_tokens_for_config,
    check=_tokens_check, datasets=_tokens_datasets,
    init_input=lambda cfg: {"input_shape": (1, cfg.data.seq_len),
                            "input_dtype": jnp.int32},
    memory_inputs=_tokens_memory_inputs, evaluate=_tokens_evaluate,
    # gated on the negated val loss, which never reaches the Jaccard
    # scale's 0
    best=lambda metrics: ("neg_loss", -metrics["loss"]), best_init=-1e30)

TASKS = {t.name: t for t in (INSTANCE, SEMANTIC, TOKENS)}


def get(name: str, cfg=None) -> Task:
    """The task of that name; with ``cfg``, as that configuration has it."""
    if name not in TASKS:
        raise ValueError(f"unknown task: {name!r} ({' | '.join(TASKS)})")
    task = TASKS[name]
    if cfg is not None and task.for_config is not None:
        return task.for_config(task, cfg)
    return task
