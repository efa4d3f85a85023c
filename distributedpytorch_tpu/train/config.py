"""Experiment configuration.

The reference had no config system: hyperparameters were module-level
constants stuffed into an ``OrderedDict p`` (reference train_pascal.py:44-82),
dataset roots hid in a machine-specific ``mypath`` module (pascal.py:13,33),
checkpoint filenames were hardcoded (train_pascal.py:103,304) and a Comet API
key was committed in source (train_pascal.py:41).  Here the whole experiment
is one nested dataclass tree, JSON-serializable both ways, with dotted-path
CLI overrides — and no secrets in code (anything secret comes from the
environment).

Defaults reproduce the reference's hyperparameter point
(train_pascal.py:50-71): 100 epochs, train batch 16, val batch 1, 4-channel
512² input, SGD lr=5e-8 / momentum 0.9 / wd 5e-4, constant LR (the poly
scheduler existed but was commented out, train_pascal.py:34,164 — it is a
first-class option here), eval every epoch, threshold sweep {0.3, 0.5, 0.8}.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any


@dataclass
class DataConfig:
    seq_len: int = 128                  # task=tokens: ids per sequence
    token_file: str = ""                # task=tokens: a flat uint32 file
                                        # of ids (data/tokens.py), cut
                                        # into seq_len windows; the last
                                        # token_val_samples windows are
                                        # the val split.  "" = the seeded
                                        # synthetic source
    token_samples: int = 64             # task=tokens, synthetic source:
                                        # sequences per epoch
    token_val_samples: int = 8          # task=tokens: val sequences
    source: str = "fs"                  # fs | packed: where samples come
                                        # from.  'fs' decodes JPEG/PNG
                                        # per sample off the dataset
                                        # tree; 'packed' memory-maps the
                                        # pre-decoded, checksummed
                                        # records dptpu-pack wrote
                                        # (data/packed.py — no per-
                                        # sample decode, O(1) seek, the
                                        # governor's rung 0).  Samples
                                        # are bit-identical either way.
    pack_path: str = ""                 # source=packed: the pack ROOT
                                        # dptpu-pack --out wrote; the
                                        # trainer opens
                                        # <pack_path>/<dataset>-<task>-
                                        # <splits> per source
    pack_quarantine: tuple[int, ...] = ()
                                        # source=packed: RAW record
                                        # indices dropped from the TRAIN
                                        # pack's epoch (the recovery
                                        # move for records `dptpu-pack
                                        # --verify` flagged as torn)
    session_log: str = ""               # flywheel: a serve session-log
                                        # directory (serve/session_log)
                                        # mixed into training via
                                        # data/sessions.SessionLogDataset
    session_only: bool = False          # flywheel: train on the session
                                        # log ALONE in replay mode (the
                                        # exact serving inputs, no
                                        # augmentation) — the continuous
                                        # mode's incremental fits
    session_quarantine: tuple[int, ...] = ()
                                        # RAW session record ids dropped
                                        # from the log's epoch (poisoned
                                        # examples the sentinel ledger /
                                        # dptpu-pack --verify named)
    root: str = ""                      # dataset root (was: the mypath module)
    sbd_root: str = ""                  # set: merge SBD into training via
                                        # CombinedDataset, excluding the
                                        # VOC-val overlap.  Instance task:
                                        # the reference's use_sbd recipe
                                        # (train_pascal.py:150-154).
                                        # Semantic task: the standard
                                        # "train_aug" recipe (~10k extra
                                        # images for the DeepLab configs).
    fake: bool = False                  # synth fixture instead of real VOC
    download: bool = False              # fetch + MD5-verify VOC if absent
    train_split: str = "train"
    val_split: str = "val"
    area_thres: int = 500               # instance area filter (pascal.py:36)
    crop_size: tuple[int, int] = (512, 512)
    relax: int = 50                     # bbox relax px (train_pascal.py:127)
    zero_pad: bool = True
    rots: tuple[float, float] = (-20.0, 20.0)
    scales: tuple[float, float] = (0.75, 1.25)
    guidance: str = "nellipse_gaussians"
    guidance_alpha: float = 0.6         # z1 + alpha*z2 (custom_transforms.py:45)
    train_batch: int = 16
    val_batch: int = 1
    loader: str = "threads"             # threads | grain (train loader;
                                        # eval always uses threads, which
                                        # wrap-pads so every sample scores)
    num_workers: int = 2                # loader threads (train_pascal.py:161)
    prefetch: int = 2                   # host-side decoded-batch buffer
    device_prefetch: int = 2            # batches placed on-device ahead
    device_augment: bool = False        # flip on-device (fused into step)
    device_augment_geom: bool = False   # rotation/scale on-device too (the
                                        # device form warps the fixed crop,
                                        # not the pre-crop full image)
    device_guidance: bool = False       # synthesize the guidance channel
                                        # on-device from crop_gt (the most
                                        # expensive host transform; instance
                                        # task, all five guidance families)
    fused_crop_resize: bool = False     # crop+resize as ONE native-kernel
                                        # pass (no materialized crop).
                                        # Wins on the cv2-free native
                                        # imaging backend (+26%); with cv2
                                        # present its SIMD resize is still
                                        # faster — leave off
    prepared_cache: str = ""            # dir for the prepared-sample disk
                                        # cache (FFCV-style): the train
                                        # pipeline's deterministic front
                                        # (instance: decode→crop→resize;
                                        # semantic: decode→resize) is
                                        # computed once per sample and
                                        # mmap-read ever after; flip/rotate/
                                        # guidance stay per-epoch random,
                                        # post-crop.  Keyed by a config
                                        # fingerprint — changing crop knobs
                                        # rebuilds.  ~0.75 MB/sample @512².
    uint8_transfer: bool = False        # ship train batches to the device
                                        # as uint8 (4x fewer H2D bytes and
                                        # host memcpys; the compiled step
                                        # dequantizes on device).  Requires
                                        # prepared_cache (whose arrays are
                                        # uint8-exact by construction).
    packbits_masks: bool = False        # ship the binary train mask at
                                        # 1 bit/pixel (np.packbits on the
                                        # wire, fused bit-ops unpack inside
                                        # the step) — ~22% fewer wire bytes
                                        # on top of uint8_transfer; pays
                                        # when H2D placement bounds e2e.
                                        # Instance task + uint8_transfer
                                        # only.
    coalesce_wire: bool = False         # pack the train batch's device-
                                        # bound uint8 leaves into ONE
                                        # (B, bytes) buffer per batch: one
                                        # H2D transfer instead of one per
                                        # leaf, so the fixed per-transfer
                                        # cost is paid once (on a local
                                        # chip: not measured).  The compiled
                                        # step slices the leaves back out
                                        # (static offsets, fused by XLA).
                                        # Requires uint8_transfer; composes
                                        # with packbits_masks (the packed
                                        # row rides the same buffer).
    val_prepared: bool = True           # when prepared_cache is set, serve
                                        # the crop-res VAL protocol from a
                                        # prepared cache too (eval is fully
                                        # deterministic, so the WHOLE
                                        # per-epoch decode→crop→resize(→
                                        # guidance) front caches; instance
                                        # mode also caches full-res gt/void
                                        # as packed bits for the paste-back
                                        # metric).  With uint8_transfer the
                                        # val wire ships uint8 as well.
                                        # SEMANTICS: the cached val image
                                        # is uint8-rounded (same <=0.5/255
                                        # perturbation the train cache
                                        # makes; masks/bboxes bit-exact),
                                        # so val metrics move ~1e-3 vs the
                                        # plain path — set false for
                                        # bit-exact protocol comparisons.
                                        # The semantic full-res protocol
                                        # (eval_full_res) composes: its
                                        # native-res gt caches as padded
                                        # uint8 id rows (gt_full).
    val_max_im_size: tuple[int, int] = (512, 512)
                                        # eval-cache budget for native-res
                                        # mask rows (instance packed
                                        # gt/void bits AND the semantic
                                        # eval_full_res gt_full ids):
                                        # raise for datasets with images
                                        # larger than VOC's 500px sides
                                        # (changing it rebuilds the val
                                        # cache)
    decode_cache: int = 0               # decode-once LRU over this many
                                        # images (FFCV-style; instance mode
                                        # revisits an image once per object
                                        # per epoch).  ~0.7 MB/image host
                                        # RAM; 0 = off.
    steps_per_dispatch: int = 1         # >1: scan this many optimizer
                                        # steps inside ONE compiled call
                                        # (each over its own batch) —
                                        # per-step dispatch overhead drops
                                        # K-fold, the lever when the host's
                                        # dispatch path (not data prep) is
                                        # the bound.  Epoch-tail batches
                                        # run through the single-step
                                        # program.  Mutually exclusive with
                                        # echo>1.
    echo: int = 1                       # data echoing (Choi et al. 2019,
                                        # arXiv:1907.05550): step each loaded
                                        # batch this many times — recovers
                                        # throughput when the host input
                                        # pipeline, not the chip, is the
                                        # bottleneck.  With device_augment
                                        # each echo draws fresh augmentation
                                        # randomness.
    governor: str = "observe"           # input-feed governor
                                        # (data/governor.py): off |
                                        # observe (default: the ladder's
                                        # decisions are logged to
                                        # run_dir/governor.jsonl and the
                                        # registry, nothing is actuated)
                                        # | auto (decisions applied: hot
                                        # prefetch resize, epoch-boundary
                                        # device-path flip, auto-armed
                                        # echo with hysteresis disarm).
                                        # Multi-host auto routes every
                                        # ladder input through the
                                        # consensus primitive (stall =
                                        # max across hosts, parallel/
                                        # consensus.py), so all hosts
                                        # take identical decisions.
    governor_target: float = 0.1        # windowed input-stall fraction
                                        # the governor keeps the feed
                                        # under (and the bench feed
                                        # gate's threshold)
    governor_window: int = 16           # stall-window size in ticks
                                        # (log-cadence samples); smaller
                                        # reacts faster, larger resists
                                        # transients
    max_echo: int = 4                   # clamp for the governor's auto-
                                        # armed echo factor
                                        # (ceil(1/(1-stall)) capped here;
                                        # a manually-set data.echo is
                                        # never clamped)


@dataclass
class ModelConfig:
    name: str = "danet"                 # danet | deeplabv3 | deeplabv3plus
                                        # | fcn | pspnet | encnet |
                                        # nemotron_h (task=tokens)
    lm_config: str = ""                 # nemotron_h: a preset's name
                                        # (models/nemotron_h.py PRESETS;
                                        # "" = tiny) or a JSON file of
                                        # the published config keys (the
                                        # benchmark's configs/*.json)
    nclass: int = 1                     # binary/sigmoid head (DANet(1, ...))
    backbone: str = "resnet101"
    output_stride: int | None = None
    in_channels: int = 4                # RGB + guidance heatmap
    remat_policy: str = ""              # with model.remat: a jax.
                                        # checkpoint_policies name (e.g.
                                        # dots_saveable — keep conv/matmul
                                        # outputs, recompute elementwise/BN
                                        # chains) instead of full recompute
    bn_fp32_stats: bool = True          # False: BN batch stats in the
                                        # compute dtype (bf16) instead of
                                        # flax's f32 promotion — the A/B
                                        # for the convert+reduce chains the
                                        # op profiles blame for the b16
                                        # regression
    dtype: str = "float32"              # 'bfloat16' = BASELINE config 3
    loss_weights: tuple[float, ...] | None = None
    pam_block_size: int | None = None   # blocked position-attention
    attention_impl: str = "auto"        # BOTH DANet attention branches at
                                        # once: auto (flash Pallas kernels
                                        # for bf16 compute on TPU — the
                                        # mixed-precision hot path — XLA
                                        # einsum otherwise, per the f32
                                        # crossover sweep) | xla (einsum
                                        # everywhere, the reference-parity
                                        # form) | flash (force the Pallas
                                        # kernels; Mosaic only — fails
                                        # off-TPU).
                                        # pam_impl below overrides the
                                        # position branch when set.
    pam_impl: str = ""                  # position-branch override of
                                        # attention_impl: auto | einsum |
                                        # flash (pallas) | ring (sequence-
                                        # parallel PAM over the mesh's
                                        # model axis).  "" = inherit
                                        # attention_impl.  auto = flash for
                                        # bf16-TPU; otherwise einsum while
                                        # the N^2 scores fit HBM, flash
                                        # beyond (memory feasibility)
    pam_score_dtype: str | None = None  # einsum PAM only: dtype the N x N
                                        # score matrix materializes in.
                                        # 'bfloat16' halves the dominant
                                        # non-MXU HBM round trip of the
                                        # flagship step; softmax arithmetic
                                        # and einsum accumulation stay f32.
                                        # Measured round 3: +2.5% (b8) /
                                        # +5.7% (b16) step rate, accuracy
                                        # curve tracks f32 within epoch
                                        # noise (conv run d) — recommended
                                        # on; default stays f32 for bit-
                                        # parity with the reference.
                                        # None = f32 (exact reference-like
                                        # scores)
    quantization: str = ""              # SERVE-side post-training weight
                                        # quantization (serve/quantize):
                                        # 'int8' = per-channel symmetric
                                        # int8 kernels, dequant-at-use in
                                        # the jitted forward, JA002-
                                        # audited against QuantPolicy's
                                        # declared dequant points.
                                        # Training always runs
                                        # full-precision; dptpu-serve and
                                        # dptpu-aot read this knob (their
                                        # --quantize flag overrides).
                                        # "" = serve the checkpoint as
                                        # trained
    remat: bool = False                 # rematerialize backbone blocks
    moe_experts: int = 0                # >0: MoE FFN in the DANet head
    moe_hidden: int | None = None       # expert MLP width (default: channels)
    moe_k: int = 1                      # top-k routing (1 = Switch)
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01        # load-balancing aux-loss weight
    aux_head: bool = False              # DeepLabV3/FCN/PSPNet/EncNet:
                                        # auxiliary FCN head on c3 (second
                                        # output; weight it via
                                        # loss_weights, e.g. [1.0,0.4])
    encnet_codes: int = 32              # EncNet: context-encoding codebook
                                        # size (the SE branch's codewords)
    ccnet_recurrence: int = 2           # CCNet: weight-shared criss-cross
                                        # steps (R=2 = full-image receptive
                                        # field through one hop)
    guidance_inject: str = "stem"       # DANet: where the click-guidance
                                        # channel enters — 'stem'
                                        # (reference parity: backbone sees
                                        # the 4-channel concat) or 'head'
                                        # (backbone sees RGB only; the
                                        # guidance joins at the head via a
                                        # zero-init 1x1 projection), which
                                        # makes the backbone encoding
                                        # reusable across a session's
                                        # refinement clicks
                                        # (serve/sessions.py)


@dataclass
class TrainConfig:
    """Raw step-speed levers (train/precision.py + parallel/step.py):
    the ROADMAP item-4 trio, each off by default for reference parity."""
    precision: str = "float32"          # float32 | bfloat16: 'bfloat16' is
                                        # the mixed-precision policy (bf16
                                        # compute, f32 master params/
                                        # optimizer/loss — train/precision
                                        # .py) threaded through the model
                                        # build and the compiled steps;
                                        # overrides model.dtype.  jaxaudit
                                        # JA002 audits the bf16 step
                                        # against the policy's declared
                                        # accumulation points.
    reduce_buckets: int = 0             # >0: data-parallel gradients are
                                        # all-reduced in this many reverse-
                                        # topological buckets (explicit
                                        # shard_map psums) instead of the
                                        # compiler's fused end-of-backward
                                        # reduce — head-param buckets
                                        # become schedulable as soon as the
                                        # early backward produces them, so
                                        # their reduce overlaps the
                                        # remaining backbone backward (the
                                        # arxiv 1711.00705 bucketed-
                                        # overlap recipe; async -start
                                        # forms contract-pinned on TPU).
                                        # Pure data parallel only (no TP/
                                        # ring PAM); loss/BN take DDP
                                        # semantics (per-shard loss
                                        # normalization averaged across
                                        # shards, cross-replica BN stats).
                                        # 0 = GSPMD-implicit (reference-
                                        # parity numerics).


@dataclass
class OptimConfig:
    name: str = "sgd"                   # sgd (reference parity,
                                        # train_pascal.py:118) | adamw
                                        # (decoupled weight decay; its two
                                        # moment buffers are where
                                        # mesh.shard_opt_state pays most)
    lr: float = 5e-8
    momentum: float = 0.9
    weight_decay: float = 5e-4
    adam_b1: float = 0.9                # adamw only
    adam_b2: float = 0.999
    adam_eps: float = 1e-8
    schedule: str = "constant"          # constant | poly | cosine
    poly_power: float = 0.9
    warmup_steps: int = 0
    accum_steps: int = 1                # the reference's nAveGrad knob
    loss_scale: float = 1.0             # static loss scaling for bf16
                                        # regimes: loss is scaled before the
                                        # backward pass and gradients
                                        # unscaled after, guarding tiny
                                        # gradients against bf16/f32
                                        # underflow at aggressive LRs.  The
                                        # reported loss is unscaled.  1.0 =
                                        # off (the flagship's bf16 runs are
                                        # stable without it).
    grad_clip_norm: float | None = None
    freeze: tuple[str, ...] = ()        # param-path prefixes to freeze
    lr_mult: dict[str, float] | None = None  # per-prefix LR multipliers


@dataclass
class ParallelConfig:
    """The declarative sharding strategy (parallel/plan.py): one knob
    that resolves to a validated mesh + composed state layout.  Leave
    ``strategy`` unset to keep driving the low-level ``mesh.*`` knobs —
    the planner then derives the plan FROM them, so every run carries
    one either way."""
    strategy: str = ""                  # "" = derive from mesh.* |
                                        # dp | dp_tp | dp_zero1 |
                                        # dp_tp_zero1 | auto (walk the
                                        # mesh-shape ladder with the
                                        # memory model, smallest model
                                        # axis that fits per-chip HBM)
    data: int | None = None             # explicit data-axis size
                                        # (None = all devices not
                                        # claimed by model, per slice)
    model: int = 0                      # explicit model-axis size
                                        # (0 = derive: 1 for the dp
                                        # family, 2 for the tp family)
    hbm_budget_gb: float = 0.0          # auto only: per-chip HBM budget
                                        # override (0 = detect from the
                                        # backend's bytes_limit, 16 GiB
                                        # fallback on backends without
                                        # memory stats)


@dataclass
class MeshConfig:
    data: int | None = None             # None = all devices (per slice
                                        # when slices > 1)
    model: int = 1                      # tensor-parallel axis size
    slices: int = 1                     # DCN factor of the data axis:
                                        # >1 = hierarchical DP over a
                                        # multi-slice topology
                                        # (make_hybrid_mesh)
    process_is_granule: bool | None = None
                                        # DCN granule choice for slices>1:
                                        # None = auto (device slice_index
                                        # when it matches, else hosts);
                                        # true forces host granules
    shard_params: bool = False          # TP: shard kernels over `model`
    shard_opt_state: bool = False       # ZeRO-1: shard optimizer state
                                        # over `data` (1/N optimizer
                                        # memory per device for one
                                        # param-sized all-gather per step)


@dataclass
class CheckpointConfig:
    keep_latest: int = 3
    snapshot_every: int = 100           # epoch snapshots (train_pascal.py:56)
    best_metric_init: float = 0.0       # reference pinned 0.913 (…:177)
    warm_start: str | None = None       # .pth to import weights from (the
                                        # reference's unconditional torch
                                        # warm start, train_pascal.py:103)
    warm_start_partial: bool = False    # tolerate missing/unused keys
    async_save: bool = True
    save_on_preempt: bool = True        # SIGTERM -> final full-state save
    preempt_check_every: int = 32       # stop-consensus cadence (steps)
    exact_resume: bool = True           # continue a preempted epoch at the
                                        # batch it stopped (no batch trains
                                        # twice); false = replay the epoch
                                        # from its start (batches repeat,
                                        # none skipped)
    digest: bool = False                # stamp each save's meta with a
                                        # sha256 over the param bytes —
                                        # the byte-identical-restore
                                        # invariant becomes checkable
                                        # across process deaths (the
                                        # chaos crash_loop scenario's
                                        # hook).  Costs one full param
                                        # readback per save; off by
                                        # default.


@dataclass
class SentinelConfig:
    """Self-healing training (train/sentinel.py): detection thresholds
    and the rollback budget.  Off by default — the trainer's legacy
    responses (log-and-continue / debug_asserts abort) stay pinned."""
    enabled: bool = False               # verdicts + rollback-and-replay
    ema_beta: float = 0.9               # loss-EMA smoothing
    suspect_factor: float = 3.0         # loss > f x EMA -> suspect
    diverged_factor: float = 10.0       # loss > f x EMA -> diverged
    warmup_steps: int = 8               # EMA updates before spike
                                        # verdicts arm (non-finite always
                                        # armed)
    monitor_grads: bool = False         # train step also emits
                                        # (grad_norm, update/param ratio)
                                        # — a second (2,) output on the
                                        # compiled program, so contracts
                                        # of sentinel-monitored programs
                                        # differ from the canonical ones
    grad_factor: float = 10.0           # grad_norm > f x EMA -> suspect
    update_ratio_max: float | None = None
                                        # ||update||/||param|| above this
                                        # -> diverged (None = off)
    max_rollbacks: int = 2              # rollback budget: consecutive
                                        # rollbacks without a cleanly
                                        # completed epoch in between
                                        # before the run fails loudly
                                        # (chaos CircuitBreaker)


@dataclass
class Config:
    task: str = "instance"              # instance (reference) | semantic
                                        # | tokens (a token model under
                                        # its loss: models.TOKEN_MODELS)
    data: DataConfig = field(default_factory=DataConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    sentinel: SentinelConfig = field(default_factory=SentinelConfig)
    epochs: int = 100
    eval_every: int = 1                 # nTestInterval (train_pascal.py:62)
    val_overlap: bool = False           # run each validation on a thread
                                        # CONCURRENTLY with the next train
                                        # epoch (eval forwards interleave
                                        # on device; paste-back runs beside
                                        # the loader) — hides the val epoch
                                        # behind training wall-clock.
                                        # Best-save/logging land when the
                                        # next train epoch finishes — so a
                                        # HARD crash (no SIGTERM) during
                                        # that epoch loses one more epoch
                                        # than serial mode would (the
                                        # deferred checkpoint never
                                        # landed).  Costs one extra full
                                        # state in HBM while in flight;
                                        # single-process only (two threads
                                        # issuing collectives could
                                        # deadlock across hosts).
    eval_thresholds: tuple[float, ...] = (0.3, 0.5, 0.8)
    eval_tta_scales: tuple[float, ...] = ()  # semantic TTA: average softmax
                                        # probs over these input scales
                                        # (1.0 = the base pass)
    eval_tta_flip: bool = False         # semantic TTA: also average the
                                        # horizontal flip
    eval_full_res: bool = False         # semantic: score mIoU at each
                                        # image's ORIGINAL resolution
                                        # (probabilities bilinearly resized
                                        # back per sample — the standard
                                        # DeepLab protocol) instead of at
                                        # the resized eval crop
    eval_bf16_probs: bool = True        # semantic full-res/TTA: read the
                                        # softmax volumes back in bfloat16
                                        # — halves the dominant D2H cost
                                        # (~22 MB/image f32 at 513², the
                                        # measured bound of the full-res
                                        # protocol on a slow wire); argmax-
                                        # after-resize is tie-epsilon
                                        # sensitive only (tested).  Also
                                        # halves the INSTANCE val logit
                                        # readback (boundary-pixel rounding
                                        # at the thresholds; tested).
                                        # false restores exact f32
                                        # readback everywhere.
    eval_device_fullres: bool = True    # semantic full-res (non-TTA): do
                                        # the per-sample native-res resize
                                        # + argmax ON DEVICE (separable
                                        # weight-matmul warp, ops/warp.py)
                                        # and ship only the uint8 class
                                        # map — 21x fewer wire bytes and
                                        # no per-image host resize (the
                                        # 1.5 imgs/s r4 bound).  Applies
                                        # when every image in the batch
                                        # fits data.val_max_im_size and
                                        # the run is single-process;
                                        # false restores the host resize
                                        # path (bit-exact legacy).
    seed: int = 0
    work_dir: str = "runs"              # run_<N> dirs created under this
    resume: str | None = None           # checkpoint dir to resume from, or
                                        # 'auto' = newest prior run under
                                        # work_dir with a saved step
    debug_asserts: bool = False         # data-contract checks (…:188-190)
    log_every_steps: int = 50
    experiment_name: str = "experiment"
    log_writers: tuple[str, ...] = ("console", "jsonl")
                                        # console | jsonl | tensorboard |
                                        # comet (key from COMET_API_KEY)
    comet_project: str = ""             # reference used 'Attention' (:41)
    comet_workspace: str = ""
    profile_epoch: int | None = None    # XPlane-trace this epoch (0-based)
    telemetry: bool = True              # goodput/MFU accounting + the
                                        # SIGUSR2 on-demand trace trigger
                                        # (telemetry/); false = every
                                        # account() is a no-op (the <=2%
                                        # overhead contract's baseline)


def _to_jsonable(obj: Any) -> Any:
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: _to_jsonable(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if isinstance(obj, tuple):
        return list(obj)
    return obj


def _from_dict(cls, d: dict):
    # f.type is a *string* under `from __future__ import annotations`;
    # resolve real types once so nested dataclasses recurse properly.
    import typing
    hints = typing.get_type_hints(cls)
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name not in d:
            continue
        v = d[f.name]
        ftype = hints.get(f.name, f.type)
        if isinstance(ftype, type) and dataclasses.is_dataclass(ftype) \
                and isinstance(v, dict):
            v = _from_dict(ftype, v)
        elif f.name in ("crop_size", "rots", "scales", "loss_weights",
                        "eval_thresholds", "eval_tta_scales",
                        "freeze", "val_max_im_size", "pack_quarantine",
                        "session_quarantine") and isinstance(v, list):
            v = tuple(v)
        kwargs[f.name] = v
    return cls(**kwargs)


_SUBCONFIGS = {"data": DataConfig, "model": ModelConfig,
               "train": TrainConfig, "optim": OptimConfig,
               "parallel": ParallelConfig, "mesh": MeshConfig,
               "checkpoint": CheckpointConfig,
               "sentinel": SentinelConfig}


def to_json(cfg: Config, path: str | None = None) -> str:
    s = json.dumps(_to_jsonable(cfg), indent=2)
    if path:
        with open(path, "w") as f:
            f.write(s + "\n")
    return s


def from_json(source: str) -> Config:
    """Parse a JSON string or (if it names an existing file) a JSON file."""
    import os
    if os.path.exists(source):
        with open(source) as f:
            source = f.read()
    d = json.loads(source)
    kwargs = {}
    for k, v in d.items():
        if k in _SUBCONFIGS:
            kwargs[k] = _from_dict(_SUBCONFIGS[k], v)
        else:
            kwargs[k] = v
    base = Config()
    for f in dataclasses.fields(Config):
        if f.name not in kwargs:
            kwargs[f.name] = getattr(base, f.name)
        elif f.name in ("eval_thresholds", "eval_tta_scales",
                        "log_writers") \
                and isinstance(kwargs[f.name], list):
            kwargs[f.name] = tuple(kwargs[f.name])
    return Config(**kwargs)


def apply_overrides(cfg: Config, overrides: dict[str, Any] | list[str]) -> Config:
    """Dotted-path overrides: ``{"optim.lr": 1e-3}`` or ``["optim.lr=1e-3"]``.

    String values are JSON-decoded when possible so CLI args round-trip to
    numbers/bools/lists.
    """
    if isinstance(overrides, list):
        parsed = {}
        for item in overrides:
            k, _, v = item.partition("=")
            parsed[k.strip()] = v.strip()
        overrides = parsed
    cfg = dataclasses.replace(cfg)  # shallow copy of the root
    for path, value in overrides.items():
        if isinstance(value, str):
            try:
                value = json.loads(value)
            except (ValueError, TypeError):
                pass
        *parents, leaf = path.split(".")
        node = cfg
        trail = []
        for p in parents:
            trail.append((node, p))
            node = getattr(node, p)
        if not any(f.name == leaf for f in dataclasses.fields(node)):
            raise KeyError(f"unknown config field: {path}")
        if isinstance(getattr(node, leaf), tuple) and isinstance(value, list):
            value = tuple(value)
        new_leaf = dataclasses.replace(node, **{leaf: value})
        for parent, name in reversed(trail):
            new_leaf = dataclasses.replace(parent, **{name: new_leaf})
        cfg = new_leaf
    return cfg


def flatten(cfg: Config) -> dict[str, Any]:
    """Flat ``section.field -> value`` view — feeds the param report
    (the reference's ``generate_param_report``, train_pascal.py:169)."""
    out: dict[str, Any] = {}

    def walk(prefix: str, obj: Any):
        if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
            for f in dataclasses.fields(obj):
                walk(f"{prefix}{f.name}.", getattr(obj, f.name))
        else:
            out[prefix[:-1]] = obj

    walk("", cfg)
    return out
