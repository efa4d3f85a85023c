"""End-to-end Trainer throughput on the real chip: host input pipeline
(decode -> augment -> crop -> resize -> guidance -> batch) overlapped with
the compiled train step, measured together through ``Trainer.train_epoch``.

``bench.py`` measures the step alone (data pre-placed); ``bench_input.py``
measures the host pipeline alone.  This script measures what a user actually
gets: the two running concurrently through the prefetch/overlap machinery.
Prints one JSON line per variant.

The variants are full-size DANet-R101 512px configs, so this needs the
chip; ``JAX_PLATFORMS=cpu`` asks by name for a downsized flow check.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: JAX_PLATFORMS=cpu asks for the downsized flow check by name
import jax  # noqa: E402

from distributedpytorch_tpu.backend_health import (  # noqa: E402
    enable_compile_cache,
    require_accelerator,
)

CPU_SMOKE = require_accelerator("scripts/bench_e2e.py") == "cpu"
enable_compile_cache()

from distributedpytorch_tpu.data.fake import make_fake_voc  # noqa: E402
from distributedpytorch_tpu.train import Config, Trainer, apply_overrides  # noqa: E402

# VOC-like image sizes (VOC2012 images are ~500x375) so decode/crop/resize
# cost what it costs on the real dataset.
N_IMAGES = 20 if CPU_SMOKE else 144  # keeps 104 TRAIN images (the round-3
                                 # workload) now that N_VAL is 40 —
                                 # make_fake_voc carves val out of n_images
N_VAL = 2 if CPU_SMOKE else 40   # enough val samples for a stable val rate
                                 # (val >= 10 imgs/s needs > a few seconds
                                 # of samples to time honestly)
IMG_SIZE = (96, 128) if CPU_SMOKE else (375, 500)
BATCH = 8  # also divides the smoke run's 8-device CPU mesh
EPOCHS_TIMED = 1 if CPU_SMOKE else 2  # after a warmup epoch (compile + caches)


def run(fixture_root: str, overrides: dict) -> dict:
    work = tempfile.mkdtemp(prefix="bench_e2e_")
    overrides = dict(overrides)
    schedule = overrides.pop("_schedule", None)  # not a Config field
    if str(overrides.get("data.prepared_cache", "")).startswith("AUTO"):
        # shared across variants on purpose: same crop config -> same
        # fingerprint -> later variants start warm (like a user's epoch 2+)
        overrides["data.prepared_cache"] = os.path.join(
            fixture_root, "prepared")
    cfg = apply_overrides(Config(), {
        "data.root": fixture_root,
        "data.train_batch": BATCH,
        "model.dtype": "float32" if CPU_SMOKE else "bfloat16",
        "optim.lr": 1e-4,
        "work_dir": work,
        "epochs": 1,
        "log_writers": [],
        **overrides,
        # smoke downsizing wins over variant shapes (513^2 on CPU is not a
        # flow check)
        **({"model.backbone": "resnet18", "data.crop_size": [64, 64],
            "model.dtype": "float32"} if CPU_SMOKE else {}),
    })
    try:
        trainer = Trainer(cfg)
        n_batches = len(trainer.train_loader)
        if schedule:
            return run_schedule(trainer, cfg, n_batches, schedule)
        trainer.train_epoch(0)  # warmup: compile + any decode cache fill
        t0 = time.perf_counter()
        for ep in range(1, 1 + EPOCHS_TIMED):
            trainer.train_epoch(ep)
        # train_epoch defers syncs; one param read closes the timed region.
        jax.block_until_ready(jax.tree.leaves(trainer.state.params)[0])
        dt = time.perf_counter() - t0
        echo = cfg.data.echo
        steps = EPOCHS_TIMED * n_batches * echo
        # Fresh-image rate (echoed repeats are NOT fresh data — same rule as
        # the trainer's train/imgs_per_sec); the step rate is what the
        # optimizer sees and is the number data echoing improves.  Count
        # with the variant's EFFECTIVE batch, not the module default — a
        # train_batch override (variant 9) would otherwise under-report by
        # exactly the ratio (round 2's b16 row was halved this way).
        fresh = EPOCHS_TIMED * n_batches * cfg.data.train_batch
        rec = {"imgs_per_sec_per_chip": round(
                   fresh / dt / jax.device_count(), 2),
               "steps": steps}
        if echo > 1:
            rec["step_imgs_per_sec_per_chip"] = round(
                fresh * echo / dt / jax.device_count(), 2)
        # Val-epoch rate (the full protocol: forward + host paste-back +
        # threshold-swept Jaccard); first call compiles the eval step, the
        # second is the steady-state number.
        trainer.validate(log_panels=False)
        vm = trainer.validate(log_panels=False)
        rec["val_imgs_per_sec_per_chip"] = round(
            vm["n_samples"] / vm["seconds"] / jax.device_count(), 2)
        rec["val_seconds"] = round(vm["seconds"], 2)
        return rec
    finally:
        shutil.rmtree(work, ignore_errors=True)


def run_schedule(trainer: Trainer, cfg, n_batches: int,
                 schedule: str) -> dict:
    """Epoch wall-clock INCLUDING validation, for the serial vs
    val_overlap A/B: the plain variants time train epochs and val epochs
    separately, which cannot show what overlap hides.

    Symmetry rules (the A/B is meaningless without them): both schedules
    run EPOCHS_TIMED train epochs and EPOCHS_TIMED evaluations, neither
    pays checkpoint/panel costs inside the timed region (``_eval_metrics``
    / ``finish=False``), and every overlapped validation is joined AFTER a
    timed train epoch it could hide behind — the steady-state pipeline
    shape, achieved by launching the first val just before the clock
    starts and not launching one after the last train epoch."""
    trainer.train_epoch(0)
    trainer._eval_metrics(trainer.state)      # warm eval program + caches
    overlap = schedule == "overlap"
    if overlap:
        trainer._launch_overlapped_val(0, int(trainer.state.step))
    t0 = time.perf_counter()
    for ep in range(1, 1 + EPOCHS_TIMED):
        trainer.train_epoch(
            ep, abort_check=(trainer._poll_overlapped_val_error
                             if overlap else None))
        if overlap:
            trainer._join_overlapped_val(None, finish=False)
            if ep < EPOCHS_TIMED:
                trainer._launch_overlapped_val(
                    ep, int(trainer.state.step))
        else:
            trainer._eval_metrics(trainer.state)
    jax.block_until_ready(jax.tree.leaves(trainer.state.params)[0])
    dt = time.perf_counter() - t0
    fresh = EPOCHS_TIMED * n_batches * cfg.data.train_batch
    return {"schedule": schedule,
            "epoch_incl_val_seconds": round(dt / EPOCHS_TIMED, 2),
            "epoch_incl_val_imgs_per_sec_per_chip": round(
                fresh / dt / jax.device_count(), 2)}


if __name__ == "__main__":
    fixture = tempfile.mkdtemp(prefix="bench_e2e_voc_")
    make_fake_voc(fixture, n_images=N_IMAGES, size=IMG_SIZE, max_objects=2,
                  n_val=N_VAL)
    variants = [
        # reference-shape host pipeline: guidance synthesized on host
        dict(),
        # guidance fused into the compiled step (data.device_guidance)
        {"data.device_guidance": True},
        # + decode-once cache sized to the whole fixture
        {"data.device_guidance": True, "data.decode_cache": N_IMAGES},
        # + data echoing: each loaded batch steps twice
        {"data.device_guidance": True, "data.decode_cache": N_IMAGES,
         "data.echo": 2},
        # everything movable moved on-device: flip + rotate/scale + guidance
        # all inside the compiled step; host does decode -> crop -> resize
        {"data.device_guidance": True, "data.decode_cache": N_IMAGES,
         "data.device_augment": True, "data.device_augment_geom": True},
        # prepared-sample disk cache: decode/crop/resize mmap-read after the
        # fill epoch; host does flip + rotate/scale on the crop + guidance
        {"data.prepared_cache": "AUTO"},
        # + guidance on device: host is flip + rotate/scale + collate only
        {"data.prepared_cache": "AUTO", "data.device_guidance": True},
        # + flip and rotate/scale on device too: host is mmap-read + collate
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.device_augment": True, "data.device_augment_geom": True},
        # + uint8 wire format: 4x fewer H2D bytes and host memcpys
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True},
        # the full package at global batch 16 (fewer dispatches per image)
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.train_batch": 16},
        # fast path + batched val: the reference protocol is bs=1
        # (dispatch-bound); val_batch=8 amortizes it
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.val_batch": 8},
        # + multi-step dispatch: 3 optimizer steps per compiled call
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.steps_per_dispatch": 3},
        # semantic task on its prepared+uint8 fast path (DeepLabV3-R101
        # os=16 513^2 — BASELINE config 4's model at the e2e level)
        {"task": "semantic", "model.name": "deeplabv3", "model.nclass": 21,
         "model.in_channels": 3, "model.output_stride": 16,
         "data.crop_size": [513, 513], "data.val_batch": 8,
         "data.prepared_cache": "AUTO_SEM", "data.uint8_transfer": True},
        # fast path + 1-bit mask wire (data.packbits_masks): ~22% fewer
        # H2D bytes — the lever when placement bounds e2e
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.packbits_masks": True},
        # 14: the stacked headline: fast path +
        # packbits wire + bf16 PAM scores, in the same sequential run as
        # its controls
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.packbits_masks": True,
         "model.pam_score_dtype": "bfloat16"},
        # 15: val-path A/B control — fast path with the OLD plain val
        # (data.val_prepared=false); variants 8/10 minus this row isolate
        # the prepared-val win within one run
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.val_batch": 8,
         "data.val_prepared": False},
        # 16: semantic val-path A/B control (the round-3 1.0 imgs/s row's
        # config, now with val_prepared off vs variant 12's on)
        {"task": "semantic", "model.name": "deeplabv3", "model.nclass": 21,
         "model.in_channels": 3, "model.output_stride": 16,
         "data.crop_size": [513, 513], "data.val_batch": 8,
         "data.prepared_cache": "AUTO_SEM", "data.uint8_transfer": True,
         "data.val_prepared": False},
        # 17: the FULL-RES semantic protocol (metric at native size) on
        # the prepared val path — gt_full served from padded uint8 rows
        {"task": "semantic", "model.name": "deeplabv3", "model.nclass": 21,
         "model.in_channels": 3, "model.output_stride": 16,
         "data.crop_size": [513, 513], "data.val_batch": 8,
         "eval_full_res": True,
         "data.prepared_cache": "AUTO_SEM", "data.uint8_transfer": True},
        # 18: full-res control (plain ragged val path)
        {"task": "semantic", "model.name": "deeplabv3", "model.nclass": 21,
         "model.in_channels": 3, "model.output_stride": 16,
         "data.crop_size": [513, 513], "data.val_batch": 8,
         "eval_full_res": True,
         "data.prepared_cache": "AUTO_SEM", "data.uint8_transfer": True,
         "data.val_prepared": False},
        # 19/20: epoch wall INCLUDING validation, serial vs val_overlap —
        # the overlap hides the val epoch behind the next train epoch
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.val_batch": 8,
         "_schedule": "serial"},
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.val_batch": 8,
         "val_overlap": True, "_schedule": "overlap"},
        # 21: stacked headline + K-step dispatch: a K=3 program keeps the
        # chip busy 3 steps per dispatch (whether placement and dispatch
        # overlap the running step on a local chip: not measured).
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.packbits_masks": True,
         "model.pam_score_dtype": "bfloat16",
         "data.steps_per_dispatch": 3},
        # 22: same with K=6 (half an epoch per dispatch at the bench size)
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.packbits_masks": True,
         "model.pam_score_dtype": "bfloat16",
         "data.steps_per_dispatch": 6},
        # 23: stacked headline + the coalesced one-buffer wire
        # (data.coalesce_wire): one H2D transfer per batch instead of one
        # per leaf — the lever when the fixed per-transfer cost (not
        # bandwidth) bounds placement
        {"data.prepared_cache": "AUTO", "data.device_guidance": True,
         "data.uint8_transfer": True, "data.packbits_masks": True,
         "model.pam_score_dtype": "bfloat16", "data.coalesce_wire": True},
    ]
    sel = sys.argv[1:]
    try:
        for i, ov in enumerate(variants):
            if sel and str(i) not in sel:
                continue
            rec = {"variant": i, **{k: v for k, v in ov.items()}}
            try:
                rec.update(run(fixture, ov))
            except Exception as e:
                rec["error"] = str(e)[:200]
            print(json.dumps(rec), flush=True)
    finally:
        shutil.rmtree(fixture, ignore_errors=True)
