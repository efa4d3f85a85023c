"""Per-stage budget of the end-to-end fast path.

``bench_e2e.py`` measures the overlapped pipeline as a user gets it; this
script measures each stage of the SAME config in isolation, so the gap
between the e2e number and its theoretical ceiling can be attributed:

  host  — the Trainer's own train loader (prepared cache prebuilt in a
          warmup epoch): mmap read + per-epoch random stage + collate.
          CPU-safe, no accelerator touched (the model is swapped for a
          tiny one — it never runs).
  place — ``shard_batch`` on one real host batch, looped: the placement
          thread's per-batch capacity (layout/copy + H2D DMA).  TPU.
  step  — the compiled train step on one pre-placed batch, looped:
          ``bench.py``'s chip rate re-measured inside this exact config.
          With ``data.steps_per_dispatch=K`` this measures the K-step
          program (items_per_step = K*batch), so the K-step executable's
          chip-side efficiency can be compared against K singles.  TPU.
  dispatch — host-blocking time of *issuing* one step call (sync, then
          time the async enqueue alone).  This is the per-step host cost
          that ``data.steps_per_dispatch`` amortizes; measuring it tells
          whether K-step dispatch can pay on this host at all.  TPU.
  valhost — the Trainer's VAL loader iterated alone (decode + eval
          transform + collate; no device).  Val has no prepared cache by
          design, so this stage names how much of a slow measured val
          rate is host-side
          before any caching work is considered.  CPU-safe.

Under perfect overlap e2e == min(host, place, step); the printed
``ideal_overlap_imgs_per_sec`` vs the measured bench_e2e row is the
overlap slack worth engineering at, and the slowest stage is the lever.

Usage:
  python scripts/bench_breakdown.py host            # CPU-safe stage
  python scripts/bench_breakdown.py place step      # chip stages
  python scripts/bench_breakdown.py host place step dispatch [k=v ...]
Default config = bench_e2e variant 8 (prepared + device guidance + uint8
wire), the measured-48.7 row.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STAGES = [a for a in sys.argv[1:]
          if a in ("host", "place", "step", "dispatch", "valhost",
                   "valplace", "valstep", "valmetric")]
OVERRIDES = [a for a in sys.argv[1:] if "=" in a]
if not STAGES:
    STAGES = ["host", "place", "step"]

#: JAX_PLATFORMS=cpu asks for the downsized flow check by name
import jax  # noqa: E402

from distributedpytorch_tpu.backend_health import (  # noqa: E402
    enable_compile_cache,
    require_accelerator,
)

CPU_SMOKE = require_accelerator("scripts/bench_breakdown.py") == "cpu"
enable_compile_cache()

import numpy as np  # noqa: E402

from distributedpytorch_tpu.data.fake import make_fake_voc  # noqa: E402
from distributedpytorch_tpu.parallel import shard_batch  # noqa: E402
from distributedpytorch_tpu.train import Config, Trainer, apply_overrides  # noqa: E402
from distributedpytorch_tpu.utils.profiling import throughput  # noqa: E402

N_IMAGES = 8 if CPU_SMOKE else 120
IMG_SIZE = (96, 128) if CPU_SMOKE else (375, 500)
BATCH = 8  # divides the smoke run's 8-device CPU mesh too
DEVICE_KEYS = ("concat", "crop_gt", "crop_void")


def make_trainer(fixture: str, work: str, tiny_model: bool) -> Trainer:
    cfg = apply_overrides(Config(), [
        f"data.root={fixture}",
        f"data.train_batch={BATCH}",
        "data.area_thres=0",
        # bench_e2e variant 8 — the measured-48.7 fast path
        f"data.prepared_cache={os.path.join(fixture, 'prepared')}",
        "data.device_guidance=true",
        "data.uint8_transfer=true",
        "model.dtype=" + ("float32" if tiny_model else "bfloat16"),
        "optim.lr=1e-4",
        "epochs=1", "log_writers=[]",
        *(["model.backbone=resnet18", "model.output_stride=8",
           "data.crop_size=[64,64]", "model.dtype=float32"]
          if (tiny_model and CPU_SMOKE) else
          ["model.backbone=resnet18", "model.output_stride=8"]
          if tiny_model else []),
        # user overrides LAST (apply_overrides is last-write-wins): the
        # printed `overrides` record must be the config that actually ran
        *OVERRIDES,
    ])
    import dataclasses
    return Trainer(dataclasses.replace(cfg, work_dir=work))


def one_host_batch(tr: Trainer) -> dict:
    tr.train_loader.set_epoch(0)
    batch = next(iter(tr.train_loader))
    return {k: v for k, v in batch.items() if k in DEVICE_KEYS}


def stage_host(fixture: str, work: str) -> dict:
    tr = make_trainer(fixture, work, tiny_model=True)
    loader = tr.train_loader
    n_batches = len(loader)
    loader.set_epoch(0)            # warmup epoch fills the prepared cache
    for _ in loader:
        pass
    t0 = time.perf_counter()
    epochs = 2
    for ep in range(1, 1 + epochs):
        loader.set_epoch(ep)
        for _ in loader:
            pass
    dt = time.perf_counter() - t0
    tr.close()
    bs = tr.cfg.data.train_batch
    return {"host_imgs_per_sec": round(epochs * n_batches * bs / dt, 2),
            "host_ms_per_batch": round(dt / (epochs * n_batches) * 1e3, 1)}


def stage_valhost(fixture: str, work: str) -> dict:
    """Val loader alone: decode -> eval transform (incl. ragged full-res
    gt passthrough when configured) -> collate, two passes."""
    tr = make_trainer(fixture, work, tiny_model=True)
    loader = tr.val_loader
    n = 0
    for b in loader:       # warm OS page cache like a 2nd-epoch val
        n += b[next(iter(b))].shape[0] if hasattr(
            b[next(iter(b))], "shape") else len(b[next(iter(b))])
    t0 = time.perf_counter()
    n = 0
    for b in loader:
        first = b[next(iter(b))]
        n += first.shape[0] if hasattr(first, "shape") else len(first)
    dt = time.perf_counter() - t0
    tr.close()
    return {"valhost_imgs_per_sec": round(n / dt, 2),
            "valhost_ms_per_img": round(dt / max(n, 1) * 1e3, 1)}


def stage_place(tr: Trainer, batch: dict, prefix: str = "",
                n_real: int | None = None) -> dict:
    """H2D placement rate of ``batch``; shared by the train and val
    (``prefix='val'``) pipelines.  ``n_real`` counts only genuine samples
    when the batch carries pad rows (the evaluator discards them, so a
    padded-row rate would overstate val throughput by the pad factor)."""
    mesh = tr.mesh
    nbytes = sum(np.asarray(v).nbytes for v in batch.values())
    with mesh:
        shard_batch(mesh, batch)   # warm layouts
        reps = 5 if CPU_SMOKE else 30
        t0 = time.perf_counter()
        for _ in range(reps):
            placed = shard_batch(mesh, batch)
            jax.block_until_ready(placed)
        dt = time.perf_counter() - t0
    bs = n_real if n_real is not None \
        else next(iter(batch.values())).shape[0]
    return {f"{prefix}place_imgs_per_sec": round(reps * bs / dt, 2),
            f"{prefix}place_ms_per_batch": round(dt / reps * 1e3, 1),
            (f"{prefix}_batch_mb" if prefix else "batch_mb"):
                round(nbytes / 2**20, 2)}


def stage_step(tr: Trainer, batch: dict) -> dict:
    mesh = tr.mesh
    k = tr.cfg.data.steps_per_dispatch
    with mesh:
        placed = shard_batch(mesh, batch)
        box = [tr.state]

        if tr.multi_train_step is not None:
            # K-step program: one compiled call consumes K batches (the
            # same placed batch K times is fine — batches are read-only;
            # only the state arg is donated).
            def one():
                box[0], lv = tr.multi_train_step(box[0],
                                                 *([placed] * k))
                return lv
        else:
            def one():
                box[0], loss = tr.train_step(box[0], placed)
                return loss

        bs = next(iter(batch.values())).shape[0]
        stats = throughput(one, steps=5 if CPU_SMOKE else 20,
                           warmup=2, items_per_step=bs * k)
        # the step donates its state arg: the trainer's original buffers
        # are gone after the first call — hand the live state back so a
        # later stage (dispatch) doesn't touch deleted arrays.
        tr.state = box[0]
    # per-BATCH ms (÷k) so the field stays comparable with host_/place_
    # ms_per_batch across K; the per-call time is the K-step program's
    # whole dispatch.
    ms_per_call = bs * k / stats["items_per_sec"] * 1e3
    return {"step_imgs_per_sec": round(stats["items_per_sec"], 2),
            "step_ms_per_batch": round(ms_per_call / k, 1),
            "step_ms_per_call": round(ms_per_call, 1),
            "steps_per_dispatch": k}


def one_val_batch(tr: Trainer) -> tuple[dict, dict, int]:
    """(full val batch, placed-shape device subset, REAL sample count) —
    the evaluator's own split and padding (evaluate.py pads to the mesh's
    device multiple before sharding; without it a val_batch of 1 cannot
    shard).  Rates must count only the real samples: the evaluator
    discards the pad rows."""
    from distributedpytorch_tpu.parallel import pad_to_multiple
    batch = next(iter(tr.val_loader))
    dev = {k: v for k, v in batch.items() if k in DEVICE_KEYS}
    n_real = next(iter(dev.values())).shape[0]
    dev, _ = pad_to_multiple(dev, tr.mesh.devices.size)
    return batch, dev, n_real


def stage_valstep(tr: Trainer, dev: dict, n_real: int) -> dict:
    """The jitted eval forward alone (loss + logits), pre-placed batch."""
    mesh = tr.mesh
    with mesh:
        placed = shard_batch(mesh, dev)

        def one():
            outputs, loss = tr.eval_step(tr.state, placed)
            return loss, outputs[0]

        stats = throughput(one, steps=5 if CPU_SMOKE else 20, warmup=2,
                           items_per_step=n_real)
    return {"valstep_imgs_per_sec": round(stats["items_per_sec"], 2),
            "valstep_ms_per_batch": round(
                n_real / stats["items_per_sec"] * 1e3, 1)}


def stage_valmetric(tr: Trainer, batch: dict, dev: dict) -> dict:
    """D2H readback of the primary logits + the host paste-back/threshold
    sweep — the two val terms no forward overlap hides.  Instance protocol
    only (the semantic path scores its confusion matrix on device).

    Mirrors evaluate()'s own loop via its helpers (_sigmoid/_as_list,
    bbox-or-get_bbox fallback) and the trainer's ACTUAL eval config — a
    hardcoded workload here would attribute numbers to a config that
    never ran."""
    if tr.cfg.task != "instance":
        return {"valmetric_skipped": "instance-only stage"}
    import numpy as _np

    from distributedpytorch_tpu.ops.metrics import np_jaccard_thresholds
    from distributedpytorch_tpu.train.evaluate import _as_list, _sigmoid
    from distributedpytorch_tpu.utils.helpers import (
        crop2fullmask,
        get_bbox,
        tens2image,
    )
    thresholds = tuple(tr.cfg.eval_thresholds)
    relax = tr.cfg.data.relax
    zero_pad = tr.cfg.data.zero_pad
    mesh = tr.mesh
    with mesh:
        import jax.numpy as jnp

        def fetch(out0):
            # mirror the evaluator's wire: eval_bf16_probs (default on)
            # casts the logit volume to bf16 ON DEVICE before the D2H
            if tr.cfg.eval_bf16_probs:
                out0 = out0.astype(jnp.bfloat16)
            return _np.asarray(jax.device_get(out0), _np.float32)

        placed = shard_batch(mesh, dev)
        outputs, _ = tr.eval_step(tr.state, placed)
        fetch(outputs[0])                   # compile + settle
        # forward + D2H readback together; subtract
        # valstep_ms_per_batch to get the readback term alone
        reps = 3 if CPU_SMOKE else 10
        t0 = time.perf_counter()
        for _ in range(reps):
            outputs, _ = tr.eval_step(tr.state, placed)
            logits = fetch(outputs[0])
        dt_read = (time.perf_counter() - t0) / reps
    probs = _sigmoid(logits)  # fetch() already widened to f32
    n = len(batch["gt"]) if isinstance(batch["gt"], list) \
        else batch["gt"].shape[0]
    gts = _as_list(batch["gt"], n)
    voids = _as_list(batch.get("void_pixels", [None] * n), n)
    bboxes = _as_list(batch["bbox"], n) if "bbox" in batch else [None] * n
    t0 = time.perf_counter()
    reps_m = 3 if CPU_SMOKE else 10
    for _ in range(reps_m):
        for j in range(n):
            gt = tens2image(_np.asarray(gts[j]))
            if gt.max() <= 0.5:
                continue
            if bboxes[j] is not None:
                bbox = tuple(int(v) for v in _np.asarray(bboxes[j]))
            else:
                bbox = get_bbox(gt > 0.5, pad=relax, zero_pad=zero_pad)
            pred = tens2image(probs[j])
            full = crop2fullmask(pred, bbox, gt.shape[:2],
                                 zero_pad=zero_pad, relax=relax)
            void = None if voids[j] is None \
                else tens2image(_np.asarray(voids[j]))
            np_jaccard_thresholds(full, thresholds, gt > 0.5, void)
    dt_metric = (time.perf_counter() - t0) / reps_m
    return {"valfwdread_ms_per_batch": round(dt_read * 1e3, 1),
            "valmetric_ms_per_batch": round(dt_metric * 1e3, 1),
            "valmetric_imgs_per_sec": round(n / dt_metric, 2)}


def stage_dispatch(tr: Trainer, batch: dict) -> dict:
    """Host-blocking cost of issuing one (possibly K-step) train-step call.

    Sync the device first, then time the call itself: JAX dispatch is
    async, so the timed interval is trace-cache lookup + arg handling +
    runtime enqueue — pure host work, none of the chip's compute.  This is
    the term ``data.steps_per_dispatch`` divides by K; if it is already
    small next to the step's chip time, K-step dispatch has nothing to
    amortize (and its burstier K-batch consumption can make e2e WORSE on
    a 1-core host)."""
    mesh = tr.mesh
    k = tr.cfg.data.steps_per_dispatch
    step = tr.multi_train_step if tr.multi_train_step is not None \
        else tr.train_step
    with mesh:
        args = [shard_batch(mesh, batch)] * k
        box = [tr.state]
        box[0], out = step(box[0], *args)   # compile
        jax.device_get(out)
        reps = 3 if CPU_SMOKE else 15
        issue = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            box[0], out = step(box[0], *args)
            issue += time.perf_counter() - t0
            # drain before the next timed call: unsynced back-to-back
            # enqueues would inflate the number toward full step time once
            # the in-flight limit is hit.  device_get of the loss output
            # waits for the step, so each timed call starts on an idle
            # queue and measures pure enqueue cost.
            jax.device_get(out)
        tr.state = box[0]   # state was donated; keep the live one
    return {"dispatch_ms_per_call": round(issue / reps * 1e3, 2),
            "dispatch_calls_timed": reps}


def main() -> int:
    fixture = tempfile.mkdtemp(prefix="bench_breakdown_voc_")
    work = tempfile.mkdtemp(prefix="bench_breakdown_")
    try:
        # val stages need a real val split; keep n_val tiny otherwise so
        # the train-stage workload stays identical to earlier rounds'
        # committed breakdowns
        n_val = 24 if any(s.startswith("val") for s in STAGES) else 2
        make_fake_voc(fixture, n_images=N_IMAGES + (n_val - 2),
                      size=IMG_SIZE, max_objects=2, n_val=n_val, seed=0)
        rec: dict = {"variant": "e2e-fast-path(prepared+devguid+uint8)",
                     "overrides": OVERRIDES, "batch": BATCH}
        def add(stage_rec: dict) -> None:
            # incremental: a late-stage crash must not lose earlier
            # measurements (each partial is a valid JSON line; the last
            # line printed is the most complete record)
            rec.update(stage_rec)
            print(json.dumps(rec), flush=True)

        if "host" in STAGES:
            add(stage_host(fixture, work))
        if "valhost" in STAGES:
            add(stage_valhost(fixture, work))
        if {"place", "step", "dispatch", "valplace", "valstep",
                "valmetric"} & set(STAGES):
            tr = make_trainer(fixture, work, tiny_model=CPU_SMOKE)
            batch = one_host_batch(tr)
            if "place" in STAGES:
                add(stage_place(tr, batch))
            if "step" in STAGES:
                add(stage_step(tr, batch))
            if "dispatch" in STAGES:
                add(stage_dispatch(tr, batch))
            if {"valplace", "valstep", "valmetric"} & set(STAGES):
                vbatch, vdev, n_real = one_val_batch(tr)
                if "valplace" in STAGES:
                    add(stage_place(tr, vdev, prefix="val",
                                    n_real=n_real))
                if "valstep" in STAGES:
                    add(stage_valstep(tr, vdev, n_real))
                if "valmetric" in STAGES:
                    add(stage_valmetric(tr, vbatch, vdev))
            tr.close()
        # train-path stages only: the val stages are a separate pipeline
        # and must not drag the train overlap ceiling down
        rates = [v for k, v in rec.items()
                 if k in ("host_imgs_per_sec", "place_imgs_per_sec",
                          "step_imgs_per_sec")]
        if len(rates) > 1:
            rec["ideal_overlap_imgs_per_sec"] = round(min(rates), 2)
            print(json.dumps(rec), flush=True)
        return 0
    finally:
        shutil.rmtree(fixture, ignore_errors=True)
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
