"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py [k=v ...]        # extra trainer overrides, CLI syntax

One process drives the main path once at the flagship's full width —
DANet-ResNet101 os8, 512² 4-channel crops, 8 per chip, bf16 policy,
``attention_impl=auto`` (the Mosaic PAM + CAM kernels), bucketed gradient
reduce — through the entry points a user calls: ``Config`` →
``apply_overrides`` → ``Trainer.fit()`` (≥ 3 optimizer steps, one validation,
one checkpoint), then ``Predictor.from_run`` on that checkpoint behind an
in-process ``InferenceService`` answering three clicks.  Weights are random
from the seed; the data is a seeded ``make_fake_voc`` fixture.

It refuses (exit 2, no result line) unless JAX's first device is a TPU, and
any failed check raises: there is no path on which a stage fails and the run
passes.  It never starts a child that needs the chip — a chip belongs to one
process.  The last line of stdout is the result, e.g.
``{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite",
"count": 1}}``; the line before it carries stage seconds (first-step compile
included), the losses and per-device peak bytes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.metadata as md
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

PER_CHIP_BATCH = 8
CROP = 512


def _kernel_parity() -> dict:
    """The Mosaic kernels against the XLA einsum forms on this chip, at the
    flagship head's tile shapes (N=4096 tokens, C=512, bf16), and the token
    model's causal grouped-query attention at the benchmark cell's (8,192
    tokens, 4 query heads to 1 key/value head of 128), forward and reverse."""
    from distributedpytorch_tpu.ops import (
        causal_attention,
        channel_attention,
        flash_causal_attention,
        flash_channel_attention,
        flash_position_attention,
        position_attention,
    )

    r = np.random.default_rng(0)
    b, n, c = 2, 4096, 512
    q, k = (jnp.asarray(r.normal(0, 0.5, (b, n, c // 8)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(r.normal(0, 1.0, (b, n, c)), jnp.bfloat16)
    x = jnp.asarray(r.normal(0, 0.05, (b, n, c)), jnp.bfloat16)

    def rel_err(got, want):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert got.shape == want.shape and np.isfinite(got).all()
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    errs = {
        "pam": rel_err(jax.jit(flash_position_attention)(q, k, v),
                       jax.jit(position_attention)(q, k, v)),
        "cam": rel_err(jax.jit(flash_channel_attention)(x),
                       jax.jit(channel_attention)(x)),
    }
    tq = jnp.asarray(r.normal(0, 1.0, (1, 8192, 4, 128)), jnp.bfloat16)
    tk, tv = (jnp.asarray(r.normal(0, 1.0, (1, 8192, 1, 128)), jnp.bfloat16)
              for _ in range(2))

    def out_and_grads(attend):
        def loss(*a):
            return (attend(*a).astype(jnp.float32) ** 2).sum()
        return (jax.jit(attend)(tq, tk, tv),
                *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(tq, tk, tv))

    for name, got, want in zip(
            ("causal_attn", "causal_attn_dq", "causal_attn_dk",
             "causal_attn_dv"), out_and_grads(flash_causal_attention),
            out_and_grads(causal_attention)):
        errs[name] = rel_err(got, want)
    for name, e in errs.items():
        # bf16 stores round at 2^-9; a wrong tile or transpose is O(1)
        assert e < 3e-2, f"{name} kernel vs einsum: relative error {e:.3g}"
    return errs


def _click_from_fixture(root: str):
    """One val image of the fixture and the four extreme points of its
    first object, as a user would click them."""
    from PIL import Image

    from distributedpytorch_tpu.data.voc import BASE_DIR

    voc = os.path.join(root, BASE_DIR)
    with open(os.path.join(voc, "ImageSets", "Segmentation",
                           "val.txt")) as f:
        im_id = f.readline().strip()
    image = np.asarray(Image.open(
        os.path.join(voc, "JPEGImages", im_id + ".jpg")).convert("RGB"))
    inst = np.asarray(Image.open(
        os.path.join(voc, "SegmentationObject", im_id + ".png")))
    ys, xs = np.nonzero(inst == 1)
    picks = [xs.argmin(), xs.argmax(), ys.argmin(), ys.argmax()]
    return image, np.array([[xs[i], ys[i]] for i in picks], np.float32)


def _first_batch(loader) -> dict:
    """The device-bound leaves of the loader's first batch."""
    from distributedpytorch_tpu.parallel import DEVICE_KEYS

    batches = iter(loader)
    try:
        batch = next(batches)
    finally:
        batches.close()  # stops the loader's producer thread
    return {k: batch[k] for k in DEVICE_KEYS if k in batch}


def _scope_capture(trainer) -> dict:
    """Arm one 4-step ``TraceCapture`` (what ``kill -USR2`` does), train an
    epoch under it and one without, and check what the capture left: the
    scope table of the step that ran, not stale, resolving the trace, with
    backbone / head / loss / optimizer in it, and the device's idle gaps
    named by the program's own host spans."""
    from distributedpytorch_tpu.telemetry import scopes

    def epoch_step_s(epoch):
        before = int(trainer.state.step)
        t0 = time.perf_counter()
        trainer.train_epoch(epoch)
        steps = int(trainer.state.step) - before
        return (time.perf_counter() - t0) / steps, steps

    target = trainer._trace.request(steps=4)
    assert target is not None
    with_capture_s, steps = epoch_step_s(1)
    trainer._trace.close()  # a short epoch ends before the fourth tick
    without_s, _ = epoch_step_s(2)
    with open(os.path.join(target, "scope_summary.json")) as f:
        summary = json.load(f)
    with open(os.path.join(target, "scope_table.json")) as f:
        table = json.load(f)
    assert not summary["stale"] and not table["stale"], table["differing"]
    assert summary["devices"] == len(jax.devices()), summary["devices"]
    assert summary["steps"] >= min(4, steps), summary["steps"]
    assert summary["unresolved_share"] < 0.01, summary["unresolved_share"]
    layers = summary["ms_per_step_by_layer"]
    for layer in ("backbone", "head", scopes.LOSS, scopes.OPTIMIZER):
        assert layers.get(layer, 0) > 0, (layer, layers)
    # host and device on one clock: the goodput buckets are spans of the
    # trace, and an idle gap is named by one of the program's spans or by
    # none (the loop's logging between two steps is in no bucket)
    spans = summary["host_spans"]
    for name in ("goodput/step", "goodput/input_wait", scopes.STEP_ANNOTATION):
        assert spans.get(name, 0) > 0, (name, spans)
    assert all(g[0] in spans or g[0] == "no host span"
               for g in summary["idle_gaps"]), summary["idle_gaps"]
    return {"step_s_with_capture": round(with_capture_s, 4),
            "step_s_without": round(without_s, 4), "epoch_steps": steps,
            "traced_steps": summary["steps"],
            "busy_ms_per_step": round(summary["busy_ms_per_step"], 3),
            "ms_per_step_by_layer_phase": {
                k: round(v, 3) for k, v in
                summary["ms_per_step_by_layer_phase"].items()},
            "collectives_ms_per_step": {
                k: round(v, 3) for k, v in
                summary["ms_per_step_collectives_by_layer"].items()},
            "mixed_share": round(summary["mixed_share"], 4),
            "unresolved_share": round(summary["unresolved_share"], 5),
            "host_spans": spans, "idle_gaps": summary["idle_gaps"][:5]}


def main(argv: list[str]) -> int:
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{dev[0].platform!r}) — this is the chip check and it does "
              "not run anywhere else", file=sys.stderr)
        return 2
    n_chips = len(dev)
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": n_chips}

    from distributedpytorch_tpu import imaging, native_ops
    from distributedpytorch_tpu.backend_health import enable_compile_cache
    from distributedpytorch_tpu.data import make_fake_voc
    from distributedpytorch_tpu.parallel import shard_batch
    from distributedpytorch_tpu.predict import Predictor
    from distributedpytorch_tpu.serve import InferenceService
    from distributedpytorch_tpu.telemetry.goodput import PEAK_FLOPS_BY_KIND
    from distributedpytorch_tpu.telemetry.lowering import lower_cached
    from distributedpytorch_tpu.train import Config, Trainer, apply_overrides

    cache_dir = enable_compile_cache()
    print(f"platform: {device['platform']}\ndevice_kind: {device['kind']}\n"
          f"devices: {n_chips}\n"
          f"jax {jax.__version__} jaxlib {md.version('jaxlib')} "
          f"libtpu {md.version('libtpu')}\n"
          f"compile cache: {cache_dir} "
          f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
          " entries)\n"
          f"imaging: {imaging.backend()} (native host library "
          f"{'loaded' if native_ops.enabled() else 'absent'})", flush=True)

    stages: dict[str, float] = {}

    @contextlib.contextmanager
    def stage(name: str):
        t0 = time.perf_counter()
        yield
        stages[name] = round(time.perf_counter() - t0, 2)
        print(f"[{name}] {stages[name]} s", flush=True)

    report: dict = {"device": device, "stages_s": stages}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        with stage("kernel_parity"):
            report["kernel_rel_err"] = _kernel_parity()

        with stage("fixture"):
            # every image holds 1-3 objects: >= 3 train batches of
            # 8 x n_chips instances, and a val split of at least one batch
            root = make_fake_voc(
                os.path.join(work, "voc"), n_images=32 * n_chips,
                size=(240, 320), n_val=8 * n_chips, seed=0)

        batch = PER_CHIP_BATCH * n_chips
        cfg = apply_overrides(Config(), [
            "model.name=danet", "model.backbone=resnet101",
            "model.output_stride=8", "model.in_channels=4",
            "model.attention_impl=auto", "train.precision=bfloat16",
            "train.reduce_buckets=8", f"data.crop_size=[{CROP},{CROP}]",
            f"data.train_batch={batch}", f"data.val_batch={batch}",
            f"data.root={root}", "data.num_workers=8", "epochs=1",
            "eval_every=1", "log_every_steps=1", "debug_asserts=true",
            "checkpoint.async_save=false", *argv])
        cfg = dataclasses.replace(cfg, work_dir=os.path.join(work, "runs"))

        with stage("trainer_build"):
            trainer = Trainer(cfg)
        try:
            with stage("fit"):
                history = trainer.fit()
            run_dir = trainer.run_dir

            # --- what the run wrote, not what the process remembers
            with open(os.path.join(run_dir, "metrics.jsonl")) as f:
                metrics = [json.loads(line) for line in f]
            with open(os.path.join(run_dir, "fit_summary.json")) as f:
                summary = json.load(f)
            losses = [m["train/loss"] for m in metrics if "train/loss" in m]
            assert len(losses) >= 3, f"{len(losses)} optimizer step(s) logged"
            assert np.isfinite(losses).all(), losses
            assert summary["completed"] is True, summary
            assert summary["final_step"] == len(losses), summary
            val = [m["val/jaccard"] for m in metrics if "val/jaccard" in m]
            assert len(val) == 1 and 0.0 <= val[0] <= 1.0, val
            mfu = summary["mfu"]
            assert mfu["peak_source"] in PEAK_FLOPS_BY_KIND, mfu
            assert mfu["flops_source"] == "xla_cost_analysis", mfu
            assert trainer.ckpt.latest_step() == len(losses)
            report.update(
                losses=losses, val_jaccard=val[0],
                first_step_compile_s=round(
                    history["goodput"]["buckets"]["compile"], 2),
                step_s=round(mfu["step_time_s"], 4), mfu=round(mfu["mfu"], 4))

            # --- every chip used, none carrying the others' share
            # peak_bytes_in_use is the allocator's high-water mark (state,
            # batches, outputs); the loaded programs' scratch is reserved
            # apart from it
            stats = [d.memory_stats() for d in dev]
            peaks = [s["peak_bytes_in_use"] for s in stats]
            report["peak_bytes_in_use"] = peaks
            report["peak_bytes_reserved"] = [
                s["peak_bytes_reserved"] for s in stats]
            assert trainer.mesh.devices.size == n_chips
            placed = shard_batch(trainer.mesh, {"concat": np.zeros(
                (batch, CROP, CROP, 4), np.float32)})["concat"]
            rows = [s.data.shape[0] for s in placed.addressable_shards]
            assert rows == [PER_CHIP_BATCH] * n_chips, rows
            assert max(peaks) <= 1.2 * min(peaks), peaks

            # --- the compiled programs carry the kernels: Mosaic custom
            # calls, not the interpreter and not the einsum forms.  Real
            # loader batches give the programs the loop dispatched (the
            # train step's lowering is the one its MFU count already made)
            with stage("hlo_check"):
                programs = trainer.audit_programs(
                    train_batch=_first_batch(trainer.train_loader),
                    val_batch=_first_batch(trainer.val_loader))
                # forward: flash PAM, CAM energy, CAM apply; the train step
                # adds the PAM reverse pass's one fused sweep
                for name, want in (("train_step", 4), ("eval_step", 3)):
                    fn, args = programs[name]
                    hlo = lower_cached(fn, *args).compiled.as_text()
                    n_calls = hlo.count(
                        'custom_call_target="tpu_custom_call"')
                    assert n_calls == want, \
                        f"{name}: {n_calls} tpu_custom_call"
            # --- a capture armed on the live trainer leaves its answer:
            # one more epoch with a 4-step capture, one without, both warm
            with stage("scope_capture"):
                report["scope_capture"] = _scope_capture(trainer)
        finally:
            trainer.close()

        # --- the serve stack's first contact with the chip
        image, points = _click_from_fixture(root)
        with stage("serve_restore"):
            predictor = Predictor.from_run(run_dir)
        with InferenceService(predictor, max_batch=1) as svc:
            with stage("serve_first_request"):
                masks = [svc.predict(image, points)]
            with stage("serve_requests"):
                masks += [svc.predict(image, points) for _ in range(2)]
        for m in masks:
            assert m.shape == image.shape[:2] and m.dtype == np.float32
            assert np.isfinite(m).all() and 0.0 <= m.min() <= m.max() <= 1.0
        # a lone request rides bucket 1: the predictor's own program
        assert all(np.array_equal(m, predictor.predict(image, points))
                   for m in masks)
        report["served"] = len(masks)

    print(json.dumps(report), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
