"""The reader PR 27 added, ``pam_backward_kernel_roofline``, beside the
forward's: each sums its own events.  CPU, from the root of the repo, as
``test_scope_readers.py`` (whose recorded trace and helpers it borrows)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_scope_readers as readers  # noqa: E402


def test_pam_rooflines_tell_the_forward_from_the_reverse_pass():
    """A hand-made summary of two steps: the forward's ``%pam.1`` 2 ms a
    step, the reverse pass's two sweeps 1.5 ms + 1 ms a step.  Each metric
    sums its own events only; the least times are 0.785 ms (forward) and
    1.657 ms (2 * N^2 * (3 * 64 + 2 * 512) * 8 FLOPs at 197 TFLOP/s, against
    0.184 ms of bytes) at the DANet cell's shapes.  A trace without the
    events reads nothing, never 0."""
    ctx, _ = readers._recorded()
    ms = 1_000_000
    ops = [["%fusion.7 fusion", 0, 1 * ms],
           ["%pam.1 custom-call", 1 * ms, 3 * ms],
           ["%pam_bwd_dkv.1 custom-call", 3 * ms, int(4.5 * ms)],
           ["%pam_bwd_dq.1 custom-call", 5 * ms, 6 * ms],
           ["%pam.1 custom-call", 7 * ms, 9 * ms],
           ["%pam_bwd_dkv.1 custom-call", 9 * ms, int(10.5 * ms)],
           ["%pam_bwd_dq.1 custom-call", 11 * ms, 12 * ms]]

    def reading(ops):
        dev = {"plane": "/device:TPU:0", "steps": 2, "ops": ops}
        return {"summary": {"devices": [dev]}, "images_per_step": 8,
                "chips": 1}

    def fwd(ops):
        return readers._metric(ctx, reading(ops), "pam_kernel_roofline")

    def bwd(ops):
        return readers._metric(ctx, reading(ops),
                               "pam_backward_kernel_roofline")

    tokens, peak = 4096, ctx.peaks["bf16_flops_per_s"]
    fwd_least_ms = 1e3 * 2.0 * tokens ** 2 * (64 + 512) * 8 / peak
    bwd_least_ms = 1e3 * 2.0 * tokens ** 2 * (3 * 64 + 2 * 512) * 8 / peak
    assert bwd_least_ms == pytest.approx(1.657, abs=0.002)
    assert fwd(ops) == pytest.approx(100 * fwd_least_ms / 2.0)
    assert bwd(ops) == pytest.approx(100 * bwd_least_ms / 2.5)
    # the fused schedule's one call is read by the same pattern
    fused = [["%pam_bwd_fused.1 custom-call", b, e] if "pam_bwd_dkv" in name
             else [name, b, e] for name, b, e in ops
             if "pam_bwd_dq" not in name]
    assert bwd(fused) == pytest.approx(100 * bwd_least_ms / 1.5)
    bare = [o for o in ops if "pam" not in o[0]]
    assert fwd(bare) is None and bwd(bare) is None
    # the recorded steps of the parent's program hold a forward and no
    # reverse-pass call
    ctx, recorded = readers._recorded()
    assert readers._metric(ctx, recorded,
                           "pam_backward_kernel_roofline") is None
    manifest = readers.harness.load_json(
        os.path.join(readers.REPO, "BENCHMARK.json"))
    entry = {m["name"]: m for m in manifest["per_layer"]}[
        "pam_backward_kernel_roofline"]
    assert entry["workloads"] == ["danet_r101_512_b8_step_1chip",
                                  readers.NEW_CELL]
    assert (entry["layer"], entry["moves"]) == (
        "kernels", "train_imgs_per_s_per_chip")
