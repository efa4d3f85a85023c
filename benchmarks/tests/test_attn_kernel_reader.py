"""The reader PR 34 added, ``attn_kernel_roofline``: the causal attention
kernels' calls of a step against the algorithm's work at the token cell's
shapes.  CPU, from the root of the repo, as ``test_pam_backward_reader.py``
(whose helpers it borrows)."""

import os
import re
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_scope_readers as readers  # noqa: E402

CELL = "nemotron3_super_8k_b1_step_1chip"


def _ctx():
    harness = readers.harness
    manifest = harness.load_json(os.path.join(readers.REPO, "BENCHMARK.json"))
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    return types.SimpleNamespace(
        bench_dir=readers.BENCH,
        config=harness.load_json(os.path.join(
            readers.BENCH, "configs", cell["config"] + ".json")),
        traffic=harness.load_json(os.path.join(
            readers.BENCH, "traffic", cell["traffic"] + ".json")),
        peaks=harness.peaks_for(readers.BENCH, "TPU v5 lite")), manifest


def test_attn_roofline_sums_every_causal_call_of_a_step():
    """A hand-made summary of two steps.  Each step: the trunk layer's
    forward call 0.7 ms, the prediction module's 0.7 ms, both again in a
    recomputation (a program whose blocks do not keep the call's results),
    and the two fused reverse calls 1.2 ms each: 5.2 ms a step.  The least time is 2.44 ms: two layers of 7 * 2 * (8192^2 / 2) *
    128 * 4 FLOPs at 197 TFLOP/s (0.15 ms of bytes).  The two-sweep
    schedule's calls are read by the same pattern; DANet's ``%pam…`` calls
    and a trace without the kernels (the einsum form) give nothing."""
    ctx, manifest = _ctx()
    us = 1_000

    def step(t0, reverse):
        ops, t = [["%fusion.607 fusion", t0, t0 + 100 * us]], t0 + 100 * us
        for name, dur in ([("%causal_attn.4 custom-call", 700),
                           ("%causal_attn.5 custom-call", 700),
                           ("%causal_attn.6 custom-call", 700)]
                          + reverse[:len(reverse) // 2]
                          + [("%causal_attn.7 custom-call", 700)]
                          + reverse[len(reverse) // 2:]):
            ops.append([name, t, t + dur * us])
            t += (dur + 50) * us
        return ops

    def reading(reverse):
        ops = step(0, reverse) + step(20_000 * us, reverse)
        dev = {"plane": "/device:TPU:0", "steps": 2, "ops": ops}
        return {"summary": {"devices": [dev]}, "images_per_step": 1,
                "chips": 1}

    def metric(r):
        return readers._metric(ctx, r, "attn_kernel_roofline")

    least_ms = 1e3 * 2 * 7 * 2.0 * (8192 ** 2 / 2) * 128 * 4 \
        / ctx.peaks["bf16_flops_per_s"]
    assert least_ms == pytest.approx(2.442, abs=0.002)
    fused = [("%causal_attn_bwd_fused.2 custom-call", 1200),
             ("%causal_attn_bwd_fused.3 custom-call", 1200)]
    assert metric(reading(fused)) == pytest.approx(100 * least_ms / 5.2)
    sweeps = [("%causal_attn_bwd_dkv.2 custom-call", 800),
              ("%causal_attn_bwd_dq.2 custom-call", 700),
              ("%causal_attn_bwd_dkv.3 custom-call", 800),
              ("%causal_attn_bwd_dq.3 custom-call", 700)]
    assert metric(reading(sweeps)) == pytest.approx(100 * least_ms / 5.8)
    # the einsum form's trace (every parent of PR 34): nothing, never 0
    bare = reading(fused)
    bare["summary"]["devices"][0]["ops"] = [
        o for o in bare["summary"]["devices"][0]["ops"]
        if "causal_attn" not in o[0]] + [["%pam.1 custom-call", 0, us],
                                         ["%pam_bwd_fused.1 custom-call",
                                          us, 2 * us]]
    assert metric(bare) is None
    # and the DANet cells' patterns read none of the new calls
    for name in ("pam_kernel_roofline", "pam_backward_kernel_roofline",
                 "cam_energy_kernel_roofline", "cam_apply_kernel_roofline"):
        rx = re.compile(readers.harness.load_json(os.path.join(
            readers.BENCH, "metrics", name + ".json"))["args"][
                "event_pattern"])
        assert not any(rx.search(o[0]) for o in
                       reading(fused + sweeps)["summary"]["devices"][0]["ops"])
    # the recorded DANet steps hold no such call either
    danet_ctx, recorded = readers._recorded()
    danet_ctx.traffic = {}
    assert readers._metric(danet_ctx, recorded,
                           "attn_kernel_roofline") is None
    entry = manifest["per_layer"][-1]
    assert entry == {
        "name": "attn_kernel_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_imgs_per_s_per_chip", "workloads": [CELL]}
