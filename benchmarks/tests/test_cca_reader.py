"""The reader ``cca_attn_kernel_roofline``: the causal attention
kernels' calls of a step against the algorithm's work at the ZAYA1 cell's
shapes, whose every layer holds an attention block; and the cell's two other
new metrics, which are data on readers that were there.  CPU, from the root
of the repo, as ``test_attn_kernel_reader.py`` (whose helpers it borrows)."""

import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_scope_readers as readers  # noqa: E402

CELL = "zaya1_8b_8k_b1_cca_step_1chip"
NEW_METRICS = ("cca_mix_device_ms", "moe_skip_share",
               "cca_attn_kernel_roofline")


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


def _reading(calls, steps=2):
    """A hand-made summary: each step a fusion, then ``calls`` ((name,
    microseconds) each) end to end."""
    us, ops = 1_000, []
    for k in range(steps):
        t = k * 500_000 * us
        ops.append(["%fusion.12 fusion", t, t + 100 * us])
        t += 100 * us
        for name, dur in calls:
            ops.append([name, t, t + dur * us])
            t += (dur + 10) * us
    dev = {"plane": "/device:TPU:0", "steps": steps, "ops": ops}
    return {"summary": {"devices": [dev]}, "images_per_step": 1, "chips": 1}


def test_roofline_counts_every_layer_of_the_stage():
    """Seven layers of 7 · 2 · (8192² / 2) · 128 · 8 FLOPs = 3.37e12, 17.1 ms
    at 197 TFLOP/s (1.1 ms of bytes); each layer's forward call 1.5 ms and
    fused reverse call 3.0 ms: 31.5 ms a step."""
    ctx, _ = _ctx()
    flops = 7 * 7 * 2.0 * (8192 ** 2 / 2) * 128 * 8
    assert flops == pytest.approx(3.367e12, rel=1e-3)
    least_ms = 1e3 * flops / ctx.peaks["bf16_flops_per_s"]
    assert least_ms == pytest.approx(17.09, abs=0.01)
    base = readers.harness.load_module(readers.BENCH, "readers",
                                       "cca_attn_kernel_roofline")
    got_flops, got_bytes = base.cca_causal_attention(ctx.config, 1, 8192)
    assert got_flops == flops
    assert 1e3 * got_bytes / ctx.peaks["hbm_bytes_per_s"] == pytest.approx(
        1.075, abs=0.001)
    calls = []
    for layer in range(7):
        calls += [(f"%causal_attn.{layer} custom-call", 1500),
                  (f"%causal_attn_bwd_fused.{layer} custom-call", 3000)]
    got = readers._metric(ctx, _reading(calls), "cca_attn_kernel_roofline")
    assert got == pytest.approx(100 * least_ms / 31.5)
    # the two-sweep reverse schedule's calls are read by the same pattern
    sweeps = [(n.replace("_fused", "_dkv"), d // 2) for n, d in calls
              if "bwd" in n] + [(n.replace("_fused", "_dq"), d // 2)
                                for n, d in calls if "bwd" in n]
    fwd = [c for c in calls if "bwd" not in c[0]]
    assert readers._metric(ctx, _reading(fwd + sweeps),
                           "cca_attn_kernel_roofline") == pytest.approx(got)


def test_roofline_reads_nothing_where_no_causal_call_ran():
    """The einsum form's trace, or another kind of kernel (the block
    diffusion and sparse calls), gives nothing, never 0."""
    ctx, _ = _ctx()
    other = [("%blockdiff_attn.1 custom-call", 900),
             ("%sparse_attn_bwd_fused.3 custom-call", 900),
             ("%fusion.99 fusion", 900)]
    assert readers._metric(ctx, _reading(other),
                           "cca_attn_kernel_roofline") is None


def test_the_new_metrics_resolve_and_the_cell_reports_them():
    """The three metrics are manifest entries that list the cell, each file
    names a reader that is there; the cell is listed by every accepted
    metric it reports, and the skip share and the mixing's time read from a
    reading as the kind makes one."""
    from distributedpytorch_tpu.telemetry.scopes import Scope

    ctx, manifest = _ctx()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert CELL in by_name[name]["workloads"]
        spec = readers.harness.load_json(os.path.join(
            readers.BENCH, "metrics", name + ".json"))
        assert hasattr(readers.harness.load_module(
            readers.BENCH, "readers", spec["reader"]), "read")
    for name in ("step_mfu", "device_step_ms", "device_idle_share",
                 "hbm_peak_gb", "loss_device_ms", "optimizer_device_ms",
                 "backward_device_ms", "scope_mixed_share", "moe_device_ms",
                 "moe_dispatch_device_ms", "moe_combine_device_ms",
                 "moe_routed_device_ms", "moe_tokens_dropped",
                 "moe_expert_load_max_over_mean", "moe_router_device_ms",
                 "attn_device_ms", "vocab_device_ms"):
        assert CELL in by_name[name]["workloads"], name
    assert CELL not in by_name["attn_kernel_roofline"]["workloads"]

    def s(path, phase="fwd"):
        return Scope(path.split("/")[0], path, phase, False, "fusion")

    table = {"fusion.1": s("attn/l00/cca_mix"),
             "fusion.2": s("attn/l00/cca_mix", "bwd"),
             "fusion.3": s("attn/l00"),
             "fusion.4": s("moe/l01/router")}
    ops = [["%fusion.1 fusion", 0, 2_000_000],
           ["%fusion.2 fusion", 2_000_000, 5_000_000],
           ["%fusion.3 fusion", 5_000_000, 9_000_000],
           ["%fusion.4 fusion", 9_000_000, 10_000_000]]
    reading = {"summary": {"devices": [{"plane": "/device:TPU:0",
                                        "steps": 2, "ops": ops}]},
               "scope_table": table,
               "counters": {"moe_skip_share": 0.0625}}
    assert readers._metric(ctx, reading, "cca_mix_device_ms") == \
        pytest.approx(2.5)                       # (2 + 3) ms over 2 steps
    assert readers._metric(ctx, reading, "moe_router_device_ms") == \
        pytest.approx(0.5)
    assert readers._metric(ctx, reading, "moe_skip_share") == 0.0625
    # a program that counts no skip gives nothing
    assert readers._metric(ctx, {"counters": {}}, "moe_skip_share") is None
