"""The reader ``mla_attn_kernel_roofline``: the causal attention kernels'
calls of a step against the algorithm's work at the Kanana-2 cell's shapes,
whose every layer holds a multi-head latent attention block with q and k
wider than v; and the cell's other new metric, which is data on a reader
that was there.  CPU, from the root of the repo, as ``test_cca_reader.py``
(whose helpers it borrows)."""

import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_cca_reader as cca  # noqa: E402
import test_scope_readers as readers  # noqa: E402

CELL = "kanana2_30b_8k_b1_mla_step_1chip"
NEW_METRICS = ("mla_latent_device_ms", "mla_attn_kernel_roofline")


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


def test_shape_function_counts_both_widths():
    """Per layer 2 · (8192² / 2) · 32 · (4 · 192 + 3 · 128) FLOPs — S, dK
    and dQ read q or k at 192, P·v, dP and dV read v at 128 — seven layers:
    1.732e13 FLOPs, 87.9 ms at 197 TFLOP/s against 8.6 ms of bytes.  At
    equal widths the count is the seven products' of the CCA reader."""
    ctx, _ = _ctx()
    base = readers.harness.load_module(readers.BENCH, "readers",
                                       "mla_attn_kernel_roofline")
    flops, bytes_ = base.mla_causal_attention(ctx.config, 1, 8192)
    assert flops == 7 * 2.0 * (8192 ** 2 / 2) * 32 * (4 * 192 + 3 * 128)
    assert flops == pytest.approx(1.732e13, rel=1e-3)
    assert 1e3 * flops / ctx.peaks["bf16_flops_per_s"] == pytest.approx(
        87.90, abs=0.01)
    assert 1e3 * bytes_ / ctx.peaks["hbm_bytes_per_s"] == pytest.approx(
        8.60, abs=0.01)
    even = dict(num_hidden_layers=7, num_attention_heads=8,
                num_key_value_heads=8, qk_head_dim=128, v_head_dim=128,
                head_dim=128)
    theirs = readers.harness.load_module(
        readers.BENCH, "readers", "cca_attn_kernel_roofline")
    assert base.mla_causal_attention(even, 1, 8192) == \
        theirs.cca_causal_attention(even, 1, 8192)


def test_roofline_counts_every_layer_of_the_stage():
    """Each of the seven layers' forward call 6 ms and fused reverse call
    15 ms: 147 ms a step against 87.9 ms; the two-sweep schedule's calls
    are read by the same pattern."""
    ctx, _ = _ctx()
    calls = []
    for layer in range(7):
        calls += [(f"%causal_attn.{layer} custom-call", 6000),
                  (f"%causal_attn_bwd_fused.{layer} custom-call", 15000)]
    least_ms = 1e3 * 1.7317e13 / ctx.peaks["bf16_flops_per_s"]
    got = readers._metric(ctx, cca._reading(calls),
                          "mla_attn_kernel_roofline")
    assert got == pytest.approx(100 * least_ms / 147, rel=1e-4)
    assert got < 100
    sweeps = [(n.replace("_fused", "_dkv"), d // 2) for n, d in calls
              if "bwd" in n] + [(n.replace("_fused", "_dq"), d // 2)
                                for n, d in calls if "bwd" in n]
    fwd = [c for c in calls if "bwd" not in c[0]]
    assert readers._metric(ctx, cca._reading(fwd + sweeps),
                           "mla_attn_kernel_roofline") == pytest.approx(got)


def test_roofline_reads_nothing_where_no_causal_call_ran():
    ctx, _ = _ctx()
    other = [("%blockdiff_attn.1 custom-call", 900),
             ("%sparse_attn_bwd_fused.3 custom-call", 900),
             ("%fusion.99 fusion", 900)]
    assert readers._metric(ctx, cca._reading(other),
                           "mla_attn_kernel_roofline") is None


def test_the_new_metrics_resolve_and_the_cell_reports_them():
    """The two metrics are manifest entries that list the cell, each file
    names a reader that is there; the cell is listed by every accepted
    token metric it reports; the latent path's time reads from a reading
    as the kind makes one."""
    from distributedpytorch_tpu.telemetry.scopes import Scope

    ctx, manifest = _ctx()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
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
                 "moe_shared_expert_device_ms", "attn_device_ms",
                 "vocab_device_ms"):
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in ("attn_kernel_roofline", "cca_attn_kernel_roofline"):
        assert CELL not in by_name[name]["workloads"], name

    def s(path, phase="fwd"):
        return Scope(path.split("/")[0], path, phase, False, "fusion")

    table = {"fusion.1": s("attn/l00/mla_latent"),
             "fusion.2": s("attn/l02/mla_latent", "bwd"),
             "fusion.3": s("attn/l00"),
             "fusion.4": s("moe/l03/shared_expert")}
    ops = [["%fusion.1 fusion", 0, 2_000_000],
           ["%fusion.2 fusion", 2_000_000, 5_000_000],
           ["%fusion.3 fusion", 5_000_000, 9_000_000],
           ["%fusion.4 fusion", 9_000_000, 10_000_000]]
    reading = {"summary": {"devices": [{"plane": "/device:TPU:0",
                                        "steps": 2, "ops": ops}]},
               "scope_table": table}
    assert readers._metric(ctx, reading, "mla_latent_device_ms") == \
        pytest.approx(2.5)                       # (2 + 3) ms over 2 steps
    assert readers._metric(ctx, reading, "moe_shared_expert_device_ms") \
        == pytest.approx(0.5)
