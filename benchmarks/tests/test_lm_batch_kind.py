"""The ``train_step_lm_batch`` kind on the CPU: a tiny ``sdar_lm`` cell
brought as new files and manifest entries (``tests/data``), run through
``run.main``; the control and the four faults that ``correct`` has to catch,
under the real cell's limit names; the reader of the new roofline metric.
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (HERE, BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import rehearsal  # noqa: E402

CONFIG = {"name": "sdar_lm_tiny", "source": "rehearsal only",
          "file": "benchmarks/configs/sdar_lm_tiny.json",
          "reduced": ["hidden_size"], "why": "CPU rehearsal"}
CELL = {"name": "rehearsal_lm_batch", "config": "sdar_lm_tiny",
        "traffic": "step_lm_batch_tiny", "chips": 1, "why": "CPU rehearsal"}
REAL_CELL = "sdar_30b_4k_b1_blockdiff_step_1chip"
JOINED = {"step_mfu", "device_step_ms", "device_idle_share", "hbm_peak_gb",
          "scope_mixed_share", "loss_device_ms", "optimizer_device_ms",
          "backward_device_ms", "attn_device_ms", "vocab_device_ms",
          "moe_device_ms", "moe_dispatch_device_ms", "moe_combine_device_ms",
          "moe_routed_device_ms", "moe_tokens_dropped",
          "moe_expert_load_max_over_mean"}
NEW = {"blockdiff_attn_kernel_roofline", "diffusion_masked_share"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.make_root(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(CONFIG)
    manifest["workloads"].append(CELL)
    for m in manifest["per_layer"]:
        if m["name"] != "pam_kernel_roofline":
            m["workloads"] = m["workloads"] + [CELL["name"]]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


@pytest.fixture(scope="module")
def sound(root):
    res = rehearsal.run(root, CELL["name"], seed=2**31 + 17)
    assert res["correct"], res["compared"]
    return res


def _broke(res) -> set:
    return {k for k, (v, lim) in res["compared"].items() if not v <= lim}


def test_untraced_run_is_correct_and_judges_the_counters(sound):
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["rehearsal"] and "setup_s" in sound["metrics"]
    assert sound["compared"]["moe_tokens_dropped"] == [0.0, 0]
    off, limit = sound["compared"]["diffusion_masked_share_off_expected"]
    assert off <= limit
    assert {"moe_tokens_dropped", "moe_expert_load_max_over_mean",
            "moe_chunks_run_share", "diffusion_masked_share"} <= set(
                sound["numbers"])
    # the resident batch reads the same every step: a maximum of equals
    assert 0 < sound["numbers"]["diffusion_masked_share"] <= 1
    assert sound["numbers"]["grad_norm_gap"] < 1e-5
    assert sound["reference_s"] > 0


def _with_step(monkeypatch, root, wrap, seed=5):
    """Run the cell with the timed step broken underneath."""
    rehearsal.run(root, "rehearsal_echo", trace=1)   # fresh module objects
    import harness

    real_load = harness.load_module

    def load(bench_dir, folder, name):
        mod = real_load(bench_dir, folder, name)
        if folder == "kinds" and name == "train_step":
            real_build = mod.build_step
            mod.build_step = lambda make_step, state, batch: wrap(
                real_build, make_step, state, batch)
        return mod

    monkeypatch.setattr(harness, "load_module", load)
    import run as run_mod

    return run_mod.main(["--workload", CELL["name"], "--seed", str(seed),
                         "--seconds", "0.5", "--trace", "0"],
                        root=root, allow_cpu=True)


def _reference_in_the_steps_place(root, **variant):
    """A ``wrap`` for :func:`_with_step`: the plain reference's step, in
    ``variant``, stands where the program's stood; the counters are a sound
    step's."""
    import functools

    import jax
    import jax.numpy as jnp

    def wrap(real_build, make_step, state, batch):
        import harness
        from reference import sdar_lm as ref

        cfg = harness.load_json(os.path.join(
            root, "benchmarks", "configs", "sdar_lm_tiny.json"))
        _, (_, counters) = real_build(make_step, state, batch)(
            jax.tree.map(jnp.copy, state), batch)
        fault = jax.jit(functools.partial(ref.train_step, cfg,
                                          cfg["optimizer"], **variant))
        momentum = jax.tree.structure(state.opt_state)

        def step(state, batch):
            params, trace, loss = fault(
                state.params, jax.tree.structure(state.params).unflatten(
                    jax.tree.leaves(state.opt_state)), batch)
            return state.replace(
                step=state.step + 1, params=params,
                opt_state=momentum.unflatten(jax.tree.leaves(trace))), \
                (loss, counters)
        return step
    return wrap


@pytest.mark.parametrize("seed", [5, 6])
def test_control_in_the_programs_place_is_not_correct(sound, root,
                                                      monkeypatch, seed):
    sys.path.insert(0, os.path.join(root, "benchmarks", "tools"))
    from control import BELOW
    from reference import nets

    res = _with_step(monkeypatch, root, _reference_in_the_steps_place(
        root, q=nets.Rounding(BELOW["float32"])), seed=seed)
    assert not res["correct"]
    assert {"grad_norm_gap", "change_norm_gap"} <= _broke(res)


@pytest.mark.parametrize("name,variant,breaks", [
    ("half_tokens", {"rows": 24},
     {"grad_norm_gap", "change_norm_gap_median_leaf"}),
    ("no_routed_experts", {"drop_routed": True}, {"grad_norm_gap"}),
    ("causal_mask", {"causal_mask": True}, {"loss1_gap", "grad_norm_gap"}),
    ("unweighted_loss", {"unweighted_loss": True},
     {"loss1_gap", "grad_norm_gap_median_leaf",
      "change_norm_gap_median_leaf"}),
])
def test_each_fault_is_not_correct(sound, root, monkeypatch, name, variant,
                                   breaks):
    res = _with_step(monkeypatch, root,
                     _reference_in_the_steps_place(root, **variant))
    assert not res["correct"], name
    assert breaks <= _broke(res), (name, _broke(res))


def test_a_batch_masked_off_the_recipe_is_not_correct(sound, root,
                                                      monkeypatch):
    """A step whose counter says nearly every token was masked."""
    def wrap(real_build, make_step, state, batch):
        step = real_build(make_step, state, batch)

        def over(state, batch):
            state, (loss, counters) = step(state, batch)
            return state, (loss, dict(counters,
                                      diffusion_masked_share=1.5))
        return over

    res = _with_step(monkeypatch, root, wrap)
    assert not res["correct"]
    assert _broke(res) == {"diffusion_masked_share_off_expected"}


def test_control_tool_judges_by_the_cells_limits(sound, root):
    sys.path.insert(0, os.path.join(root, "benchmarks", "tools"))
    import importlib

    tool = importlib.import_module("control_lm")
    lines = tool.main([CELL["name"], "5", "6"], allow_cpu=True, root=root)
    assert {line["what"] for line in lines} == {
        "control", "half_tokens", "no_routed_experts", "causal_mask",
        "unweighted_loss"}
    for line in lines:
        assert not line["correct"] and line["broke"], line
        assert "diffusion_masked_share_off_expected" not in line["broke"]


@pytest.mark.parametrize("drawn", [False, True])
def test_routing_tool_counts_the_positions_whose_experts_differ(sound, root,
                                                                drawn):
    """``tools/mask_routing_sdar.py`` at rehearsal size: in float32 the
    program's router sees what the reference's sees, so no position's
    experts differ, the mask positions are the noised copy's, and the
    sharpened row routes with a margin."""
    sys.path.insert(0, os.path.join(root, "benchmarks", "tools"))
    import importlib

    tool = importlib.import_module("mask_routing_sdar")
    lines = tool.main([CELL["name"], "--train", "5"]
                      + (["--drawn"] if drawn else []),
                      allow_cpu=True, root=root)
    assert [line["what"] for line in lines] == [
        "program_against_reference", "witness_against_reference",
        "witness_train_steps"]
    for line in lines[:2]:
        assert line["drawn"] == drawn and 0 < line["mask_positions"] <= 48
        assert len(line["layers"]) == 2
        for layer in line["layers"]:
            assert layer["mask_differ"] == layer["other_differ"] == 0
            assert layer["mask_logit_rms_gap"] < 1e-5
            assert drawn or layer["mask_margin_mean"] > 0.5
    assert lines[2]["numbers"]["grad_norm_gap"] == 0.0


def test_rehearsal_holds_the_real_cells_limit_names():
    import harness

    real = harness.load_json(os.path.join(BENCH, "limits",
                                          REAL_CELL + ".json"))
    tiny = harness.load_json(os.path.join(HERE, "data", "limits",
                                          CELL["name"] + ".json"))
    assert set(tiny["limits"]) == set(real["limits"])
    assert set(real["reasons"]) == set(real["limits"])
    counters = {"moe_tokens_dropped", "diffusion_masked_share_off_expected"}
    assert counters <= set(real["limits"])
    made = {f"loss{i}_gap" for i in (1, 2, 3)} | {
        f"{w}_norm_gap{s}" for w in ("grad", "change")
        for s in ("", "_p90_leaf", "_median_leaf", "_whole")}
    assert made == (set(real["limits"]) - counters) \
        | set(real.get("not_compared", ()))


def test_real_cell_files_are_there_and_name_each_other():
    import argparse

    import harness

    ns = argparse.Namespace(workload=REAL_CELL, seed=0, seconds=1, trace=0)
    ctx = harness.Context.load(REPO, ns, allow_cpu=True, t_start=0.0)
    assert ctx.traffic["kind"] == "train_step_lm_batch"
    assert ctx.config["architecture"] == ctx.config["reference"] == "sdar_lm"
    assert ctx.cell["chips"] == 1 and len(ctx.cell["why"]) <= 200
    assert (ctx.traffic["seq_len"], ctx.traffic["per_chip_batch"],
            ctx.traffic["block_length"]) == (4096, 1, 4)
    assert ctx.traffic["block_length"] == ctx.config["block_length"]
    assert ctx.limits["moe_tokens_dropped"] == 0
    assert ctx.traffic["expected_counters"] == {
        "diffusion_masked_share": 0.5}
    assert ctx.limits["diffusion_masked_share_off_expected"] == 0.06
    mine = {m["name"] for m in ctx.manifest["per_layer"]
            if ctx.cell["name"] in m.get("workloads", ())}
    assert mine == JOINED | NEW
    for name in mine:
        spec = harness.load_json(os.path.join(BENCH, "metrics",
                                              name + ".json"))
        harness.load_module(BENCH, "readers", spec["reader"])
    kind = harness.load_module(BENCH, "kinds", "train_step_lm_batch")
    assert set(ctx.traffic["faults"]) == {
        "half_tokens", "no_routed_experts", "causal_mask", "unweighted_loss"}
    ref = kind.reference_of(ctx)
    assert ctx.config["model_flops_per_image"] == int(round(
        ref.flops_per_sequence(ctx.config, 4096)["total"]))


def test_kind_names_no_architecture_and_holds_no_loop():
    with open(os.path.join(BENCH, "kinds", "train_step_lm_batch.py")) as f:
        text = f.read().split('"""', 2)[2]
    for word in ("sdar", "keye", "nemotron", "noised", "loss_weight",
                 "while ", "for ", "perf_counter"):
        assert word not in text, word


def test_new_files_leave_the_parents_kinds_alone():
    """``make_inputs`` is replaced on a module of this kind's own: a cell of
    the plain ``train_step_lm`` kind loaded afterwards draws uniform ids."""
    import harness

    class Ctx:
        bench_dir = BENCH

    batch_kind = harness.load_module(BENCH, "kinds", "train_step_lm_batch")
    patched = batch_kind._lm(Ctx)
    assert patched.make_inputs is batch_kind.make_inputs
    plain = harness.load_module(BENCH, "kinds", "train_step_lm")
    assert plain.make_inputs is not batch_kind.make_inputs


# -------------------------------------------------------------- the reader
class _Ctx:
    def __init__(self, config, seq_len=4096):
        self.config, self.traffic = config, {"seq_len": seq_len}
        self.bench_dir = BENCH
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _real_config():
    with open(os.path.join(BENCH, "configs",
                           "sdar_30b_a3b_stage_ep8.json")) as f:
        return json.load(f)


def test_blockdiff_roofline_sums_every_blockdiff_call_of_a_step():
    """Seven layers' forward calls and fused reverse calls of two steps
    against the algorithm's 7 x 4.888 ms: other kernels' events and a
    fusion are not read."""
    import harness

    spec = harness.load_json(os.path.join(
        BENCH, "metrics", "blockdiff_attn_kernel_roofline.json"))
    reader = harness.load_module(BENCH, "readers", spec["reader"])
    mine = ["%blockdiff_attn.3 custom-call", "%blockdiff_attn custom-call",
            "%blockdiff_attn_bwd_fused.1 custom-call",
            "%blockdiff_attn_bwd_dkv.2 custom-call"]
    others = ["%causal_attn.1 custom-call", "%sparse_attn.3 custom-call",
              "%pam custom-call", "%fusion.12 fusion",
              "%blockdiff_attn_like fusion"]
    ops = [[name, i * 1e9, i * 1e9 + (5e7 if name in mine else 9e8)]
           for i, name in enumerate(mine + others)]
    reading = {"summary": {"devices": [{"ops": ops, "steps": 2}]},
               "images_per_step": 1, "chips": 1}
    share = reader.read(_Ctx(_real_config()), reading, **spec["args"])
    assert share == pytest.approx(100.0 * 7 * 4.8884 / (4 * 50.0 / 2),
                                  rel=1e-3)
    assert share < 100.0
    # nothing to read: a program without the call (every parent), whatever
    # its configuration holds
    reading["summary"]["devices"][0]["ops"] = ops[len(mine):]
    assert reader.read(_Ctx(_real_config()), reading, **spec["args"]) is None
    assert reader.read(_Ctx({"hidden_size": 1}), reading,
                       **spec["args"]) is None


def test_roofline_closed_form_is_the_references():
    import harness

    reader = harness.load_module(BENCH, "readers",
                                 "blockdiff_attn_kernel_roofline")
    ref = harness.load_module(BENCH, "reference", "sdar_lm")
    cfg = _real_config()
    assert reader.allowed_pairs(4096, 4) == ref.allowed_pairs(4096, 4) \
        == 16793600
    flops, bytes_ = reader.block_diffusion_attention(cfg, 1, 4096)
    assert flops == ref.flops_per_sequence(cfg, 4096)["attn_scores"]
    assert flops / 197e12 > 8 * bytes_ / 819e9   # compute-bound
