"""The ``train_step_lm`` kind on the CPU: a tiny ``keye_lm`` cell brought as
new files and manifest entries (``tests/data``), run through ``run.main``
untraced and traced; the control and the four faults that ``correct`` has to
catch, under the real cell's limit names; the readers this PR's metrics use.
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
from test_tokens_kind import CannedTracer  # noqa: E402

CONFIG = {"name": "keye_lm_tiny", "source": "rehearsal only",
          "file": "benchmarks/configs/keye_lm_tiny.json",
          "reduced": ["hidden_size"], "why": "CPU rehearsal"}
CELL = {"name": "rehearsal_lm", "config": "keye_lm_tiny",
        "traffic": "step_lm_tiny", "chips": 1, "why": "CPU rehearsal"}
REAL_CELL = "keye_vl2_30b_8k_b1_step_1chip"


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
    res = rehearsal.run(root, "rehearsal_lm", seed=2**31 + 17)
    assert res["correct"], res["compared"]
    return res


def _broke(res) -> set:
    return {k for k, (v, lim) in res["compared"].items() if not v <= lim}


def test_untraced_run_is_correct_and_judges_the_counters(sound):
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["rehearsal"] and "setup_s" in sound["metrics"]
    assert sound["compared"]["moe_tokens_dropped"] == [0.0, 0]
    assert sound["compared"]["sparse_attn_keys_over_topk"] == [0.0, 0]
    off, limit = sound["compared"]["sparse_attn_kept_share_off_expected"]
    assert off < 1e-6 < limit
    # every counter the step handed back is in the numbers, by its own name
    assert {"moe_tokens_dropped", "moe_expert_load_max_over_mean",
            "moe_chunks_run_share", "sparse_attn_kept_share",
            "sparse_attn_keys_over_topk"} <= set(sound["numbers"])
    assert sound["numbers"]["grad_norm_gap"] < 1e-5
    assert sound["reference_s"] > 0


def test_traced_run_puts_table_and_every_counter_into_the_reading(
        sound, root, monkeypatch):
    rehearsal.run(root, "rehearsal_echo", trace=1)   # fresh module objects
    import harness

    seen = {}
    real_finish = harness.finish

    def finish(ctx, run):
        seen["ctx"], seen["reading"] = ctx, run["reading"]
        return real_finish(ctx, run)

    monkeypatch.setattr(harness, "Tracer", CannedTracer)
    monkeypatch.setattr(harness, "finish", finish)
    import run as run_mod

    res = run_mod.main(["--workload", "rehearsal_lm", "--seed", "5",
                        "--seconds", "0.5", "--trace", "1"], root=root,
                       allow_cpu=True)
    assert res["correct"]
    ctx, reading = seen["ctx"], seen["reading"]
    assert reading["images_per_step"] == 2 and reading["chips"] == 1
    assert set(reading["counters"]) == {
        "moe_tokens_dropped", "moe_expert_load_max_over_mean",
        "moe_chunks_run_share", "sparse_attn_kept_share",
        "sparse_attn_keys_over_topk"}
    table = reading["scope_table"]
    assert {"embed", "attn", "moe", "lm_head", "loss", "optimizer"} <= {
        s.layer for s in table.values()}
    # one millisecond for one instruction under each of the new scopes
    picked = {}
    for name, s in table.items():
        for part in ("indexer", "topk_select", "index_align"):
            if part in s.path.split("/") and part not in picked:
                picked[part] = name
    assert set(picked) == {"indexer", "topk_select", "index_align"}
    dev = reading["summary"]["devices"][0]
    dev["ops"] = [[f"%{name} fusion", i * 1e6, (i + 1) * 1e6]
                  for i, name in enumerate(picked.values())]
    reading.pop("_scope_join", None)

    def metric(name):
        spec = harness.load_json(os.path.join(ctx.bench_dir, "metrics",
                                              name + ".json"))
        reader = harness.load_module(ctx.bench_dir, "readers",
                                     spec["reader"])
        return reader.read(ctx, reading, **spec.get("args", {}))

    for name in ("indexer_device_ms", "sparse_select_device_ms",
                 "index_align_device_ms"):
        assert metric(name) == pytest.approx(0.5), name
    assert metric("attn_device_ms") == pytest.approx(1.5)
    assert metric("sparse_attn_keys_over_topk") == 0
    assert metric("sparse_attn_kept_share") == pytest.approx(
        ctx.traffic["expected_counters"]["sparse_attn_kept_share"], abs=1e-6)


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

    return run_mod.main(["--workload", "rehearsal_lm", "--seed", str(seed),
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
        from reference import keye_lm as ref

        cfg = harness.load_json(os.path.join(
            root, "benchmarks", "configs", "keye_lm_tiny.json"))
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
    ("half_tokens", {"rows": 24}, {"grad_norm_gap", "change_norm_gap_whole"}),
    ("no_routed_experts", {"drop_routed": True}, {"grad_norm_gap"}),
    ("dense_attention", {"dense_attention": True}, {"grad_norm_gap"}),
    ("no_align_loss", {"drop_align": True}, {"loss1_gap", "grad_norm_gap",
                                             "change_norm_gap"}),
])
def test_each_fault_is_not_correct(sound, root, monkeypatch, name, variant,
                                   breaks):
    res = _with_step(monkeypatch, root,
                     _reference_in_the_steps_place(root, **variant))
    assert not res["correct"], name
    assert breaks <= _broke(res), (name, _broke(res))
    if name == "no_align_loss":  # the indexer's leaves do not change
        assert res["compared"]["change_norm_gap"][0] == pytest.approx(1.0,
                                                                      abs=1e-3)


def test_a_selection_that_keeps_too_much_is_not_correct(sound, root,
                                                        monkeypatch):
    """A step whose counter says a row kept a key beyond its top-k."""
    def wrap(real_build, make_step, state, batch):
        step = real_build(make_step, state, batch)

        def over(state, batch):
            state, (loss, counters) = step(state, batch)
            return state, (loss, dict(
                counters, sparse_attn_keys_over_topk=counters[
                    "sparse_attn_keys_over_topk"] + 1))
        return over

    res = _with_step(monkeypatch, root, wrap)
    assert not res["correct"]
    assert _broke(res) == {"sparse_attn_keys_over_topk"}


def test_control_tool_judges_by_the_cells_limits(sound, root):
    sys.path.insert(0, os.path.join(root, "benchmarks", "tools"))
    import importlib

    tool = importlib.import_module("control_lm")
    lines = tool.main(["rehearsal_lm", "5", "6"], allow_cpu=True, root=root)
    assert {line["what"] for line in lines} == {
        "control", "half_tokens", "no_routed_experts", "dense_attention",
        "no_align_loss"}
    for line in lines:
        assert not line["correct"] and line["broke"], line
        assert "sparse_attn_keys_over_topk" not in line["broke"]


def test_rehearsal_holds_the_real_cells_limit_names():
    import harness

    real = harness.load_json(os.path.join(BENCH, "limits",
                                          REAL_CELL + ".json"))
    tiny = harness.load_json(os.path.join(HERE, "data", "limits",
                                          "rehearsal_lm.json"))
    assert set(tiny["limits"]) == set(real["limits"])
    assert set(real["reasons"]) == set(real["limits"])
    counters = {"moe_tokens_dropped", "sparse_attn_keys_over_topk",
                "sparse_attn_kept_share_off_expected"}
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
    assert ctx.traffic["kind"] == "train_step_lm"
    assert ctx.config["architecture"] == ctx.config["reference"] == "keye_lm"
    assert ctx.cell["chips"] == 1 and len(ctx.cell["why"]) <= 200
    assert ctx.limits["sparse_attn_keys_over_topk"] == 0
    expected = ctx.traffic["expected_counters"]["sparse_attn_kept_share"]
    assert expected == pytest.approx(14681088 / 33558528, abs=1e-12)
    mine = [m for m in ctx.manifest["per_layer"]
            if ctx.cell["name"] in m.get("workloads", ())]
    assert len(mine) == 16 + 10
    assert "attn_kernel_roofline" not in {m["name"] for m in mine}
    for m in mine:
        spec = harness.load_json(os.path.join(BENCH, "metrics",
                                              m["name"] + ".json"))
        harness.load_module(BENCH, "readers", spec["reader"])
    kind = harness.load_module(BENCH, "kinds", "train_step_lm")
    assert set(ctx.traffic["faults"]) == {
        "half_tokens", "no_routed_experts", "dense_attention",
        "no_align_loss"}
    ref = kind.reference_of(ctx)
    assert ctx.config["model_flops_per_image"] == int(round(
        ref.flops_per_sequence(ctx.config, 8192)["total"]))


def test_kind_names_no_architecture():
    with open(os.path.join(BENCH, "kinds", "train_step_lm.py")) as f:
        text = f.read()
    for word in ("nemotron", "keye", "mtp_loss_weight", "moe_tokens_dropped",
                 "sparse_attn"):
        assert word not in text.split('"""', 2)[2], word


# ------------------------------------------------------------ the readers
class _Ctx:
    def __init__(self, config, seq_len=8192):
        self.config, self.traffic = config, {"seq_len": seq_len}
        self.bench_dir = BENCH
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _real_config():
    with open(os.path.join(BENCH, "configs",
                           "keye_vl2_30b_a3b_lm_stage_ep8.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("metric,event,ms,bound", [
    ("sparse_attn_kernel_roofline", "%sparse_attn.3 custom-call", 29.914,
     "flops"),
    ("sparse_attn_kernel_roofline", "%sparse_attn_bwd_fused.1 custom-call",
     29.914, "flops"),
    ("sparse_probs_kernel_roofline", "%sparse_probs.2 custom-call", 4.2735,
     "flops"),
    ("indexer_scores_kernel_roofline", "%indexer_scores custom-call", 2.4421,
     "flops"),
    ("indexer_scores_bwd_kernel_roofline", "%indexer_scores_bwd.7 custom-call",
     7.3263, "flops"),
    ("topk_keep_kernel_roofline", "%topk_keep.1 custom-call", 1.4341, "bytes"),
])
def test_kernel_rooflines_read_their_own_events(metric, event, ms, bound):
    """Each roofline metric sums the events of its own kernel and no other's;
    the least time is the algorithm's at the cell's shapes (7 layers)."""
    import harness

    spec = harness.load_json(os.path.join(BENCH, "metrics", metric + ".json"))
    reader = harness.load_module(BENCH, "readers", spec["reader"])
    others = ["%sparse_attn.3 custom-call",
              "%sparse_attn_bwd_fused.1 custom-call",
              "%sparse_probs.2 custom-call", "%indexer_scores custom-call",
              "%indexer_scores_bwd.7 custom-call", "%topk_keep.1 custom-call",
              "%causal_attn.1 custom-call", "%fusion.12 fusion"]
    mine = {"sparse_attn_kernel_roofline": others[:2]}.get(metric, [event])
    ops = [[name, i * 1e9, i * 1e9 + (4e7 if name in mine else 9e8)]
           for i, name in enumerate(others)]
    reading = {"summary": {"devices": [{"ops": ops, "steps": 2}]},
               "images_per_step": 1, "chips": 1}
    share = reader.read(_Ctx(_real_config()), reading, **spec["args"])
    took_ms = len(mine) * 40.0 / 2
    assert share == pytest.approx(100.0 * ms / took_ms, rel=2e-3)
    assert share < 100.0
    # nothing to read: a program without the call, a configuration without
    # the mechanism (the parent's cells)
    reading["summary"]["devices"][0]["ops"] = ops[-2:]
    assert reader.read(_Ctx(_real_config()), reading, **spec["args"]) is None
    assert reader.read(_Ctx({"hidden_size": 1}), reading,
                       **spec["args"]) is None


def test_roofline_closed_forms_are_the_references():
    import harness

    reader = harness.load_module(BENCH, "readers",
                                 "sparse_attn_kernel_roofline")
    ref = harness.load_module(BENCH, "reference", "keye_lm")
    cfg = _real_config()
    z = reader._sizes(cfg, 8192)
    assert (z["selected"], z["causal"]) == ref.pairs(8192, 2048)
    terms = ref.flops_per_sequence(cfg, 8192)
    assert reader.sparse_attention(cfg, 1, 8192)[0] == terms["attn_scores"]
    assert reader.indexer_scores(cfg, 1, 8192)[0] \
        + reader.indexer_scores_bwd(cfg, 1, 8192)[0] \
        == pytest.approx(terms["index_scores"] * 4 / 3)
