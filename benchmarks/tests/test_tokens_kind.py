"""The ``train_step_tokens`` kind on the CPU: a tiny token cell brought as
new files and manifest entries (``tests/data``), run through ``run.main``
untraced and traced; the faults that ``correct`` has to catch; the counter
reader.  ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

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

CONFIG = {"name": "nemotron_h_tiny", "source": "rehearsal only",
          "file": "benchmarks/configs/nemotron_h_tiny.json",
          "reduced": ["hidden_size"], "why": "CPU rehearsal"}
CELL = {"name": "rehearsal_tokens", "config": "nemotron_h_tiny",
        "traffic": "step_tokens_tiny", "chips": 1, "why": "CPU rehearsal"}


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
    res = rehearsal.run(root, "rehearsal_tokens", seed=2**31 + 17)
    assert res["correct"], res["compared"]
    return res


def test_untraced_run_is_correct_and_counts_sequences(sound):
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["rehearsal"] and "setup_s" in sound["metrics"]
    assert sound["compared"]["moe_tokens_dropped"] == [0.0, 0]
    assert sound["numbers"]["grad_norm_gap"] < 1e-5
    assert sound["reference_s"] > 0


class CannedTracer:
    """The CPU's profiler has no device plane: a stand-in trace of two
    executions of the step, with nothing on the ops line (the test puts
    events there from the reading's own table)."""

    def start(self):
        pass

    def stop(self):
        pass

    def read(self, ctx):
        dev = {"ops": [["placeholder", 0.0, 4e6]],
               "modules": [["jit_step_fn(1)", 0.0, 2e6],
                           ["jit_step_fn(1)", 2e6, 4e6]]}
        return {"devices": {"/device:TPU:0": dev}, "host": []}


def test_traced_run_puts_table_and_counters_into_the_reading(
        sound, root, monkeypatch):
    """A traced run hands the readers the scope table of the executable it
    ran and the counters; the scope readers join on that table (the step is
    never rebuilt from image inputs) and the counter reader reads."""
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

    res = run_mod.main(["--workload", "rehearsal_tokens", "--seed", "5",
                        "--seconds", "0.5", "--trace", "1"], root=root,
                       allow_cpu=True)
    assert res["correct"] and res["device"]["window_s"] == pytest.approx(4e-3)
    ctx, reading = seen["ctx"], seen["reading"]
    assert reading["images_per_step"] == 2 and reading["chips"] == 1
    assert reading["counters"] == {
        "moe_tokens_dropped": 0,
        "moe_expert_load_max_over_mean": pytest.approx(
            reading["counters"]["moe_expert_load_max_over_mean"])}
    assert reading["counters"]["moe_expert_load_max_over_mean"] >= 1.0
    table = reading["scope_table"]
    layers = {s.layer for s in table.values()}
    assert {"embed", "mamba", "attn", "moe", "mtp", "lm_head", "loss",
            "optimizer"} <= layers
    # one millisecond for one instruction of each layer, over two steps
    picked = {}
    for name, s in table.items():
        if s.opcode == "fusion" and s.layer not in picked:
            picked[s.layer] = name
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

    assert metric("moe_device_ms") == pytest.approx(0.5)
    assert metric("ssm_device_ms") == pytest.approx(0.5)
    assert metric("attn_device_ms") == pytest.approx(0.5)
    assert metric("vocab_device_ms") == pytest.approx(
        0.5 * len({"embed", "lm_head", "mtp"} & set(picked)))
    assert metric("moe_tokens_dropped") == 0
    assert metric("moe_expert_load_max_over_mean") >= 1.0


def _with_step(monkeypatch, root, wrap, seed=5):
    """Run the token cell with the timed step broken underneath."""
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

    return run_mod.main(["--workload", "rehearsal_tokens", "--seed",
                         str(seed), "--seconds", "0.5", "--trace", "0"],
                        root=root, allow_cpu=True)


def test_state_left_unchanged_is_not_correct(sound, root, monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(real_build, make_step, state, batch):
        step = real_build(make_step, state, batch)

        def stuck(state, batch):
            _, out = step(jax.tree.map(jnp.copy, state), batch)
            return state, out
        return stuck

    res = _with_step(monkeypatch, root, wrap)
    assert not res["correct"]
    for name in ("change_norm_gap", "change_norm_gap_median_leaf",
                 "change_norm_gap_whole"):
        value, limit = res["compared"][name]
        assert value == pytest.approx(1.0, abs=1e-3) and value > limit


def test_routed_experts_left_out_is_not_correct(sound, root, monkeypatch):
    """The step built over a model whose held experts' second product is
    zeroed: the routed part of every expert layer is gone."""
    import jax
    import jax.numpy as jnp

    def wrap(real_build, make_step, state, batch):
        step = real_build(make_step, state, batch)

        def without(state, batch):
            def zero(path, leaf):
                return jnp.zeros_like(leaf) \
                    if jax.tree_util.keystr(path).endswith("['w2']") else leaf
            gone = state.replace(params=jax.tree_util.tree_map_with_path(
                zero, state.params))
            return step(gone, batch)
        return without

    res = _with_step(monkeypatch, root, wrap)
    assert not res["correct"]
    assert res["compared"]["grad_norm_gap"][0] > \
        res["compared"]["grad_norm_gap"][1]


def test_half_the_tokens_left_out_is_not_correct(sound, root, monkeypatch):
    """The step run on the first half of the step's sequences."""
    def halve(batch):
        return {k: v[: v.shape[0] // 2] for k, v in batch.items()}

    def wrap(real_build, make_step, state, batch):
        step = real_build(make_step, state, halve(batch))
        return lambda state, batch: step(state, halve(batch))

    res = _with_step(monkeypatch, root, wrap)
    assert not res["correct"]
    broke = _broke(res)
    assert {"grad_norm_gap", "grad_norm_gap_median_leaf",
            "change_norm_gap_median_leaf", "change_norm_gap_whole"} <= broke


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_control_in_the_programs_place_is_not_correct(sound, root,
                                                      monkeypatch, seed):
    """The reference computed in the precision below the configuration's
    (``nets.Rounding``, ``tools/control.py``'s method) stands where the
    step stood, and the cell's own run reads it: not ``correct``."""
    import functools

    import jax
    import jax.numpy as jnp

    def wrap(real_build, make_step, state, batch):
        import harness
        from control import BELOW
        from reference import nemotron_h as ref
        from reference import nets

        cfg = harness.load_json(os.path.join(
            root, "benchmarks", "configs", "nemotron_h_tiny.json"))
        # the counters a sound step hands back (the step donates its state:
        # a copy goes in)
        _, (_, counters) = real_build(make_step, state, batch)(
            jax.tree.map(jnp.copy, state), batch)
        control = jax.jit(functools.partial(
            ref.train_step, cfg, cfg["optimizer"],
            q=nets.Rounding(BELOW[cfg["precision"]])))
        momentum = jax.tree.structure(state.opt_state)

        def step(state, batch):
            # optax's momentum trace is a tree like the parameters
            params, trace, loss = control(
                state.params, jax.tree.structure(state.params).unflatten(
                    jax.tree.leaves(state.opt_state)), batch)
            return state.replace(
                step=state.step + 1, params=params,
                opt_state=momentum.unflatten(jax.tree.leaves(trace))), \
                (loss, counters)
        return step

    sys.path.insert(0, os.path.join(root, "benchmarks", "tools"))
    res = _with_step(monkeypatch, root, wrap, seed=seed)
    assert not res["correct"]
    assert _broke(res) & {"loss1_gap", "loss2_gap", "loss3_gap"}
    assert {"grad_norm_gap", "change_norm_gap",
            "change_norm_gap_whole"} <= _broke(res)


def _broke(res) -> set:
    return {k for k, (v, lim) in res["compared"].items() if not v <= lim}


def test_control_tool_judges_by_the_cells_limits(sound, root):
    """``tools/control_tokens.py`` puts each variant through
    ``compare.judge`` with the cell's limits and says ``correct``: the
    control and both faults are not (a float32 cell has no witness)."""
    sys.path.insert(0, os.path.join(root, "benchmarks", "tools"))
    import importlib

    tool = importlib.import_module("control_tokens")
    lines = tool.main(["rehearsal_tokens", "5", "6", "7"], allow_cpu=True,
                      root=root)
    assert {line["what"] for line in lines} == {
        "control", "half_tokens", "no_routed_experts"}
    for line in lines:
        assert not line["correct"] and line["broke"], line


def test_rehearsal_holds_the_real_cells_limit_names():
    import harness

    real = harness.load_json(os.path.join(
        BENCH, "limits", "nemotron3_super_8k_b1_step_1chip.json"))
    tiny = harness.load_json(os.path.join(
        HERE, "data", "limits", "rehearsal_tokens.json"))
    assert set(tiny["limits"]) == set(real["limits"])
    assert set(real["reasons"]) == set(real["limits"])
    # every number the comparison makes is either held or named as left out
    made = {f"loss{i}_gap" for i in (1, 2, 3)} | {
        f"{w}_norm_gap{s}" for w in ("grad", "change")
        for s in ("", "_p90_leaf", "_median_leaf", "_whole")}
    assert made == (set(real["limits"]) - {"moe_tokens_dropped"}) \
        | set(real["not_compared"])


def test_counter_reads_nothing_where_the_reading_has_none():
    import harness

    reader = harness.load_module(BENCH, "readers", "counter")
    assert reader.read(None, {"summary": {}}, "moe_tokens_dropped") is None
    assert reader.read(None, {"counters": {}}, "moe_tokens_dropped") is None
    assert reader.read(None, {"counters": {"moe_tokens_dropped": 0}},
                       "moe_tokens_dropped") == 0


def test_real_cell_files_are_there_and_name_each_other():
    """``harness.Context.load`` looks each file up by name and a missing one
    is a ``KeyError``: the new cell's files are all present."""
    import argparse

    import harness

    ns = argparse.Namespace(workload="nemotron3_super_8k_b1_step_1chip",
                            seed=0, seconds=1, trace=0)
    ctx = harness.Context.load(REPO, ns, allow_cpu=True, t_start=0.0)
    assert ctx.traffic["kind"] == "train_step_tokens"
    assert ctx.config["architecture"] == "nemotron_h"
    assert ctx.limits["moe_tokens_dropped"] == 0
    mine = [m for m in ctx.manifest["per_layer"]
            if ctx.cell["name"] in m.get("workloads", ())]
    assert len(mine) >= 17
    for m in mine:
        spec = harness.load_json(os.path.join(BENCH, "metrics",
                                              m["name"] + ".json"))
        harness.load_module(BENCH, "readers", spec["reader"])
