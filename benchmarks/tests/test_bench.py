"""The benchmark's own fast checks.  They run on the CPU, from the root of
the repo: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.  The
tier-1 suite (``tests/``) does not collect them."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (HERE, BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import rehearsal  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.make_root(str(tmp_path_factory.mktemp("checkout")))


# ------------------------------------------------------- trace reduction

def test_trace_reduction_on_recorded_trace():
    """A small recorded trace with known answers: two steps of 1.0 ms; ops
    from 0.1 to 2.1 ms; idle 0.2 ms inside step one and 0.1 ms at the start
    of step two; loops with nested ops, which must not count twice; an op
    and a module of another program before the steps, which do not count."""
    import xtrace

    with open(os.path.join(HERE, "small_trace.json")) as f:
        raw = json.load(f)
    s = xtrace.summarize(raw, "^jit_step_fn")
    assert s["steps"] == 2
    assert s["span_s"] == pytest.approx(2.0e-3)
    assert s["busy_s"] == pytest.approx(1.7e-3)
    b = xtrace.breakdown(s)
    ops = dict(b["device_ops"])
    assert "copy.0" not in ops
    assert ops["fusion.1"] == pytest.approx(0.8e-3)
    assert ops["while.2"] == pytest.approx(0.2e-3)   # 0.6 ms less 0.4 nested
    assert ops["fusion.9"] == pytest.approx(0.4e-3)
    assert ops["custom-call.3"] == pytest.approx(0.3e-3)
    assert b["idle_gaps"] == [["bench_wait_params", pytest.approx(0.2e-3)],
                              ["bench_wait_params", pytest.approx(0.1e-3)]]
    # the module of another program is not a step
    assert all(d["steps"] == 2 for d in s["devices"])


def test_reduction_on_two_recorded_steps_of_the_danet_cell():
    """Two steps cut from a trace of the DANet cell on the v5e (PR 24, seed
    103): 14,688 device ops with loops nested; the readers' numbers for it
    are pinned to what that run reported for its eight steps, to the digit
    that two steps share with eight."""
    import gzip
    import types

    import harness
    import xtrace

    with gzip.open(os.path.join(HERE, "recorded_trace_danet_2steps.json.gz"),
                   "rt") as f:
        raw = json.load(f)
    s = xtrace.summarize(raw, "^jit_step_fn")
    assert (s["steps"], s["span_s"], s["busy_s"]) == \
        (2, pytest.approx(0.249328268), pytest.approx(0.249232461))
    ctx = types.SimpleNamespace(
        config=harness.load_json(os.path.join(
            BENCH, "configs", "danet_r101_os8_512.json")),
        peaks=harness.peaks_for(BENCH, "TPU v5 lite"))
    reading = {"summary": s, "images_per_step": 8, "chips": 1,
               "memory_peak_bytes": 10735631360}

    def metric(name):
        spec = harness.load_json(os.path.join(BENCH, "metrics",
                                              name + ".json"))
        reader = harness.load_module(BENCH, "readers", spec["reader"])
        return reader.read(ctx, reading, **spec.get("args", {}))

    assert metric("device_step_ms") == pytest.approx(124.66, abs=0.02)
    assert metric("step_mfu") == pytest.approx(55.0, abs=0.1)
    assert metric("device_idle_share") == pytest.approx(0.038, abs=0.005)
    assert metric("pam_kernel_roofline") == pytest.approx(33.4, abs=0.1)
    assert metric("hbm_peak_gb") == pytest.approx(10.7356, abs=1e-3)
    top = xtrace.breakdown(s)
    assert top["device_ops"][0][0] == "%multiply_add_fusion.3 fusion"
    assert top["idle_gaps"][0][0] == "bench_wait_params"


def test_trace_without_the_step_says_what_it_holds():
    import xtrace

    with open(os.path.join(HERE, "small_trace.json")) as f:
        raw = json.load(f)
    with pytest.raises(ValueError, match="jit_leaf_norms"):
        xtrace.summarize(raw, "^jit_train$")


def test_kernel_roofline_reads_nothing_where_nothing_matches():
    import harness

    reader = harness.load_module(BENCH, "readers", "kernel_roofline")
    dev = {"steps": 1, "ops": [["fusion.1", 0, 10]]}
    reading = {"summary": {"devices": [dev]}, "images_per_step": 8,
               "chips": 1}
    assert reader.read(None, reading, "pam", "pam_forward") is None


# ----------------------------------------------------------- the harness

def test_unknown_device_kind_raises():
    import harness

    assert harness.peaks_for(BENCH, "TPU v5 lite")["bf16_flops_per_s"] == \
        197e12
    with pytest.raises(KeyError, match="TPU v9"):
        harness.peaks_for(BENCH, "TPU v9")


def test_no_chip_no_result(root, capsys):
    """Without a TPU the command fails and prints no result line."""
    with pytest.raises(SystemExit) as e:
        rehearsal.run(root, "rehearsal_danet", allow_cpu=False)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""


def test_new_files_are_picked_up_with_no_edit(root, capsys):
    """A configuration, a cell of a new traffic kind and a per-layer metric
    with its own reader, each brought as new files plus manifest entries."""
    for folder in ("configs", "traffic", "metrics", "readers", "kinds",
                   "limits"):
        for name in os.listdir(os.path.join(BENCH, folder)):
            if name == "__pycache__":
                continue
            with open(os.path.join(BENCH, folder, name), "rb") as a, \
                    open(os.path.join(root, "benchmarks", folder, name),
                         "rb") as b:
                assert a.read() == b.read(), f"{folder}/{name} was edited"
    res = rehearsal.run(root, "rehearsal_echo", trace=1)
    assert res["correct"] and res["attempted"] == 2
    assert res["metrics"]["device_busy_ms"] == {"value": 1.5, "unit": "ms"}
    # the metrics that list other cells are not reported in this one
    assert "pam_kernel_roofline" not in res["metrics"]
    assert res["metrics"]["device_step_ms"]["value"] == pytest.approx(2.0)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert list(json.loads(last))[-1] == "compared"


def test_reference_tree_is_the_programs():
    """The weights the benchmark makes fit the program: same leaves, same
    shapes, for both configurations at their published sizes."""
    import jax
    import jax.numpy as jnp
    import harness
    from distributedpytorch_tpu.models import build_model
    from reference import nets

    def shapes(tree):
        return {jax.tree_util.keystr(p): tuple(v.shape) for p, v in
                jax.tree_util.tree_leaves_with_path(tree)}

    for name in ("danet_r101_os8_512", "deeplabv3_r101_os16_513"):
        cfg = harness.load_json(os.path.join(BENCH, "configs", name + ".json"))
        model = build_model(
            cfg["architecture"], nclass=cfg["num_classes"],
            backbone=f"resnet{cfg['backbone_depth']}",
            output_stride=cfg["output_stride"], **cfg["build_model"])
        want = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 64, 64, cfg["in_channels"])), train=False))
        got = jax.eval_shape(lambda k: nets.make_weights(k, cfg),
                             jax.random.PRNGKey(0))
        assert shapes(got[0]) == shapes(want["params"])
        assert shapes(got[1]) == shapes(want["batch_stats"])


def test_four_device_mesh_on_virtual_devices(root):
    """The same ``train_step`` traffic with ``chips: 4`` builds the
    four-device mesh (bucketed reduce, cross-replica BatchNorm) with no
    harness edit.  A process of its own: the device count is fixed at import."""
    code = ("import sys; sys.path.insert(0, %r); import rehearsal; "
            "r = rehearsal.run(%r, 'rehearsal_danet_4dev'); "
            "assert r['device']['count'] == 4 and r['attempted'] > 0, r"
            % (HERE, root))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]


# ------------------------------------------------- correct has to fail

@pytest.fixture(scope="module")
def sound(root):
    res = rehearsal.run(root, "rehearsal_danet", seed=2**31 + 11)
    assert res["correct"], res["compared"]
    return res


def _with_step(monkeypatch, root, wrap, seed=5):
    """Run the rehearsal cell with the timed step broken underneath."""
    import harness

    real_load = harness.load_module

    def load(bench_dir, folder, name):
        mod = real_load(bench_dir, folder, name)
        if folder == "kinds" and name == "train_step":
            real_build = mod.build_step
            mod.build_step = lambda make_step, state, batch: wrap(
                real_build, make_step, state, batch)
        return mod

    rehearsal.run(root, "rehearsal_echo", trace=1)  # fresh module objects
    import harness as fresh

    monkeypatch.setattr(fresh, "load_module", load)
    import run

    return run.main(["--workload", "rehearsal_danet", "--seed", str(seed),
                     "--seconds", "0.5", "--trace", "0"], root=root,
                    allow_cpu=True)


def test_state_left_unchanged_is_not_correct(sound, root, monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(real_build, make_step, state, batch):
        step = real_build(make_step, state, batch)

        def stuck(state, batch):
            _, loss = step(jax.tree.map(jnp.copy, state), batch)
            return state, loss
        return stuck

    res = _with_step(monkeypatch, root, wrap)
    assert not res["correct"]
    assert res["compared"]["change_norm_gap"][0] > \
        res["compared"]["change_norm_gap"][1]


def test_half_the_batch_left_out_is_not_correct(sound, root, monkeypatch):
    def wrap(real_build, make_step, state, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        step = real_build(make_step, state, half)
        return lambda state, batch: step(
            state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

    res = _with_step(monkeypatch, root, wrap)
    assert not res["correct"]


def test_control_in_lower_precision_is_not_correct(sound, root):
    """The reference in the precision below the configuration's, put in the
    program's place, has to fail at least one of the cell's numbers."""
    sys.path.insert(0, os.path.join(root, "benchmarks", "tools"))
    import importlib

    import compare
    import harness

    control = importlib.import_module("control")
    lines = control.main(["rehearsal_danet", "5", "6", "7"], allow_cpu=True,
                         root=root)
    limits = harness.load_json(os.path.join(
        root, "benchmarks", "limits", "rehearsal_danet.json"))["limits"]
    for line in lines:
        ok, table = compare.judge(line["numbers"], limits)
        assert not ok, (line["what"], line["seed"], table)
