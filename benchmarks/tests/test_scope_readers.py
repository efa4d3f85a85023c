"""The scope readers on the recorded two-step DANet trace, with a recorded
scope table beside it, and the four-chip cell and the ten scope metrics
picked up from data.  CPU, from the root of the repo, as ``test_bench.py``."""

import gzip
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (HERE, BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import xtrace  # noqa: E402

NEW_METRICS = (
    "backbone_device_ms", "head_device_ms", "loss_device_ms",
    "optimizer_device_ms", "backward_device_ms", "pam_backward_device_ms",
    "cam_energy_kernel_roofline", "cam_apply_kernel_roofline",
    "scope_mixed_share", "collective_exposed_ms")
NEW_CELL = "danet_r101_512_b8_step_4chip"


def _recorded():
    from distributedpytorch_tpu.telemetry import scopes

    with gzip.open(os.path.join(HERE, "recorded_trace_danet_2steps.json.gz"),
                   "rt") as f:
        summary = xtrace.summarize(json.load(f), "^jit_step_fn")
    with gzip.open(os.path.join(HERE, "recorded_scope_table_danet.json.gz"),
                   "rt") as f:
        table = scopes.ScopeTable.from_json(json.load(f)).table
    ctx = types.SimpleNamespace(
        bench_dir=BENCH,
        config=harness.load_json(os.path.join(
            BENCH, "configs", "danet_r101_os8_512.json")),
        peaks=harness.peaks_for(BENCH, "TPU v5 lite"))
    reading = {"summary": summary, "images_per_step": 8, "chips": 1,
               "scope_table": table, "breakdown": {}}
    return ctx, reading


def _metric(ctx, reading, name):
    spec = harness.load_json(os.path.join(BENCH, "metrics", name + ".json"))
    reader = harness.load_module(BENCH, "readers", spec["reader"])
    return reader.read(ctx, reading, **spec.get("args", {}))


def test_scope_metrics_on_the_recorded_danet_steps():
    """Two steps of the DANet cell on the v5e (PR 24's recording), put down
    by the table of this tree's step compiled for a described v5e: the
    instruction names are the same, metadata steers no pass.  The numbers
    are the self times summed by hand from ``attribute`` on that trace."""
    ctx, reading = _recorded()
    got = {n: _metric(ctx, reading, n) for n in NEW_METRICS}
    assert got["backbone_device_ms"] == pytest.approx(79.253, abs=0.01)
    assert got["head_device_ms"] == pytest.approx(44.94, abs=0.02)
    assert got["loss_device_ms"] == pytest.approx(0.004, abs=0.002)
    assert got["optimizer_device_ms"] == pytest.approx(0.071, abs=0.002)
    assert got["backward_device_ms"] == pytest.approx(83.495, abs=0.01)
    assert got["pam_backward_device_ms"] == pytest.approx(11.045, abs=0.01)
    assert got["scope_mixed_share"] == pytest.approx(32.50, abs=0.05)
    # one chip: no collective in the program, nothing to read
    assert got["collective_exposed_ms"] is None
    # the recording predates the kernels' names (its CAM calls are %cam.2
    # and %cam.3): nothing matches, and nothing is never 0
    assert got["cam_energy_kernel_roofline"] is None
    assert got["cam_apply_kernel_roofline"] is None
    # the parts stay under the whole
    step_ms = 1e3 * reading["summary"]["span_s"] / 2
    assert got["backbone_device_ms"] + got["head_device_ms"] \
        + got["loss_device_ms"] + got["optimizer_device_ms"] <= step_ms
    # the whole table rides in the breakdown, once
    table = reading["breakdown"]["scope_ms_per_step"]
    assert table["backbone.bwd"] == pytest.approx(51.275, abs=0.01)
    assert table["head.fwd"] == pytest.approx(12.675, abs=0.01)


def test_cam_rooflines_on_renamed_events():
    """The same two steps with the CAM calls under the names this tree gives
    them: 0.1212 ms and 0.1069 ms a step against least times of 0.0872 ms
    (FLOPs) and 0.0922 ms (bytes)."""
    ctx, reading = _recorded()
    renames = {"%cam.2 custom-call": "%cam_energy.1 custom-call",
               "%cam.3 custom-call": "%cam_apply.1 custom-call"}
    for dev in reading["summary"]["devices"]:
        dev["ops"] = [[renames.get(n, n), s, e] for n, s, e in dev["ops"]]
    assert _metric(ctx, reading, "cam_energy_kernel_roofline") == \
        pytest.approx(71.96, abs=0.05)
    assert _metric(ctx, reading, "cam_apply_kernel_roofline") == \
        pytest.approx(86.24, abs=0.05)
    assert _metric(ctx, reading, "pam_kernel_roofline") == \
        pytest.approx(33.4, abs=0.1)


def test_collective_exposed_counts_done_and_synchronous_forms_only():
    from distributedpytorch_tpu.telemetry.scopes import Scope

    def s(layer, opcode, path=None):
        return Scope(layer, path or layer, "fwd", False, opcode)

    table = {
        "all-reduce.1": s("grad_reduce", "all-reduce", "grad_reduce/b0"),
        "all-reduce-start.2": s("grad_reduce", "all-reduce-start"),
        "all-reduce-done.2": s("grad_reduce", "all-reduce-done"),
        "all-reduce.9": s("backbone", "all-reduce", "backbone/bn1"),
        "fusion.4": s("grad_reduce", "fusion"),
        "fusion.5": s("backbone", "fusion"),
    }
    ops = [["%fusion.5 fusion", 0, 1000_000],
           ["%all-reduce-start.2 all-reduce-start", 1000_000, 1010_000],
           ["%fusion.5 fusion", 1010_000, 1500_000],
           ["%all-reduce-done.2 all-reduce-done", 1500_000, 1800_000],
           ["%all-reduce.1 all-reduce", 1800_000, 2000_000],
           ["%all-reduce.9 all-reduce", 2000_000, 2100_000],   # BatchNorm's
           ["%fusion.4 fusion", 2100_000, 2200_000]]           # the divide
    dev = {"plane": "/device:TPU:0", "steps": 2, "ops": ops}
    reading = {"summary": {"devices": [dev, dict(dev)]}, "scope_table": table}
    ctx = types.SimpleNamespace(bench_dir=BENCH)
    # (0.3 ms waited + 0.2 ms synchronous) / 2 steps, mean of two equal chips
    assert _metric(ctx, reading, "collective_exposed_ms") == \
        pytest.approx(0.25)


def test_a_table_that_misses_the_busy_time_raises():
    ctx, reading = _recorded()
    table = reading["scope_table"]
    heavy = {"multiply_add_fusion.3", "convert_reduce_fusion.9"}
    reading["scope_table"] = {k: v for k, v in table.items()
                              if k not in heavy}
    with pytest.raises(RuntimeError, match="not of the executable that ran"):
        _metric(ctx, reading, "backbone_device_ms")
    # under the limit it reads: the recording's two CAM calls are 0.18%
    ctx, reading = _recorded()
    del reading["scope_table"]["cam.2"], reading["scope_table"]["cam.3"]
    assert _metric(ctx, reading, "backbone_device_ms") > 0


def test_the_new_cell_and_metrics_are_data():
    """The four-chip cell and the ten metrics are manifest entries and new
    files; nothing the harness had is edited (``git diff`` against the
    parent is the driver's check, this is the reader's)."""
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = {w["name"]: w for w in manifest["workloads"]}[NEW_CELL]
    assert cell["chips"] == 4 and cell["config"] == "danet_r101_os8_512"
    traffic = harness.load_json(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json"))
    assert (traffic["kind"], traffic["per_chip_batch"],
            traffic["reduce_buckets"]) == ("train_step", 8, 8)
    assert os.path.isfile(os.path.join(BENCH, "limits", NEW_CELL + ".json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    four_chip_cells = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four_chip_cells) == 1
    for name in NEW_METRICS:
        assert NEW_CELL in by_name[name]["workloads"]
        spec = harness.load_json(os.path.join(BENCH, "metrics",
                                              name + ".json"))
        assert hasattr(harness.load_module(BENCH, "readers", spec["reader"]),
                       "read")
    for name in ("step_mfu", "device_step_ms", "device_idle_share",
                 "hbm_peak_gb", "pam_kernel_roofline"):
        assert by_name[name]["workloads"][-1] == NEW_CELL
    assert by_name["collective_exposed_ms"]["workloads"] == [NEW_CELL]
    deeplab = "deeplabv3_r101_513_b16_step_1chip"
    kernels = [m["name"] for m in manifest["per_layer"]
               if m["layer"] == "kernels"]
    assert all(deeplab not in by_name[k]["workloads"] for k in kernels)


def test_the_reader_builds_the_table_as_the_kind_builds_the_step(tmp_path):
    """``scope_ms.build_table`` on four virtual devices, at rehearsal size:
    the bucketed step's table holds ``grad_reduce`` collectives and every
    model layer.  A process of its own: the device count is fixed at
    import."""
    import rehearsal

    root = rehearsal.make_root(str(tmp_path))
    code = """
import sys, time, argparse
sys.path[:0] = [%(bench)r, %(repo)r]
import harness
ns = argparse.Namespace(workload="rehearsal_danet_4dev", seed=0, seconds=1,
                        trace=1)
ctx = harness.Context.load(%(root)r, ns, allow_cpu=True, t_start=time.time())
ctx.acquire_devices()
ctx.enable_cache()
table = harness.load_module(ctx.bench_dir, "readers", "scope_ms").build_table(ctx)
from distributedpytorch_tpu.telemetry import scopes
layers = {s.layer for s in table.values()}
assert {"backbone", "head", "loss", "optimizer", "grad_reduce"} <= layers, layers
assert any(s.layer == "grad_reduce" and scopes.is_collective(s.opcode)
           for s in table.values())
""" % {"bench": os.path.join(root, "benchmarks"), "repo": REPO, "root": root}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
