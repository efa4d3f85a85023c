"""A scratch checkout for the benchmark's own tests: the benchmark's files
copied as they are, plus new files only (``tests/data``: two tiny
configurations, traffic mixes, a metric with its own reader, a new kind of
traffic) and a manifest with new entries for them.  Nothing that is there is
edited, which is what a later PR is held to.

``run(...)`` drives a cell through ``run.main`` with the look for a chip
skipped (``allow_cpu``), as the rehearsal on the CPU."""

from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")

EXTRA_CONFIGS = [
    {"name": "danet_r50_os8_64", "source": "rehearsal only",
     "file": "benchmarks/configs/danet_r50_os8_64.json",
     "reduced": ["backbone_depth", "crop_size"], "why": "CPU rehearsal"},
    {"name": "deeplabv3_r50_os16_65", "source": "rehearsal only",
     "file": "benchmarks/configs/deeplabv3_r50_os16_65.json",
     "reduced": ["backbone_depth", "crop_size"], "why": "CPU rehearsal"},
]
EXTRA_CELLS = [
    {"name": "rehearsal_danet", "config": "danet_r50_os8_64",
     "traffic": "step_b2_tiny", "chips": 1, "why": "CPU rehearsal"},
    {"name": "rehearsal_deeplab", "config": "deeplabv3_r50_os16_65",
     "traffic": "step_b2_tiny", "chips": 1, "why": "CPU rehearsal"},
    {"name": "rehearsal_danet_4dev", "config": "danet_r50_os8_64",
     "traffic": "step_b2_tiny_rb2", "chips": 4,
     "why": "CPU rehearsal of a four-device mesh with bucketed reduce"},
    {"name": "rehearsal_echo", "config": "danet_r50_os8_64",
     "traffic": "echo_mix", "chips": 1, "why": "a new kind of traffic"},
]
EXTRA_METRICS = [
    {"name": "device_busy_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "device",
     "moves": "train_imgs_per_s_per_chip",
     "workloads": ["rehearsal_echo"]},
]


def make_root(tmp: str) -> str:
    shutil.copytree(BENCH, os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    for folder in os.listdir(DATA):
        for name in os.listdir(os.path.join(DATA, folder)):
            dst = os.path.join(tmp, "benchmarks", folder, name)
            assert not os.path.exists(dst), f"{dst} would be overwritten"
            shutil.copy(os.path.join(DATA, folder, name), dst)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] += EXTRA_CONFIGS
    manifest["workloads"] += EXTRA_CELLS
    # a new cell adds its name to the metrics it reports (pam_kernel_roofline
    # stays with the cell it lists), and brings metrics of its own
    for m in manifest["per_layer"]:
        if m["name"] != "pam_kernel_roofline":
            m["workloads"] = m["workloads"] + [c["name"] for c in EXTRA_CELLS]
    manifest["per_layer"] += EXTRA_METRICS
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return tmp


def run(root: str, workload: str, seed: int = 3, seconds: float = 0.5,
        trace: int = 0, allow_cpu: bool = True) -> dict:
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    bench = os.path.join(root, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import importlib

    for name in ("run", "harness", "inputs", "compare", "xtrace"):
        sys.modules.pop(name, None)
    run_mod = importlib.import_module("run")
    return run_mod.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        root=root, allow_cpu=allow_cpu)
