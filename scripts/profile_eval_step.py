"""Op-level device profile of the jitted EVAL step (the r4 val breakdown
measured valstep at 202 ms/batch on the semantic 513² config — ~15x the
expected forward cost; this names the ops responsible).

Builds the real Trainer for the bench_e2e variant-12 config (or the
instance fast path with --task instance), traces N eval-step calls on a
pre-placed batch, and prints the hlo_stats top ops as one JSON line —
the same report shape as profile_step.py.
"""

from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")

import jax  # noqa: E402

from distributedpytorch_tpu.backend_health import (  # noqa: E402
    enable_compile_cache,
    require_accelerator,
)

ON_TPU = require_accelerator("scripts/profile_eval_step.py") == "tpu"
enable_compile_cache()

import dataclasses  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

TASK = "semantic"
if "--task" in sys.argv:
    TASK = sys.argv[sys.argv.index("--task") + 1]
OUT = "profile_eval_out"
if "--out" in sys.argv:
    OUT = sys.argv[sys.argv.index("--out") + 1]
STEPS = 10


def main() -> None:
    from distributedpytorch_tpu.parallel import (
        INPUT_KEY,
        pad_to_multiple,
        shard_batch,
    )
    from distributedpytorch_tpu.train import Config, Trainer, apply_overrides

    size = 513 if ON_TPU else 64
    overrides = [
        "data.fake=true", "data.train_batch=4", "data.val_batch=8",
        "model.dtype=" + ("bfloat16" if ON_TPU else "float32"),
        "checkpoint.async_save=false", "epochs=1",
    ]
    if TASK == "semantic":
        overrides += [
            "task=semantic", "model.name=deeplabv3", "model.nclass=21",
            "model.in_channels=3", "model.output_stride=16",
            f"data.crop_size=[{size},{size}]",
        ]
    else:
        overrides += [
            f"data.crop_size=[{size - 1},{size - 1}]",
            "model.output_stride=8",
        ]
    if not ON_TPU:
        overrides += ["model.backbone=resnet18"]
    cfg = apply_overrides(Config(), overrides)
    cfg = dataclasses.replace(cfg, work_dir=tempfile.mkdtemp())
    tr = Trainer(cfg)
    b = 8
    r = np.random.RandomState(0)
    in_ch = cfg.model.in_channels
    batch = {
        INPUT_KEY: r.uniform(0, 255, (b, size, size, in_ch)
                             ).astype(np.float32),
        "crop_gt": (
            r.randint(0, cfg.model.nclass, (b, size, size)).astype(np.int32)
            if TASK == "semantic" else
            (r.uniform(size=(b, size, size)) > 0.7).astype(np.float32)),
    }
    with tr.mesh:
        padded, _ = pad_to_multiple(batch, tr.mesh.devices.size)
        placed = shard_batch(tr.mesh, padded)
        outputs, loss = tr.eval_step(tr.state, placed)  # compile
        jax.block_until_ready(loss)
        with jax.profiler.trace(OUT):
            for _ in range(STEPS):
                outputs, loss = tr.eval_step(tr.state, placed)
            jax.block_until_ready((outputs, loss))
    tr.close()

    from tensorflow.python.profiler.internal import (
        _pywrap_profiler_plugin as pp,
    )
    paths = sorted(glob.glob(
        os.path.join(OUT, "plugins", "profile", "*", "*.xplane.pb")))
    data, _ = pp.xspace_to_tools_data([paths[-1]], "hlo_stats")
    t = json.loads(data.decode() if isinstance(data, bytes) else data)
    cols = [c.get("label") or c.get("id") for c in t["cols"]]

    def ci(name):
        return cols.index(name)

    rows = []
    for row in t["rows"]:
        c = [x.get("v") if isinstance(x, dict) else x for x in row["c"]]
        rows.append(c)
    rows.sort(key=lambda c: -float(c[ci("Total self time (us)")] or 0))
    total = sum(float(c[ci("Total self time (us)")] or 0) for c in rows)
    report = {
        "metric": f"{TASK}_eval_step_profile",
        "platform": "tpu" if ON_TPU else "cpu",
        "steps": STEPS,
        "total_self_us_per_step": round(total / STEPS),
        "top_ops": [
            {
                "us_per_step": round(
                    float(c[ci("Total self time (us)")]) / STEPS),
                "op": c[ci("HLO op name")],
                "fw_op": str(c[ci("Framework op name")])[:110],
                "bound_by": c[ci("Bound by")],
                "bw_gibs": round(
                    float(c[ci("Measured memory BW (GiB/s)")] or 0), 1),
                "src": str(c[ci("Source Info")]).split("/")[-1],
            }
            for c in rows[:12]
        ],
    }
    print(json.dumps(report))


if __name__ == "__main__":
    main()
