"""Op-level device profile of the flagship train step: run N steps under ``jax.profiler.trace``, convert
the XPlane capture to the XProf "hlo_stats" table, and print the top ops by
self time as JSON — plus write the raw trace for TensorBoard/xprof.

Usage:  python scripts/profile_step.py [--batch N] [--out DIR]
Writes <out>/plugins/profile/... (raw trace) and prints one JSON line with
the top-15 self-time ops and their category shares.
"""

from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# tensorboard_plugin_profile's generated protos predate protobuf 4's C++
# fast path; pure-python parsing works and only runs at conversion time.
os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")

import jax  # noqa: E402

from distributedpytorch_tpu.backend_health import (  # noqa: E402
    enable_compile_cache,
    require_accelerator,
)

ON_TPU = require_accelerator("scripts/profile_step.py") == "tpu"
enable_compile_cache()

import numpy as np  # noqa: E402
import optax  # noqa: E402

BATCH = 8
STEPS = 10
if "--batch" in sys.argv:
    BATCH = int(sys.argv[sys.argv.index("--batch") + 1])
OUT = "profile_step_out"
if "--out" in sys.argv:
    OUT = sys.argv[sys.argv.index("--out") + 1]
SCORE_DTYPE = None  # model.pam_score_dtype: profile the bf16-scores step
if "--score-dtype" in sys.argv:
    SCORE_DTYPE = sys.argv[sys.argv.index("--score-dtype") + 1]
#: --model deeplabv3 profiles BASELINE config 4 (DeepLabV3-R101 os=16 513²,
#: 21-class multi-output CE, 3-channel input) — the same shape bench.py's
#: DPTPU_BENCH_MODEL hook measures.
MODEL = "danet"
if "--model" in sys.argv:
    MODEL = sys.argv[sys.argv.index("--model") + 1]
SEMANTIC = MODEL != "danet"
SIZE = (513 if SEMANTIC else 512) if ON_TPU else 64
BACKBONE = "resnet101" if ON_TPU else "resnet18"


def hlo_stats_table(trace_dir: str):
    """XPlane capture -> hlo_stats rows via the xprof conversion library."""
    from tensorflow.python.profiler.internal import (
        _pywrap_profiler_plugin as pp,
    )

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data, _ = pp.xspace_to_tools_data([paths[-1]], "hlo_stats")
    if isinstance(data, bytes):
        data = data.decode("utf-8", "replace")
    return json.loads(data)


def top_ops(table, n: int = 15):
    """gviz-style {cols, rows} -> top-n rows by self time."""
    cols = [c.get("label") or c.get("id") for c in table["cols"]]

    def col(name_part):
        for i, c in enumerate(cols):
            if c and name_part.lower() in str(c).lower():
                return i
        return None

    i_name = col("hlo op name") or col("op name") or 0
    i_cat = col("category")
    i_self = col("self time")  # typically us
    i_frac = col("%")
    rows = []
    for r in table["rows"]:
        c = [x.get("v") if isinstance(x, dict) else x for x in r["c"]]
        rows.append({
            "op": c[i_name],
            "category": c[i_cat] if i_cat is not None else "",
            "self_time_us": c[i_self] if i_self is not None else None,
            "pct": c[i_frac] if i_frac is not None else None,
        })
    rows = [r for r in rows if isinstance(r["self_time_us"], (int, float))]
    rows.sort(key=lambda r: -r["self_time_us"])
    return rows[:n]


def category_totals(table):
    """Self-time summed per op category over the WHOLE table — the view
    that attributes a step's device time (the top-15 alone undercounts
    long-tail categories like data formatting)."""
    rows = top_ops(table, n=10**9)
    tot: dict[str, float] = {}
    for r in rows:
        tot[r["category"] or "?"] = (
            tot.get(r["category"] or "?", 0.0) + r["self_time_us"])
    total = sum(tot.values()) or 1.0
    return {k: {"self_time_us": round(v, 1), "pct": round(100 * v / total, 2)}
            for k, v in sorted(tot.items(), key=lambda kv: -kv[1])}


def main() -> None:
    from distributedpytorch_tpu.models import build_model
    from distributedpytorch_tpu.parallel import (
        create_train_state,
        make_mesh,
        make_train_step,
        shard_batch,
    )

    mesh = make_mesh()
    dtype = "bfloat16" if ON_TPU else "float32"
    in_ch, nclass = (3, 21) if SEMANTIC else (4, 1)
    if SEMANTIC:
        model = build_model(MODEL, nclass=nclass, backbone=BACKBONE,
                            output_stride=16, dtype=dtype, aux_head=True)
    else:
        model = build_model("danet", nclass=nclass, backbone=BACKBONE,
                            output_stride=8, dtype=dtype,
                            pam_score_dtype=SCORE_DTYPE)
    tx = optax.sgd(1e-3, momentum=0.9)
    r = np.random.RandomState(0)
    host_batch = {
        "concat": r.uniform(0, 255, (BATCH, SIZE, SIZE, in_ch)
                            ).astype(np.float32),
        "crop_gt": (
            r.randint(0, nclass, (BATCH, SIZE, SIZE)).astype(np.float32)
            if SEMANTIC else
            (r.uniform(size=(BATCH, SIZE, SIZE)) > 0.7).astype(np.float32)),
    }
    with mesh:
        state = create_train_state(jax.random.PRNGKey(0), model, tx,
                                   (1, SIZE, SIZE, in_ch), mesh=mesh)
        step = make_train_step(
            model, tx, mesh=mesh,
            loss_type="multi_softmax" if SEMANTIC else "multi_sigmoid")
        batch = shard_batch(mesh, host_batch)
        state, loss = step(state, batch)  # compile outside the trace
        jax.block_until_ready(loss)
        with jax.profiler.trace(OUT):
            for _ in range(STEPS):
                state, loss = step(state, batch)
            jax.block_until_ready(loss)

    rec = {"metric": f"{MODEL}_{BACKBONE}_{SIZE}px_b{BATCH}_profile",
           "trace_dir": OUT, "steps": STEPS,
           "score_dtype": SCORE_DTYPE,
           "platform": jax.devices()[0].platform}
    try:
        table = hlo_stats_table(OUT)
        rec["top_ops_by_self_time"] = top_ops(table)
        rec["category_totals"] = category_totals(table)
    except Exception as e:
        rec["hlo_stats_error"] = f"{type(e).__name__}: {str(e)[:200]}"
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
