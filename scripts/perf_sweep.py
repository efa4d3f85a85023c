"""Perf sweep on the real chip: bench.py's config across batch size and
PAM attention implementations.  Prints one JSON line per variant.

TPU-only: the variants are full-size DANet-R101 512px configs that would
take hours per step on CPU, so the sweep has no CPU smoke and exits when
there is no TPU.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from distributedpytorch_tpu.backend_health import (  # noqa: E402
    enable_compile_cache,
    require_accelerator,
)

if require_accelerator("scripts/perf_sweep.py") != "tpu":
    sys.exit("scripts/perf_sweep.py: the sweep is full-size DANet-R101 "
             "512px — TPU only")
enable_compile_cache()

import numpy as np
import optax

from distributedpytorch_tpu.models import build_model
from distributedpytorch_tpu.parallel import (
    create_train_state,
    make_mesh,
    make_train_step,
    shard_batch,
)
from distributedpytorch_tpu.utils.profiling import throughput

SIZE = 512


def run(batch: int, pam_impl: str, block: int | None, remat: bool,
        os_: int = 8, device_guidance: bool = False,
        score_dtype: str | None = None) -> float:
    mesh = make_mesh()
    n = mesh.devices.size
    model = build_model("danet", nclass=1, backbone="resnet101",
                        output_stride=os_, dtype="bfloat16",
                        pam_impl=pam_impl, pam_block_size=block, remat=remat,
                        pam_score_dtype=score_dtype)
    tx = optax.sgd(1e-3, momentum=0.9)
    r = np.random.RandomState(0)
    in_ch = 3 if device_guidance else 4
    host = {
        "concat": r.uniform(0, 255, (batch * n, SIZE, SIZE, in_ch)
                            ).astype(np.float32),
        "crop_gt": (r.uniform(size=(batch * n, SIZE, SIZE)) > 0.7
                    ).astype(np.float32),
    }
    augment = None
    if device_guidance:  # the fused 4th-channel synthesis (ops/guidance_device)
        from distributedpytorch_tpu.ops.guidance_device import (
            make_device_guidance,
        )
        augment = make_device_guidance()
    with mesh:
        state = create_train_state(jax.random.PRNGKey(0), model, tx,
                                   (1, SIZE, SIZE, 4), mesh=mesh)
        step = make_train_step(model, tx, mesh=mesh, augment=augment)
        b = shard_batch(mesh, host)
        box = [state]

        def one():
            box[0], loss = step(box[0], b)
            return loss, jax.tree.leaves(box[0].params)[0]

        stats = throughput(one, steps=20, warmup=3, items_per_step=batch * n)
    return stats["items_per_sec"] / n


if __name__ == "__main__":
    variants = [
        dict(batch=8, pam_impl="einsum", block=None, remat=False),
        dict(batch=16, pam_impl="einsum", block=None, remat=False),
        dict(batch=8, pam_impl="flash", block=512, remat=False),
        dict(batch=16, pam_impl="flash", block=512, remat=False),
        dict(batch=32, pam_impl="einsum", block=None, remat=False),
        # online-softmax blocked einsum (no N x N scores materialized) and
        # alternate flash tiles — 2026-07-30 sweep data: full einsum b8 67.5
        # beat flash(512) 62.2; these probe whether other tilings close it.
        # (measured 2026-07-31: blocked 2048/1024 -> 62.5/63.9, flash
        # 1024/256 -> 62.3/63.2 vs in-run einsum 66.4 — they don't; at 4096
        # tokens the N x N scores fit HBM fine and XLA's fusion wins)
        dict(batch=8, pam_impl="einsum", block=2048, remat=False),
        dict(batch=8, pam_impl="einsum", block=1024, remat=False),
        dict(batch=8, pam_impl="flash", block=1024, remat=False),
        dict(batch=8, pam_impl="flash", block=256, remat=False),
        # the documented speed knob: os=16 quarters the head's token count
        # and the dilated-stage activation footprint (PAM scores 1024^2
        # instead of 4096^2)
        dict(batch=8, pam_impl="einsum", block=None, remat=False, os_=16),
        # on-device guidance synthesis fused into the step (measured
        # 2026-07-31: 65.4 vs 66.1 plain — ~1% for a 2.3x host-pipeline
        # rate; the host-side win is measured by scripts/bench_input.py)
        dict(batch=8, pam_impl="einsum", block=None, remat=False,
             device_guidance=True),
        # the roofline lever: bf16 score materialization
        # halves the PAM's N^2 HBM round trip, softmax math stays f32 —
        # variants 11/12 A/B this against rows 0/1
        dict(batch=8, pam_impl="einsum", block=None, remat=False,
             score_dtype="bfloat16"),
        dict(batch=16, pam_impl="einsum", block=None, remat=False,
             score_dtype="bfloat16"),
        # remat: per-block recompute (models/resnet.py nn.remat).  The r3
        # op profiles say the step runs at ~84% of peak HBM bandwidth with
        # 43% of MXU idle — remat trades exactly the abundant resource
        # (FLOPs) for the scarce one (activation HBM round trips between
        # forward and backward), so it can WIN on wall clock here, not
        # just on memory.  Variants 13-16 A/B it at b8/b16, alone and
        # stacked with bf16 scores; 17 probes whether b32 becomes
        # compilable/competitive once remat shrinks live activations.
        dict(batch=8, pam_impl="einsum", block=None, remat=True),
        dict(batch=16, pam_impl="einsum", block=None, remat=True),
        dict(batch=8, pam_impl="einsum", block=None, remat=True,
             score_dtype="bfloat16"),
        dict(batch=16, pam_impl="einsum", block=None, remat=True,
             score_dtype="bfloat16"),
        dict(batch=32, pam_impl="einsum", block=None, remat=True),
    ]
    sel = sys.argv[1:]
    for i, v in enumerate(variants):
        if sel and str(i) not in sel:
            continue
        # uniform output schema: every line carries "os" (the python-keyword-
        # dodging "os_" kwarg never leaks into the JSONL)
        rec = {k: val for k, val in v.items() if k != "os_"}
        rec["os"] = v.get("os_", 8)
        rec["device_guidance"] = v.get("device_guidance", False)
        rec["score_dtype"] = v.get("score_dtype")
        try:
            ips = run(**v)
            print(json.dumps({**rec, "imgs_per_sec_per_chip": round(ips, 2)}),
                  flush=True)
        except Exception as e:  # OOM etc.
            print(json.dumps({**rec, "error": str(e)[:200]}), flush=True)
