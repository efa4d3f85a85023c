"""Causal grouped-query attention: the Mosaic flash kernels' tile sweep
against the einsum form, at the token benchmark cell's shape (1 sequence of
8,192 tokens, 4 query heads to 1 key/value head of 128, bfloat16).

For every tile size the forward call alone and forward + reverse
(``jax.grad`` of a sum of squares) are timed on the chip, fused and two-sweep
reverse schedules both, and each form's output and gradients are held
against the einsum form's.  One JSON line per form on stdout, all of them in
``chiprun_out/causal_attn_sweep.json``; PERF.md has the readings that chose
``_CAUSAL_TILE`` and kept ``_BWD_BLOCK``.  ``JAX_PLATFORMS=cpu`` runs a
downsized flow check through the pallas interpreter.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from distributedpytorch_tpu.backend_health import (  # noqa: E402
    enable_compile_cache,
    require_accelerator,
)

CPU_SMOKE = require_accelerator("scripts/causal_attn_sweep.py") == "cpu"
enable_compile_cache()

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributedpytorch_tpu.ops import pallas_attention as pa  # noqa: E402
from distributedpytorch_tpu.ops.attention import causal_attention  # noqa: E402

SEQ, Q_HEADS, KV_HEADS, HEAD_DIM = (256, 4, 1, 16) if CPU_SMOKE \
    else (8192, 4, 1, 128)
BLOCKS = [128] if CPU_SMOKE else [256, 512, 1024]
#: forward tiles (queries, keys) tried beside the square ones, at the
#: reverse pass's own tile
TALL_AND_WIDE = [(256, 128)] if CPU_SMOKE else [
    (1024, 512), (512, 1024), (2048, 512), (2048, 1024), (1024, 2048)]
STEPS, WARMUP = (1, 1) if CPU_SMOKE else (20, 3)


def _time_ms(fn, *args) -> float:
    for _ in range(WARMUP):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / STEPS


def _rel(got, want) -> float:
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _measure(attend, q, k, v, want):
    """Forward and forward + reverse milliseconds of ``attend``, and how far
    its output and gradients lie from ``want`` (the einsum form's)."""
    def loss(q_, k_, v_):
        return (attend(q_, k_, v_).astype(jnp.float32) ** 2).sum()

    fwd = jax.jit(attend)
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    rec = {"fwd_ms": _time_ms(fwd, q, k, v),
           "fwd_bwd_ms": _time_ms(grad, q, k, v)}
    if want is not None:
        rec["rel_err"] = [_rel(a, b) for a, b in
                          zip((fwd(q, k, v), *grad(q, k, v)), want)]
    return rec, (fwd(q, k, v), *grad(q, k, v))


def main() -> int:
    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(0, 1, (1, SEQ, Q_HEADS, HEAD_DIM)), jnp.bfloat16)
    k, v = (jnp.asarray(r.normal(0, 1, (1, SEQ, KV_HEADS, HEAD_DIM)),
                        jnp.bfloat16) for _ in range(2))
    plan = pa._bwd_plan
    records = []
    rec, want = _measure(causal_attention, q, k, v, None)
    records.append({"form": "einsum", **rec})
    square = [((f, f), b, fused) for f in BLOCKS for b in BLOCKS
              for fused in (True, False)
              if f == b or fused]  # the second schedule once per tile
    for fwd_tile, bwd_block, fused in square + [
            (t, None, True) for t in TALL_AND_WIDE]:
        pa._CAUSAL_TILE = fwd_tile
        pa._bwd_plan = plan if bwd_block is None else (
            lambda n, ck, _b=bwd_block, _f=fused: (_b, _f))
        attend = lambda *a: pa.flash_causal_attention(  # noqa: E731
            *a, interpret=CPU_SMOKE)
        rec, _ = _measure(attend, q, k, v, want)
        records.append({"form": "flash", "fwd_tile": fwd_tile,
                        "bwd_block": bwd_block or plan(SEQ, HEAD_DIM)[0],
                        "fused": fused, **rec})
        print(json.dumps(records[-1]), flush=True)
    pa._bwd_plan = plan
    dev = jax.devices()[0]
    doc = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "shape": {"seq": SEQ, "q_heads": Q_HEADS, "kv_heads": KV_HEADS,
                     "head_dim": HEAD_DIM}, "records": records}
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "causal_attn_sweep.json"), "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(records[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
