"""Position-attention crossover sweep: XLA einsum vs blocked vs Pallas flash
as the token count grows.

The flagship shape (512² crop, output-stride 8) gives 64² = 4096 tokens,
where the fully-fused XLA einsum won in f32 (2026-07-30).  Flash attention's
regime is larger token counts — 1024² crops at os=8, or os=4, give 16k-64k
tokens where the materialized N² score matrix first saturates HBM bandwidth
and then simply does not fit.  This sweep measures forward+backward time per
implementation per token count on the real chip and prints one JSON line per
cell — the measured basis for ``model.pam_impl=auto``'s switch point.

PAM inner shapes follow models/danet.py: q/k project to C/8, v keeps C
(C=512 after the head's channel reduction), bf16 inputs.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: JAX_PLATFORMS=cpu asks for the downsized flow check by name
import jax  # noqa: E402

from distributedpytorch_tpu.backend_health import (  # noqa: E402
    enable_compile_cache,
    require_accelerator,
)

CPU_SMOKE = require_accelerator("scripts/pam_crossover.py") == "cpu"
enable_compile_cache()

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributedpytorch_tpu.ops.attention import (  # noqa: E402
    blocked_position_attention,
    position_attention,
)
from distributedpytorch_tpu.ops.pallas_attention import (  # noqa: E402
    flash_position_attention,
)
from distributedpytorch_tpu.utils.profiling import throughput  # noqa: E402

CK, CV = 64, 512  # danet.py PAM: q/k at C/8, v at C (C=512)
TOKENS = [64, 256] if CPU_SMOKE else [4096, 8192, 16384, 32768, 65536]
STEPS = 2 if CPU_SMOKE else 10
WARMUP = 1 if CPU_SMOKE else 2


def impls(n):
    out = {"einsum": lambda q, k, v: position_attention(q, k, v),
           "blocked1024": lambda q, k, v:
               blocked_position_attention(q, k, v, min(1024, n)),
           # the CPU flow check has no Mosaic: it asks for the interpreter
           "flash512": lambda q, k, v:
               flash_position_attention(q, k, v, min(512, n), min(512, n),
                                        interpret=CPU_SMOKE)}
    if not CPU_SMOKE:
        out["flash1024"] = lambda q, k, v: \
            flash_position_attention(q, k, v, min(1024, n), min(1024, n))
    return out


def bench_cell(name, fn, n):
    r = np.random.RandomState(0)
    dt = jnp.bfloat16 if not CPU_SMOKE else jnp.float32
    q = jnp.asarray(r.normal(size=(1, n, CK)), dt)
    k = jnp.asarray(r.normal(size=(1, n, CK)), dt)
    v = jnp.asarray(r.normal(size=(1, n, CV)), dt)

    @jax.jit
    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)
        l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return l, grads

    stats = throughput(lambda: fwd_bwd(q, k, v), steps=STEPS, warmup=WARMUP,
                       items_per_step=1)
    ms = 1000.0 / stats["items_per_sec"]
    return {"impl": name, "tokens": n, "fwd_bwd_ms": round(ms, 2)}


if __name__ == "__main__":
    for n in TOKENS:
        for name, fn in impls(n).items():
            try:
                rec = bench_cell(name, fn, n)
            except Exception as e:
                rec = {"impl": name, "tokens": n,
                       "error": f"{type(e).__name__}: {str(e)[:160]}"}
            print(json.dumps(rec), flush=True)
