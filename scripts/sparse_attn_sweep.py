"""Time the learned sparse attention's Mosaic calls alone, on the chip, at
the token cell's shape (1 sequence of 8,192, 32 query / 4 key-value heads of
128, an indexer of 16 heads of 64, top-2,048), and check each against its
XLA form at 2,048 tokens (where the einsum forms still fit).  Writes
``chiprun_out/sparse_attn_sweep.json``.

    chiprun -- python3 scripts/sparse_attn_sweep.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distributedpytorch_tpu.ops import attention as A  # noqa: E402
from distributedpytorch_tpu.ops import pallas_attention as pa  # noqa: E402


def timed(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3, out


def inputs(s, key):
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (1, s, 32, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, s, 4, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, s, 4, 128), jnp.bfloat16)
    qi = jax.random.normal(ks[3], (1, s, 16, 64))
    ki = jax.random.normal(ks[4], (1, s, 64))
    w = jax.random.normal(ks[5], (1, s, 16))
    return q, k, v, qi, ki, w, ks[6]


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("this measures the chip")
    out = {"device": dev.device_kind, "ms": {}, "check_2048": {}}
    # ---- agreement with the XLA forms at 2,048 tokens, top-512
    q, k, v, qi, ki, w, kg = inputs(2048, jax.random.PRNGKey(0))
    causal = jnp.tril(jnp.ones((2048, 2048), bool))
    s_k = jax.jit(pa.flash_indexer_scores)(qi, ki, w)
    s_x = jax.jit(A.indexer_scores)(qi, ki, w)
    out["check_2048"]["indexer_scores_max_abs"] = float(
        jnp.abs(jnp.where(causal, s_k - s_x, 0)).max())
    keep_x = jax.jit(lambda s: A.topk_keep(s, 512))(s_x)
    keep_k = jax.jit(lambda s: pa.flash_topk_keep(s, 512))(s_x) != 0
    keep_t = jax.jit(lambda s: A.threshold_keep(s, 512))(s_x)
    out["check_2048"]["keep_kernel_differs"] = int((keep_k != keep_x).sum())
    out["check_2048"]["keep_threshold_differs"] = int(
        (keep_t != keep_x).sum())
    out["check_2048"]["keep_from_kernel_scores_differs"] = int(
        ((jax.jit(lambda s: pa.flash_topk_keep(s, 512))(s_k) != 0)
         != keep_x).sum())
    keep8 = keep_x.astype(jnp.int8)
    g = jax.random.normal(kg, q.shape, jnp.bfloat16)

    def flash(q, k, v):
        return pa.flash_sparse_attention(q, k, v, keep8)[0]

    def einsum(q, k, v):
        return A.causal_attention(q, k, v, keep_x)

    o_k, o_x = jax.jit(flash)(q, k, v), jax.jit(einsum)(q, k, v)
    out["check_2048"]["out"] = rel(o_k, o_x)
    gk = jax.jit(jax.grad(lambda *a: (flash(*a).astype(jnp.float32)
                                      * g).sum(), (0, 1, 2)))(q, k, v)
    gx = jax.jit(jax.grad(lambda *a: (einsum(*a).astype(jnp.float32)
                                      * g).sum(), (0, 1, 2)))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gk, gx):
        out["check_2048"][name] = rel(a, b)
    lse = jax.jit(lambda *a: pa.flash_sparse_attention(*a)[1])(q, k, v,
                                                               keep8)
    out["check_2048"]["probs"] = rel(
        jax.jit(pa.flash_head_mean_probs)(q, k, keep8, lse),
        jax.jit(A.head_mean_probs)(q, k, keep_x))
    gs = jnp.where(causal, jax.random.normal(kg, (1, 2048, 2048)), 0)
    ik = jax.jit(jax.grad(lambda *a: (pa.flash_indexer_scores(*a)
                                      * gs).sum(), (0, 1, 2)))(qi, ki, w)
    ix = jax.jit(jax.grad(lambda *a: (A.indexer_scores(*a) * gs).sum(),
                          (0, 1, 2)))(qi, ki, w)
    for name, a, b in zip(("dqi", "dki", "dw"), ik, ix):
        out["check_2048"][name] = rel(a, b)
    print(json.dumps(out["check_2048"]), flush=True)

    # ---- times at the cell's shape
    q, k, v, qi, ki, w, kg = inputs(8192, jax.random.PRNGKey(1))
    ms = out["ms"]
    ms["indexer_scores"], scores = timed(jax.jit(pa.flash_indexer_scores),
                                         qi, ki, w)
    ms["topk_keep"], keep8 = timed(
        jax.jit(lambda s: pa.flash_topk_keep(s, 2048)), scores)
    ms["threshold_keep_xla"], keep_t = timed(
        jax.jit(lambda s: A.threshold_keep(s, 2048)), scores, n=2)
    out["keep_kernel_vs_xla_threshold_differs"] = int(
        ((keep8 != 0) != keep_t).sum())
    out["kept_share"] = float(keep8.astype(jnp.float32).sum()
                              / (8192 * 8193 / 2))
    ms["top_k_xla_512_rows"], _ = timed(
        jax.jit(lambda s: jax.lax.top_k(s, 2048)[1]), scores[0, -512:], n=2)
    fwd = jax.jit(lambda q, k, v, keep: pa.flash_sparse_attention(q, k, v,
                                                                  keep))
    ms["sparse_attn"], (o, lse) = timed(fwd, q, k, v, keep8)
    g = jax.random.normal(kg, q.shape, jnp.bfloat16)
    both = jax.jit(jax.grad(lambda q, k, v: (pa.flash_sparse_attention(
        q, k, v, keep8)[0].astype(jnp.float32) * g).sum(), (0, 1, 2)))
    ms["sparse_attn_fwd_and_bwd"], _ = timed(both, q, k, v)
    dense = jax.jit(jax.grad(lambda q, k, v: (pa.flash_causal_attention(
        q, k, v).astype(jnp.float32) * g).sum(), (0, 1, 2)))
    ms["causal_attn_fwd_and_bwd"], _ = timed(dense, q, k, v)
    ms["causal_attn"], _ = timed(jax.jit(pa.flash_causal_attention), q, k, v)
    ms["sparse_probs"], _ = timed(jax.jit(pa.flash_head_mean_probs), q, k,
                                  keep8, lse)
    gs = jax.random.normal(kg, (1, 8192, 8192))
    ibwd = jax.jit(jax.grad(lambda *a: (pa.flash_indexer_scores(*a)
                                        * gs).sum(), (0, 1, 2)))
    ms["indexer_scores_fwd_and_bwd"], _ = timed(ibwd, qi, ki, w)
    print(json.dumps(out), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/sparse_attn_sweep.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
