"""The learned sparse attention's Mosaic calls, each against its roofline, by
the arithmetic of ``kernel_roofline`` (least time at the cell's shapes over
the device time per step of the events that match, every call of a step
summed: one per layer, and a second forward call where a block's
recomputation runs one).

The least time is that of the **algorithm's** work, whatever implements it,
per attention layer and sequence, with ``selected = sum_t min(t + 1, topk)``
and ``causal = S (S + 1) / 2`` token pairs (``reference/keye_lm.py::pairs``
has the same closed forms; this file imports nothing of it):

* ``sparse_attention`` (``%sparse_attn``, ``%sparse_attn_bwd_…``): seven
  matmuls over the **selected** pairs — forward S = q·kᵀ and P·v; reverse S
  again, dP, dV, dK, dQ — 7 · 2 · selected · head_dim · q_heads FLOPs.  A
  program that computes every causal tile and masks it does 2.3 times that
  at 8,192 positions and reads the share it earns.  Bytes: q, out, dO, dq
  once per query head and pass, k, v, dk, dv once per key/value head
  (bfloat16), the key set once per key/value group and pass (int8).
* ``sparse_probs`` (``%sparse_probs``): one matmul over the selected pairs;
  q per query head, k per key/value head, the key set read and the float32
  result written once over the causal pairs.
* ``indexer_scores`` (``%indexer_scores``, not ``…_bwd``): one product of
  ``index heads · index head_dim`` over the causal pairs, counted against the
  chip's bfloat16 peak although it runs in float32 (the table has no other),
  so the share reads low; the float32 scores written once.
* ``indexer_scores_bwd``: three such products (the scores again, dq, dk); the
  scores' cotangent read once.
* ``topk_keep``: no matmul; the float32 scores of the causal half read once
  and the int8 key set written once: bytes-bound.

A program that holds no such call gives nothing to read."""

import harness


def _sizes(cfg, seq_len):
    sa = cfg["sa_config"]
    k = min(sa["topk"], seq_len)
    selected = k * (k + 1) // 2 + (seq_len - k) * k
    causal = seq_len * (seq_len + 1) // 2
    return {"layers": cfg["num_hidden_layers"], "s": seq_len,
            "selected": selected, "causal": causal,
            "hd": cfg["head_dim"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"],
            "ih": sa["indexer_num_heads"], "ihd": sa["indexer_head_dim"]}


def sparse_attention(cfg, rows, seq_len):
    z = _sizes(cfg, seq_len)
    flops = 7 * 2.0 * z["selected"] * z["hd"] * z["hq"]
    bytes_ = 2.0 * z["s"] * z["hd"] * (6 * z["hq"] + 6 * z["hkv"]) \
        + 2.0 * z["hkv"] * z["causal"]
    return z["layers"] * rows * flops, z["layers"] * rows * bytes_


def sparse_probs(cfg, rows, seq_len):
    z = _sizes(cfg, seq_len)
    flops = 2.0 * z["selected"] * z["hd"] * z["hq"]
    bytes_ = 2.0 * z["s"] * z["hd"] * (z["hq"] + z["hkv"]) \
        + 4.0 * z["s"] * z["hq"] + 5.0 * z["causal"]
    return z["layers"] * rows * flops, z["layers"] * rows * bytes_


def indexer_scores(cfg, rows, seq_len):
    z = _sizes(cfg, seq_len)
    flops = 2.0 * z["causal"] * z["ih"] * z["ihd"]
    bytes_ = 4.0 * z["s"] * (z["ih"] * z["ihd"] + z["ihd"] + z["ih"]) \
        + 4.0 * z["causal"]
    return z["layers"] * rows * flops, z["layers"] * rows * bytes_


def indexer_scores_bwd(cfg, rows, seq_len):
    flops, bytes_ = indexer_scores(cfg, rows, seq_len)
    z = _sizes(cfg, seq_len)
    # the operands read and their gradients written, the cotangent read
    return 3 * flops, bytes_ + z["layers"] * rows * 4.0 * z["s"] * (
        z["ih"] * z["ihd"] + z["ihd"] + z["ih"])


def topk_keep(cfg, rows, seq_len):
    z = _sizes(cfg, seq_len)
    return 0.0, z["layers"] * rows * 5.0 * z["causal"]


SHAPE_FNS = {f.__name__: f for f in (sparse_attention, sparse_probs,
                                     indexer_scores, indexer_scores_bwd,
                                     topk_keep)}


def read(ctx, reading, event_pattern, shape_fn):
    if "sa_config" not in ctx.config:
        return None
    base = harness.load_module(ctx.bench_dir, "readers", "kernel_roofline")
    fn = SHAPE_FNS[shape_fn]
    base.SHAPE_FNS[shape_fn] = lambda cfg, rows: fn(
        cfg, rows, int(ctx.traffic["seq_len"]))
    return base.read(ctx, reading, event_pattern, shape_fn)
