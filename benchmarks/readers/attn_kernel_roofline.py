"""The causal attention kernels' share of their roofline, by the arithmetic
of ``kernel_roofline`` (least time at the cell's shapes over the device time
per step of the events that match: every ``%causal_attn…`` Mosaic call of a
step summed — each layer's forward call, a second forward call where the
block's recomputation runs one, and its reverse pass, whatever schedule the
program chose).

The least time is that of the **algorithm's** work, whatever implements it:
per attention layer (the trunk's ``*`` and the prediction module's) seven
matmuls over the causal half of the token pairs — forward S = q·kᵀ and P·v;
reverse S again, dP = dO·vᵀ, dV = Pᵀ·dO, dK = dSᵀ·q, dQ = dS·k —
7 · 2 · (S²/2) · head_dim · q_heads · rows FLOPs.  A recomputation's forward
call and the masked half of the tiles on the diagonal are work of the
implementation and are not counted, so the share can only read under 100%.
Bytes: q, out, dO and dq once per query head and pass, k, v, dk, dv once per
key/value head, bfloat16.  At the token cell's shapes (1 row of 8,192 tokens,
4 query heads to 1 key/value head of 128, two layers) 4.81e11 FLOPs, 2.44 ms,
against 0.15 ms of bytes: compute-bound.  A program that holds no such call
(the einsum form: every parent of the PR that brought the kernels) gives
nothing to read."""

import harness


def causal_attention(cfg, rows_per_chip, seq_len):
    layers = cfg["hybrid_override_pattern"].count("*")
    if cfg.get("num_nextn_predict_layers", 0):
        layers += cfg["mtp_hybrid_override_pattern"].count("*")
    d, hq, hkv = (cfg["head_dim"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    flops = layers * rows_per_chip * 7 * 2.0 * (seq_len ** 2 / 2) * d * hq
    # forward reads q, k, v and writes out; reverse reads q, k, v, out, dO
    # and writes dq, dk, dv
    bytes_ = layers * rows_per_chip * 2.0 * seq_len * d * (6 * hq + 6 * hkv)
    return flops, bytes_


def read(ctx, reading, event_pattern, shape_fn):
    base = harness.load_module(ctx.bench_dir, "readers", "kernel_roofline")
    base.SHAPE_FNS.update(causal_attention=lambda cfg, rows: causal_attention(
        cfg, rows, int(ctx.traffic["seq_len"])))
    return base.read(ctx, reading, event_pattern, shape_fn)
