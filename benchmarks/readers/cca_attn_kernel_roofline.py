"""The causal attention kernels' share of their roofline in a model whose
every layer holds one attention block (compressed convolutional attention:
``num_hidden_layers`` blocks), by the arithmetic of ``kernel_roofline``
(least time at the cell's shapes over the device time per step of the
events that match: every ``%causal_attn…`` Mosaic call of a step summed —
each layer's forward call, a second forward call where a block's
recomputation runs one, and its reverse pass, whatever schedule the program
chose).  ``attn_kernel_roofline`` counts its layers from Nemotron's
``hybrid_override_pattern`` and cannot be pointed at such a model.

The least time is that of the **algorithm's** work, whatever implements it:
per attention layer seven matmuls over the causal half of the token pairs —
forward S = q·kᵀ and P·v; reverse S again, dP = dO·vᵀ, dV = Pᵀ·dO, dK = dSᵀ·q,
dQ = dS·k — 7 · 2 · (S²/2) · head_dim · q_heads · rows FLOPs; the heads are
the compressed latent's (the queries and keys that reach the kernels).  A
recomputation's forward call and the masked half of the tiles on the
diagonal are work of the implementation and are not counted, so the share
can only read under 100%.  Bytes: q, out, dO and dq once per query head and
pass, k, v, dk, dv once per key/value head, bfloat16.  At the cell's shapes
(1 row of 8,192 tokens, 8 query heads to 2 key/value heads of 128, 7 layers)
3.37e12 FLOPs, 17.1 ms a step, against 1.1 ms of bytes: compute-bound.  A
program that holds no such call gives nothing to read."""

import harness


def cca_causal_attention(cfg, rows_per_chip, seq_len):
    layers = cfg["num_hidden_layers"]
    d, hq, hkv = (cfg["head_dim"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    flops = layers * rows_per_chip * 7 * 2.0 * (seq_len ** 2 / 2) * d * hq
    # forward reads q, k, v and writes out; reverse reads q, k, v, out, dO
    # and writes dq, dk, dv
    bytes_ = layers * rows_per_chip * 2.0 * seq_len * d * (6 * hq + 6 * hkv)
    return flops, bytes_


def read(ctx, reading, event_pattern):
    base = harness.load_module(ctx.bench_dir, "readers", "kernel_roofline")
    base.SHAPE_FNS.update(
        cca_causal_attention=lambda cfg, rows: cca_causal_attention(
            cfg, rows, int(ctx.traffic["seq_len"])))
    return base.read(ctx, reading, event_pattern, "cca_causal_attention")
