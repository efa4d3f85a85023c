"""The causal attention kernels' share of their roofline in a model whose
every layer holds one multi-head latent attention block (``num_hidden_layers``
blocks, queries and keys ``qk_head_dim`` wide, values ``v_head_dim``), by the
arithmetic of ``kernel_roofline`` (least time at the cell's shapes over the
device time per step of the events that match: every ``%causal_attn…``
Mosaic call of a step summed — each layer's forward call, a second forward
call where a block's recomputation runs one, and its reverse pass, whatever
schedule the program chose).

The least time is that of the **algorithm's** work, whatever implements it:
per layer seven matmuls over the causal half of the token pairs, 2 · (S²/2)
· heads · (4 · d_qk + 3 · d_v) FLOPs a row — forward S = q·kᵀ and P·v;
reverse S again, dP = dO·vᵀ, dV = Pᵀ·dO, dK = dSᵀ·q, dQ = dS·k; S, dK and dQ
at the width of q and k, P·v, dP and dV at the width of v.  A
recomputation's forward call and the masked half of the tiles on the
diagonal are work of the implementation and are not counted, so the share
can only read under 100%.  Bytes, bfloat16, once per head and pass: the
forward reads q, k and v and writes the output; the reverse reads q, k, v,
the output and dO and writes dq, dk and dv.  At the cell's shapes (1 row of
8,192 tokens, 32 heads, q and k 192 wide, v 128, 7 layers) 1.732e13 FLOPs,
87.9 ms a step, against 8.6 ms of bytes: compute-bound.  A program that
holds no such call gives nothing to read."""

import harness


def mla_causal_attention(cfg, rows_per_chip, seq_len):
    layers, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    dqk, dv = cfg["qk_head_dim"], cfg["v_head_dim"]
    per_row = layers * rows_per_chip * heads
    flops = per_row * 2.0 * (seq_len ** 2 / 2) * (4 * dqk + 3 * dv)
    # forward: q, k, v, out; reverse: q, k, v, out, dO, dq, dk, dv
    bytes_ = per_row * 2.0 * seq_len * (6 * dqk + 6 * dv)
    return flops, bytes_


def read(ctx, reading, event_pattern):
    base = harness.load_module(ctx.bench_dir, "readers", "kernel_roofline")
    base.SHAPE_FNS.update(
        mla_causal_attention=lambda cfg, rows: mla_causal_attention(
            cfg, rows, int(ctx.traffic["seq_len"])))
    return base.read(ctx, reading, event_pattern, "mla_causal_attention")
