"""The block-diffusion attention kernels' share of their roofline, by the
arithmetic of ``kernel_roofline`` (least time at the cell's shapes over the
device time per step of the events that match: every ``%blockdiff_attn…``
Mosaic call of a step summed — each layer's forward call, a second forward
call where a block's recomputation runs one, and its reverse pass, whatever
schedule the program chose).

The least time is that of the **algorithm's** work, whatever implements it:
per attention layer and sequence of ``L`` tokens (``2L`` positions, clean ||
noised, blocks of ``b``) seven matmuls over the **allowed** pairs — forward
S = q·kᵀ and P·v; reverse S again, dP = dO·vᵀ, dV = Pᵀ·dO, dK = dSᵀ·q, dQ =
dS·k — 7 · 2 · (L² + L·b) · head_dim · q_heads FLOPs: the clean copy's
block-causal ``L (L + b) / 2`` pairs, a noised block's clean past ``L (L −
b) / 2`` and its own block ``L·b``.  The tiles that a program runs and the
pairs that it masks inside them are the implementation's and are not
counted, so the share can only read under 100%.  Bytes: q, out, dO and dq
once per query head and pass, k, v, dk, dv once per key/value head, bfloat16,
over the ``2L`` positions.  At the cell's shapes (4,096 tokens, 32 query
heads to 4 key/value heads of 128, block 4, 7 layers) 9.63e11 FLOPs a layer,
4.89 ms, against 0.55 ms of bytes: compute-bound.  A program that holds no
such call gives nothing to read."""

import harness


def allowed_pairs(seq_len: int, block: int) -> int:
    return seq_len * seq_len + seq_len * block


def block_diffusion_attention(cfg, rows_per_chip, seq_len):
    layers = cfg["num_hidden_layers"]
    d, hq, hkv = (cfg["head_dim"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    flops = layers * rows_per_chip * 7 * 2.0 * allowed_pairs(
        seq_len, cfg["block_length"]) * d * hq
    # forward reads q, k, v and writes out; reverse reads q, k, v, out, dO
    # and writes dq, dk, dv; every array spans the doubled sequence
    bytes_ = layers * rows_per_chip * 2.0 * (2 * seq_len) * d * (
        6 * hq + 6 * hkv)
    return flops, bytes_


def read(ctx, reading, event_pattern):
    base = harness.load_module(ctx.bench_dir, "readers", "kernel_roofline")
    base.SHAPE_FNS.update(
        block_diffusion_attention=lambda cfg, rows: block_diffusion_attention(
            cfg, rows, int(ctx.traffic["seq_len"])))
    return base.read(ctx, reading, event_pattern, "block_diffusion_attention")
