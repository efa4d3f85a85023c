"""The position-attention reverse pass's share of its roofline, by the
arithmetic of ``kernel_roofline`` (least time at the cell's shapes over the
device time per step of the events that match: every ``%pam_bwd…`` Mosaic
call of a step summed, whatever schedule the program chose).

The least time is that of the **algorithm's** work, whatever implements it:
five matmuls over all token pairs (S = q·kᵀ, dP = dO·vᵀ, dV = Pᵀ·dO, dK =
dSᵀ·q, dQ = dS·k), 2·N²·(3·ck + 2·cv)·rows FLOPs, a recompute of S and dP in
a second sweep not counted; q, k, v, out, dO read and dq, dk, dv written once
in bfloat16, 2·rows·N·(4·ck + 4·cv) bytes.  At the DANet cell's shapes (rows
8, N 4,096, ck 64, cv 512) 1.66 ms of FLOPs against 0.18 ms of bytes:
compute-bound.  A program whose reverse pass holds no such call (the parent
of the PR that brought the kernels) gives nothing to read."""

import harness


def pam_backward(cfg, rows_per_chip):
    n = (cfg["crop_size"] // cfg["output_stride"]) ** 2
    ck, cv = cfg["query_key_channels"], cfg["head_channels"]
    flops = 2.0 * n * n * (3 * ck + 2 * cv) * rows_per_chip
    bytes_ = 2.0 * rows_per_chip * n * (4 * ck + 4 * cv)
    return flops, bytes_


def read(ctx, reading, event_pattern, shape_fn):
    base = harness.load_module(ctx.bench_dir, "readers", "kernel_roofline")
    base.SHAPE_FNS.update(pam_backward=pam_backward)
    return base.read(ctx, reading, event_pattern, shape_fn)
