"""The two channel-attention kernels' shares of their rooflines, by the
arithmetic of ``kernel_roofline`` (least time at the cell's shapes over the
device time of the kernel's events per step).  ``x`` is the (B, N, C)
bfloat16 token map, N = (crop / output stride)^2, C = the head's channels.

* energy (gram + softmax in one call): 2*N*C^2*B FLOPs; x read once, the
  (B, C, C) float32 map written.  At the DANet cell's shapes (B 8, N 4,096,
  C 512) 0.087 ms of FLOPs against 0.051 ms of bytes: compute-bound.
* apply (map applied back over channels): 2*N*C^2*B FLOPs; x and the map
  read, the output written once: 0.087 ms of FLOPs against 0.092 ms of bytes:
  just memory-bound."""

import harness


def _sizes(cfg, rows_per_chip):
    n = (cfg["crop_size"] // cfg["output_stride"]) ** 2
    c = cfg["head_channels"]
    flops = 2.0 * n * c * c * rows_per_chip
    x_bytes = 2.0 * rows_per_chip * n * c
    map_bytes = 4.0 * rows_per_chip * c * c
    return flops, x_bytes, map_bytes


def cam_energy(cfg, rows_per_chip):
    flops, x_bytes, map_bytes = _sizes(cfg, rows_per_chip)
    return flops, x_bytes + map_bytes


def cam_apply(cfg, rows_per_chip):
    flops, x_bytes, map_bytes = _sizes(cfg, rows_per_chip)
    return flops, 2 * x_bytes + map_bytes


def read(ctx, reading, event_pattern, shape_fn):
    base = harness.load_module(ctx.bench_dir, "readers", "kernel_roofline")
    base.SHAPE_FNS.update(cam_energy=cam_energy, cam_apply=cam_apply)
    return base.read(ctx, reading, event_pattern, shape_fn)
