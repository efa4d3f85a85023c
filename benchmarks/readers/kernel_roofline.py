"""A kernel's share of its roofline: the least time the chip could take for
the call at the cell's shapes (the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s) over the device time of the kernel's events per step.
Returns nothing where no event matches, never 0."""

import re


def pam_forward(cfg, rows_per_chip):
    """Flash position attention, forward, bfloat16: QK^T and PV over all
    token pairs; q, k, v read once and the output written once."""
    n = (cfg["crop_size"] // cfg["output_stride"]) ** 2
    ck, cv = cfg["query_key_channels"], cfg["head_channels"]
    flops = 2.0 * n * n * (ck + cv) * rows_per_chip
    bytes_ = 2.0 * rows_per_chip * n * (2 * ck + 2 * cv)
    return flops, bytes_


SHAPE_FNS = {"pam_forward": pam_forward}


def read(ctx, reading, event_pattern, shape_fn):
    rx = re.compile(event_pattern)
    s = reading["summary"]
    per_dev = []
    for dev in s["devices"]:
        t = sum(e - b for name, b, e in dev["ops"] if rx.search(name))
        if t > 0:
            per_dev.append(t / 1e9 / dev["steps"])
    if not per_dev:
        return None
    flops, bytes_ = SHAPE_FNS[shape_fn](
        ctx.config, reading["images_per_step"] // reading["chips"])
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                bytes_ / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(per_dev) / len(per_dev))
