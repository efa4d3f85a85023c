"""Share of the device's busy time, in percent, that the scope table could
only attribute and not measure: ``mixed`` fusions (XLA fused ops of two
layers into one; the time goes to the heaviest) plus ops with no scope at
all (layer ``other``)."""

import harness


def read(ctx, reading):
    devices = harness.load_module(
        ctx.bench_dir, "readers", "scope_ms").joined(ctx, reading)
    if devices is None:
        return None
    shares = [(a["mixed_s"] + a["by_layer"].get("other", 0.0)) / a["busy_s"]
              for _, _, a in devices]
    return 100.0 * sum(shares) / len(shares)
