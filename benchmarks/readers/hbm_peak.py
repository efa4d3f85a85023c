"""High-water mark of the fullest chip in GB, program scratch included (see
``harness.Context.memory_peak_bytes`` for the keys)."""


def read(ctx, reading):
    return reading["memory_peak_bytes"] / 1e9
