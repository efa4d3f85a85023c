"""A later PR's reader: device busy time per step, in ms."""


def read(ctx, reading, scale=1.0):
    s = reading["summary"]
    return scale * 1e3 * s["busy_s"] / s["steps"]
