"""Device-timeline span of the traced steps (first device op's start to the
last one's end, mean over chips) per step, in milliseconds."""


def read(ctx, reading):
    s = reading["summary"]
    return 1e3 * s["span_s"] / s["steps"]
