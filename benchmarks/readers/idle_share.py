"""Share of the traced steps' span in which no operation ran on the device."""


def read(ctx, reading):
    s = reading["summary"]
    return 100.0 * (1.0 - s["busy_s"] / s["span_s"])
