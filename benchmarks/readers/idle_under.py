"""Device idle time of the traced epoch that falls under a span of the
program: the gaps between device ops whose middle a LOOP-THREAD span of that
name covers (the thread that dispatched the train steps; a feed worker's span
names no gap).  ``span: null`` = the gaps that no span of the program covers.

``per``: ``step`` | ``epoch`` | ``save`` | ``image`` give milliseconds per
that unit (as ``span_ms``); ``span_time`` gives per cent of that span's own
length (is the phase host-bound); ``idle`` gives per cent of all device idle
in the traced epoch (how much of the idle the span explains).  A reading with
no per-thread host plane, or a trace that holds no such span, gives nothing
to read."""

import hosttrace
import xtrace


def read(ctx, reading, span, per="step"):
    s = reading["summary"]
    if "gaps" not in s:
        return None
    if span is None:
        idle_ns = sum(e - b for b, e, inner, _ in s["gaps"]
                      if inner == hosttrace.NO_SPAN)
        if not s["loop"]:
            return None  # no span of the program at all: nothing to split by
    else:
        if not any(h[0] == span for h in s["loop"]):
            return None
        idle_ns = sum(e - b for b, e, _, cover in s["gaps"] if span in cover)
    if per == "idle":
        total = sum(e - b for b, e, _, _ in s["gaps"])
        return 100.0 * idle_ns / total if total else None
    if per == "span_time":
        total = xtrace.union_ns([h[:3] for h in s["loop"] if h[0] == span])
        return 100.0 * idle_ns / total if total else None
    n = hosttrace.units(reading, per)
    return idle_ns / 1e6 / n if n else None
