"""Host time in a span of the program, from the traced epoch's host plane, in
milliseconds per unit of work.

``span``: the span's name.  ``thread``: ``loop`` counts it on the thread that
dispatched the train steps, ``any`` on every thread.  ``per``: what the total
is divided by — ``step`` (train steps of the traced epoch), ``epoch`` (1),
``save`` (``checkpoint/save`` spans of the loop), ``image`` (instances the
traced validation scored), ``span`` (the spans counted: a mean).  ``phase``
``train`` counts only spans that began before the last train step ended (the
train loader's batches and not the validation loader's).  A reading with no
per-thread host plane, or a trace without that span (a program that has no
such span), gives nothing to read.  On the loop's thread a span may sit
inside one of its own name (a save that waits out the one before it): time
there counts once."""

import hosttrace
import xtrace


def read(ctx, reading, span, thread="loop", per="step", phase="all"):
    s = reading["summary"]
    if "loop" not in s:
        return None
    found = [h for h in (s["loop"] if thread == "loop" else s["host"])
             if h[0] == span]
    if phase == "train":
        ends = [h[2] for h in s["loop"] if h[0] == reading["step_name"]]
        found = [h for h in found if ends and h[1] < max(ends)]
    n = hosttrace.units(reading, per, len(found))
    if not found or not n:
        return None
    total = xtrace.union_ns([h[:3] for h in found]) if thread == "loop" \
        else sum(h[2] - h[1] for h in found)
    return total / 1e6 / n
