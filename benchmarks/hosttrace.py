"""From a profiler trace of a whole epoch (``.xplane.pb``) to what the
host-side readers use: the device's ops line as ``xtrace.py`` has it, and the
host plane kept PER THREAD, ``[name, start_ns, end_ns, line]``, cut down to
the spans the program itself put there (``host_spans.json``: the benchmark's
own list of their names).

A device gap is the loop's to explain: it is named by the spans of the thread
that dispatched the train steps (the line that holds the step annotations),
never by a feed worker's span that happened to run across it.  As in
``xtrace.py`` there are two stages, so that the arithmetic can be checked on
a small recorded trace kept as JSON: :func:`extract` reads the file, the rest
works on plain lists."""

from __future__ import annotations

import glob
import os

import xtrace

NO_SPAN = "no program span"


def extract(trace_dir: str, layout: dict, spans: dict) -> dict:
    """``{"devices": {plane: {"ops", "modules"}}, "host": [[name, s, e,
    line], ...]}`` from the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    prefixes = tuple(spans["prefixes"])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(layout["device_plane_prefix"]):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {layout["ops_line"]: "ops",
                       layout["modules_line"]: "modules"}.get(line.name)
                if key:
                    dev[key] = [[xtrace.short_name(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns]
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == layout["host_plane"]:
            for i, line in enumerate(plane.lines):
                out["host"].extend(
                    [e.name, e.start_ns, e.start_ns + e.duration_ns, i]
                    for e in line.events
                    if e.name == spans["step"] or e.name.startswith(prefixes))
    return out


def loop_spans(host: list, step_name: str) -> list:
    """The spans on the thread that holds the step annotations."""
    lines = {h[3] for h in host if h[0] == step_name}
    return [h for h in host if h[3] in lines]


def named_gaps(ops: list, spans: list, t0: float, t1: float) -> list:
    """``[start, end, innermost, covering]`` of every interval of ``[t0,
    t1]`` in which no device op runs (before the first op and after the last
    too): the name of the shortest of ``spans`` that covers its middle
    (:data:`NO_SPAN` where none does) and the set of all names that cover
    it.  One sweep in time order: an epoch holds a gap between most ops."""
    edges = [[t0, min(o[1] for o in ops)], [max(o[2] for o in ops), t1]]
    todo = sorted(spans, key=lambda h: h[1])
    i, active, out = 0, [], []
    for s, e in sorted(xtrace.gaps(ops) + [g for g in edges if g[1] > g[0]]):
        mid = (s + e) / 2
        while i < len(todo) and todo[i][1] <= mid:
            active.append(todo[i])
            i += 1
        active = [h for h in active if h[2] >= mid]
        inner = min(active, key=lambda h: h[2] - h[1])[0] if active \
            else NO_SPAN
        out.append([s, e, inner, {h[0] for h in active}])
    return out


def summarize(raw: dict, step_name: str) -> dict:
    """The summary ``xtrace.summarize`` gives, over the whole traced epoch:
    from the loop's first span or the first device op, whichever is earlier,
    to its last span's end or the last op's, whichever is later.  The epoch
    starts with a wait for its first batch and ends with the wait for its
    save, and the chip idles through both: they are the epoch's, though no
    device op follows the last one.  Per device the ops and busy time;
    ``steps`` = the train steps the loop annotated; the loop thread's spans
    and every thread's beside them."""
    loop = loop_spans(raw["host"], step_name)
    steps = sum(1 for h in loop if h[0] == step_name)
    per_dev = []
    for name in sorted(raw["devices"]):
        ops = raw["devices"][name]["ops"]
        if not ops:
            continue
        t0 = min([o[1] for o in ops] + [h[1] for h in loop])
        t1 = max([o[2] for o in ops] + [h[2] for h in loop])
        per_dev.append({"plane": name, "t0": t0, "t1": t1, "ops": ops,
                        "steps": steps, "busy_ns": xtrace.union_ns(ops),
                        "span_ns": t1 - t0})
    if not per_dev:
        raise ValueError("the trace holds no device plane with events")
    return {
        "devices": per_dev, "steps": steps,
        "span_s": sum(d["span_ns"] for d in per_dev) / len(per_dev) / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_dev) / len(per_dev) / 1e9,
        "host": raw["host"], "loop": loop,
        "gaps": named_gaps(per_dev[0]["ops"], loop, per_dev[0]["t0"],
                           per_dev[0]["t1"]),
    }


def units(reading: dict, per: str, n_spans: int = 0) -> int:
    """What a reader divides by: ``step`` (train steps of the traced epoch),
    ``epoch`` (1), ``save`` (``checkpoint/save`` spans of the loop),
    ``image`` (instances the traced validation scored), ``span`` (the spans
    counted)."""
    s = reading["summary"]
    return {"step": s["steps"], "epoch": 1,
            "save": sum(1 for h in s["loop"] if h[0] == "checkpoint/save"),
            "image": reading.get("val_images", 0), "span": n_spans}[per]


def breakdown(summary: dict, top: int = 10) -> dict:
    """The first device's ten heaviest operations (self time), its ten
    longest idle gaps named by the innermost LOOP-THREAD span over their
    middle, and the idle seconds of all gaps summed per such name."""
    ops = sorted(xtrace.self_times(summary["devices"][0]["ops"]).items(),
                 key=lambda kv: -kv[1])[:top]
    by_span: dict = {}
    for s, e, inner, _ in summary["gaps"]:
        by_span[inner] = by_span.get(inner, 0.0) + (e - s) / 1e9
    longest = sorted(summary["gaps"], key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[g[2], (g[1] - g[0]) / 1e9] for g in longest],
            "idle_by_span": dict(sorted(by_span.items(),
                                        key=lambda kv: -kv[1]))}
