"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers use.  Read with ``jax.profiler.ProfileData`` alone.

Two stages, so that the arithmetic can be checked on a small recorded trace
kept as JSON: :func:`extract` turns the file into plain lists of
``[name, start_ns, end_ns]``; everything else works on those lists.
"""

from __future__ import annotations

import glob
import os
import re


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def short_name(text: str) -> str:
    """An event of the ops line carries the whole HLO instruction
    (``%pam.1 = bf16[8,4096,512]{...} custom-call(...)``): keep the name and
    the opcode (``%pam.1 custom-call``), which is what a pattern can hold on
    to and what fits a ledger line."""
    lhs, sep, rhs = text.partition(" = ")
    if not sep:
        return text[:96]
    m = _OPCODE.search(" " + rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


def extract(trace_dir: str, layout: dict) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}``
    from the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(layout["device_plane_prefix"]):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {layout["ops_line"]: "ops",
                       layout["modules_line"]: "modules"}.get(line.name)
                if key:
                    dev[key] = [[short_name(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns]
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == layout["host_plane"]:
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.start_ns + e.duration_ns]
                    for e in line.events if e.duration_ns > 0)
    return out


def clip(events: list, t0: float, t1: float) -> list:
    """Events cut to ``[t0, t1]``; those wholly outside are dropped."""
    return [[n, max(s, t0), min(e, t1)] for n, s, e in events
            if e > t0 and s < t1]


def union_ns(events: list) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: list) -> list:
    """``[start, end]`` of every interval between the first event's start and
    the last one's end in which no event runs, longest first."""
    out, cur_e = [], None
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if cur_e is not None and s > cur_e:
            out.append([cur_e, s])
        cur_e = e if cur_e is None else max(cur_e, e)
    return sorted(out, key=lambda g: g[0] - g[1])


def self_times(events: list) -> dict:
    """Seconds per event name, counting each instant once for the innermost
    event that covers it (an op nested in a loop is not counted twice)."""
    total: dict = {}
    stack: list = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + own

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1]))):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return {k: v / 1e9 for k, v in total.items()}


def step_window(dev: dict, module_pattern: str) -> tuple[float, float, int]:
    """``(t0, t1, n)``: from the start of the first to the end of the last
    execution of the step program on this device, and how many there were."""
    rx = re.compile(module_pattern)
    runs = [m for m in dev["modules"] if rx.search(m[0])]
    if not runs:
        raise ValueError(f"no module matches {module_pattern!r}; the trace "
                         f"has {sorted({m[0] for m in dev['modules']})}")
    return min(r[1] for r in runs), max(r[2] for r in runs), len(runs)


def summarize(raw: dict, module_pattern: str) -> dict:
    """Per device: the span of the traced steps, the ops inside it, busy
    time; averaged over devices where a reader wants one number."""
    per_dev = []
    for name in sorted(raw["devices"]):
        dev = raw["devices"][name]
        if not dev["modules"] and not dev["ops"]:
            continue
        t0, t1, n = step_window(dev, module_pattern)
        ops = clip(dev["ops"], t0, t1)
        first = min((o[1] for o in ops), default=t0)
        last = max((o[2] for o in ops), default=t1)
        per_dev.append({"plane": name, "t0": first, "t1": last, "steps": n,
                        "ops": ops, "busy_ns": union_ns(ops),
                        "span_ns": last - first})
    if not per_dev:
        raise ValueError("the trace holds no device plane with events")
    return {
        "devices": per_dev,
        "steps": per_dev[0]["steps"],
        "span_s": sum(d["span_ns"] for d in per_dev) / len(per_dev) / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_dev) / len(per_dev) / 1e9,
        "host": raw["host"],
    }


def breakdown(summary: dict, top: int = 10) -> dict:
    """The ten device operations that took most (self) time on the first
    device, and its ten longest idle gaps, each named by the innermost host
    span that covers the gap's middle."""
    dev = summary["devices"][0]
    ops = sorted(self_times(dev["ops"]).items(), key=lambda kv: -kv[1])[:top]
    idle = []
    for s, e in gaps(dev["ops"])[:top]:
        mid = (s + e) / 2
        cover = [h for h in summary["host"] if h[1] <= mid <= h[2]]
        name = min(cover, key=lambda h: h[2] - h[1])[0] if cover \
            else "no host span"
        idle.append([name, (e - s) / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}
