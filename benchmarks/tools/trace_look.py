"""Look at one trace by hand: run a traced run as ``run.py`` does, but before
the trace is reduced and removed, write what it holds (planes, lines, the
names that took most time, anything that looks like a kernel call) and the
extracted event lists to ``chiprun_out/``.  Same arguments as ``run.py``."""

import collections
import glob
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run  # noqa: E402

OUT = os.path.join(os.path.dirname(BENCH), "chiprun_out")


def look(trace_dir):
    import jax

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    doc = {"file_bytes": os.path.getsize(path), "planes": []}
    for plane in data.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            tot, cnt = collections.Counter(), collections.Counter()
            first = []
            for e in line.events:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
                if len(first) < 3:
                    first.append([e.name, e.start_ns, e.duration_ns,
                                  {k: str(v)[:80] for k, v in
                                   list(e.stats)[:6]}])
            p["lines"].append({
                "name": line.name, "events": sum(cnt.values()),
                "distinct": len(cnt), "first": first,
                "top": [[n, t / 1e6, cnt[n]] for n, t in tot.most_common(40)],
                "kernel_like": sorted(
                    [n, tot[n] / 1e6, cnt[n]] for n in cnt
                    if any(s in n.lower() for s in (
                        "custom", "pallas", "mosaic", "flash", "kernel",
                        "attention")))[:60],
            })
        doc["planes"].append(p)
    return doc


_read = harness.Tracer.read


def read_and_keep(self, ctx):
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"trace_look_{ctx.cell['name']}.json"),
              "w") as f:
        json.dump(look(self.dir), f, indent=1)
    raw = _read(self, ctx)
    with open(os.path.join(OUT, f"trace_events_{ctx.cell['name']}.json"),
              "w") as f:
        json.dump({"devices": raw["devices"],
                   "host": [h for h in raw["host"]
                            if h[2] - h[1] > 20000][:20000]}, f)
    return raw


if __name__ == "__main__":
    harness.Tracer.read = read_and_keep
    run.main()
