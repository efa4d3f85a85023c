"""What every kind of run shares: finding a cell's files, the look for a
chip, the peaks table, the compile cache, memory, the traced window and the
result line."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time


def process_start(fallback: float) -> float:
    """Wall-clock time at which this process was started (``/proc``), so that
    ``setup_s`` counts the interpreter's own start-up and the imports."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        started_ago = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= started_ago < 3600:
            return time.time() - started_ago
    except (OSError, ValueError, IndexError):
        pass
    return fallback


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(bench_dir: str, folder: str, name: str):
    """``<bench_dir>/<folder>/<name>.py`` as a module, found by name."""
    path = os.path.join(bench_dir, folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{folder} {name!r}: no file {path}")
    mod_name = f"_bench_{folder}_{name}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks_for(bench_dir: str, device_kind: str) -> dict:
    table = load_json(os.path.join(bench_dir, "peaks.json"))["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in peaks.json "
            f"({sorted(table)}): add its published peaks with their source")
    return table[device_kind]


@dataclasses.dataclass
class Context:
    root: str
    bench_dir: str
    manifest: dict
    cell: dict
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    allow_cpu: bool
    t_start: float

    @classmethod
    def load(cls, root, args, allow_cpu, t_start):
        manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in manifest["workloads"]}
        if args.workload not in cells:
            raise SystemExit(f"unknown workload {args.workload!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
        cell = cells[args.workload]
        cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
        bench_dir = os.path.join(root, manifest["paths"][0])
        limits_file = os.path.join(bench_dir, "limits", cell["name"] + ".json")
        return cls(
            root=root, bench_dir=bench_dir, manifest=manifest, cell=cell,
            config=load_json(os.path.join(root, cfg_entry["file"])),
            traffic=load_json(os.path.join(bench_dir, "traffic",
                                           cell["traffic"] + ".json")),
            limits=load_json(limits_file)["limits"],
            seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            allow_cpu=allow_cpu, t_start=t_start)

    # ------------------------------------------------------------ devices
    def acquire_devices(self):
        """The cell's chips, or no run: never a quiet CPU."""
        import jax

        devs = jax.devices()
        chips = self.cell["chips"]
        if devs[0].platform != "tpu" and not self.allow_cpu:
            print(f"benchmark: JAX found no TPU (platform "
                  f"{devs[0].platform!r}); this measures the chip and runs "
                  "nowhere else", file=sys.stderr)
            raise SystemExit(3)
        if len(devs) < chips:
            print(f"benchmark: cell {self.cell['name']} needs {chips} chips, "
                  f"JAX found {len(devs)}", file=sys.stderr)
            raise SystemExit(3)
        self.peaks = None if self.allow_cpu and devs[0].platform != "tpu" \
            else peaks_for(self.bench_dir, devs[0].device_kind)
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        self.devices = devs[:chips]
        return self.devices

    def enable_cache(self):
        """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says,
        else at ``<checkout>/.jax_cache``; every program is kept, however
        short its compile, so that a second run compiles nothing."""
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(self.root, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    def memory_peak_bytes(self) -> tuple[int, dict]:
        """High-water mark of the fullest chip, program scratch included:
        ``peak_bytes_in_use`` (the allocator's arrays) plus
        ``peak_bytes_reserved`` (what loaded programs hold aside) where the
        runtime reports it."""
        best, detail = 0, {}
        for d in self.devices:
            ms = d.memory_stats() or {}
            v = int(ms.get("peak_bytes_in_use", 0)) + \
                int(ms.get("peak_bytes_reserved", 0))
            if v >= best:
                best, detail = v, ms
        return best, detail


class Tracer:
    """Profiler trace of a few steps, kept under ``TMPDIR`` and removed once
    read."""

    def __init__(self):
        self.dir = None

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        self.window_s = time.perf_counter() - self.t0

    def read(self, ctx: Context) -> dict:
        import xtrace as trace_lib

        try:
            return trace_lib.extract(
                self.dir, load_json(os.path.join(ctx.bench_dir,
                                                 "trace_layout.json")))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def per_layer_metrics(ctx: Context, reading: dict) -> dict:
    """Every per-layer metric of the manifest that this cell reports, from
    its own reader; a reader with nothing to read returns ``None`` and the
    metric is left out."""
    out = {}
    for m in ctx.manifest["per_layer"]:
        if "workloads" in m and ctx.cell["name"] not in m["workloads"]:
            continue
        spec = load_json(os.path.join(ctx.bench_dir, "metrics",
                                      m["name"] + ".json"))
        reader = load_module(ctx.bench_dir, "readers", spec["reader"])
        value = reader.read(ctx, reading, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def finish(ctx: Context, run: dict) -> dict:
    """The result line from what the kind measured.  ``run`` holds
    ``end_to_end`` values by name, counts, ``compared`` and, traced, a
    ``reading`` for the per-layer readers."""
    device = dict(ctx.device, memory_peak_bytes=run["memory_peak_bytes"])
    rehearsal = ctx.peaks is None
    if ctx.trace:
        reading = run["reading"]
        metrics = {} if rehearsal else per_layer_metrics(ctx, reading)
        device["busy_s"] = reading["summary"]["busy_s"]
        device["window_s"] = reading["summary"]["span_s"]
    else:
        metrics = {}
        for m in ctx.manifest["end_to_end"]:
            if "workloads" in m and ctx.cell["name"] not in m["workloads"]:
                continue
            if m["name"] in run["end_to_end"] and \
                    (not rehearsal or m["name"] == "setup_s"):
                metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": bool(run["correct"]), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if rehearsal:
        result["rehearsal"] = True
    if ctx.trace and "breakdown" in run["reading"]:
        result["breakdown"] = run["reading"]["breakdown"]
    for extra in ("reference_s", "setup_stages", "numbers"):
        if extra in run:
            result[extra] = run[extra]
    result["compared"] = run["compared"]
    return result
