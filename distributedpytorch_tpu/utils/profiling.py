"""Measurement helpers that are not the profiler.

The reference's only performance instrumentation was wall-clock epoch timing
with ``timeit.default_timer`` printed to stdout (reference
train_pascal.py:12,181,307-308) — no profiler, no per-step numbers
(SURVEY.md §5.1).  Device traces are :class:`telemetry.trace.TraceCapture`'s
(one capture path: ``profile_epoch``, SIGUSR2, ``POST /debug/trace``), and
what a trace means is :mod:`telemetry.scopes`'.  Here:

* :func:`throughput` — steady-state rate with dispatch fully pipelined
  (dispatch everything, synchronize once);
* :func:`percentile` — the nearest-rank rule the serve tail and the metrics
  registry share;
* :func:`device_memory_stats` — HBM in use / peak / limit of one device.
"""

from __future__ import annotations

import math
import time

import jax


def throughput(step_fn, steps: int, warmup: int = 2,
               items_per_step: int | None = None) -> dict:
    """Steady-state throughput of ``step_fn() -> outputs``.

    Dispatches all ``steps`` calls and synchronizes ONCE on the final
    output — measuring device throughput with async dispatch fully
    pipelined.  This is the right shape for benchmarks: dispatch is
    asynchronous, so a host sync after every step stalls the device while
    the host catches up (30 ms a step at DANet b8, PERF.md) and times
    launch + round trip, not the rate the device sustains with its queue
    full.  Warmup steps
    (compile) are synchronized and excluded.

    Synchronization is ``jax.device_get`` of the final output: the value
    on the host is proof the whole chain behind it ran.  Make ``step_fn``
    return something whose value depends on everything you want timed
    (e.g. the loss AND a parameter leaf, so the optimizer update is
    provably complete).
    """
    out = None
    for _ in range(warmup):
        out = step_fn()
    jax.device_get(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step_fn()
    jax.device_get(out)
    dt = time.perf_counter() - t0
    res = {"steps": steps, "total_s": dt, "mean_s": dt / steps}
    if items_per_step:
        res["items_per_sec"] = items_per_step * steps / dt
    return res


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of ``values`` (q in [0, 100]).

    The latency-reporting convention: p99 is an actually-observed sample,
    never an interpolation between two samples (an interpolated tail value
    can be a latency no request ever experienced).  Shared by the serve
    metrics (serve/metrics.py) and the registry's histograms.
    """
    if not values:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q must be in [0, 100], got {q}")
    ordered = sorted(values)
    if q == 0.0:
        return ordered[0]
    rank = math.ceil(q / 100.0 * len(ordered))
    return ordered[min(len(ordered), rank) - 1]


def device_memory_stats(device=None) -> dict:
    """HBM usage of one device, normalized to a small stable dict.

    Returns ``{bytes_in_use, peak_bytes_in_use, bytes_limit}`` (zeros for
    backends that expose no stats, e.g. CPU) — the TPU-side answer to "does
    this config fit", which the reference left to CUDA OOMs and hand-tuned
    batch sizes (SURVEY.md §2.5 note on activation memory).
    """
    device = device or jax.devices()[0]
    stats = getattr(device, "memory_stats", lambda: None)() or {}
    return {
        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
        "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
        "bytes_limit": int(stats.get("bytes_limit", 0)),
    }
