"""Tracing / profiling utilities.

The reference's only performance instrumentation was wall-clock epoch timing
with ``timeit.default_timer`` printed to stdout (reference
train_pascal.py:12,181,307-308) — no profiler, no NVTX, no per-step numbers
(SURVEY.md §5.1).  TPU-native replacements:

* :func:`trace` — context manager around ``jax.profiler`` writing a
  TensorBoard-loadable XPlane trace (op-level device timeline, HBM usage,
  fusion view) for any code region;
* :class:`StepTimer` — per-step *latency* timing (block on a representative
  output, read the clock, skip warmup).  Measures launch + sync round-trip,
  which is the right number for interactive latency but NOT for throughput:
  a per-step sync drains the dispatch pipeline every step, so for throughput
  always use :func:`throughput` instead;
* :func:`annotate` — named ``TraceAnnotation`` regions that show up inside
  the device trace (host-side markers).
"""

from __future__ import annotations

import contextlib
import math
import statistics
import time

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Profile the enclosed region into ``log_dir`` (XPlane format;
    `tensorboard --logdir` or xprof reads it)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region visible in profiler timelines."""
    return jax.profiler.TraceAnnotation(name)


def throughput(step_fn, steps: int, warmup: int = 2,
               items_per_step: int | None = None) -> dict:
    """Steady-state throughput of ``step_fn() -> outputs``.

    Dispatches all ``steps`` calls and synchronizes ONCE on the final
    output — measuring device throughput with async dispatch fully
    pipelined.  This is the right shape for benchmarks: dispatch is
    asynchronous, so a host sync after every step (``StepTimer``) stalls
    the device while the host catches up and times launch + round trip,
    not the rate the device sustains with its queue full.  Warmup steps
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
    can be a latency no request ever experienced).  Shared by
    :class:`StepTimer` and the serve metrics (serve/metrics.py).
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


class StepTimer:
    """Accumulates per-step wall times, async-dispatch-aware.

    >>> timer = StepTimer(warmup=2)
    >>> for batch in loader:
    ...     state, loss = step(state, batch)
    ...     timer.tick(loss)          # blocks on loss, records dt
    >>> timer.summary()               # {'mean_s': ..., 'p50_s': ..., ...}
    """

    def __init__(self, warmup: int = 2):
        self.warmup = warmup
        self._seen = 0
        self._last: float | None = None
        self.times: list[float] = []

    def tick(self, *outputs) -> float | None:
        """Record one step boundary; pass any step outputs to block on."""
        if outputs:
            jax.block_until_ready(outputs)
        now = time.perf_counter()
        dt = None
        if self._last is not None:
            self._seen += 1
            if self._seen > self.warmup:
                dt = now - self._last
                self.times.append(dt)
        self._last = now
        return dt

    def summary(self, items_per_step: int | None = None) -> dict:
        if not self.times:
            return {"steps": 0}
        out = {
            "steps": len(self.times),
            "mean_s": statistics.fmean(self.times),
            "p50_s": statistics.median(self.times),
            "p99_s": percentile(self.times, 99.0),
            "min_s": min(self.times),
            "max_s": max(self.times),
        }
        if items_per_step:
            out["items_per_sec"] = items_per_step / out["mean_s"]
        return out


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
