"""Goodput accounting and MFU estimation.

The question every perf PR must answer — "what fraction of wall-clock was
productive training, and if not, where did it go" — had no instrumented
answer: the trainer printed epoch seconds, the profiler needed a chip and
a human.  The :class:`GoodputAccountant` attributes the process's
wall-clock to a small closed set of buckets:

* ``step``       — productive train-step dispatch + readback
* ``compile``    — first dispatch of each compiled program (trace+XLA)
* ``checkpoint`` — save/restore/wait
* ``eval``       — validation epochs
* ``input_wait`` — the step loop blocked on the data pipeline (the
  silent killer FFCV (arxiv 2306.12517) and arxiv 2005.02130 document:
  input stalls routinely dominate training time unnoticed)
* ``idle``       — everything untracked (derived: total - tracked)

Attribution is EXCLUSIVE and nestable: entering an inner bucket pauses
the outer one's clock, so the buckets sum to tracked wall-clock by
construction (plus ``idle``, exactly total).  Per-thread stacks keep the
accounting correct on the val-overlap and checkpoint threads — with
genuinely concurrent work the per-bucket sums can legitimately exceed
wall-clock (two threads, one clock); single-threaded runs sum exactly.

MFU (model FLOPs utilization) composes the other half: model FLOPs/step
(XLA's own cost analysis) / step time / device peak FLOPs, with the peak
table keyed by device kind.  A device that is not in the table is an
error, not a default: a CPU has no MFU, and a TPU generation nobody
entered gets its row before it gets a number.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

from . import trace as trace_lib
from .registry import MetricsRegistry, get_registry
from .scopes import GOODPUT_PREFIX

#: the closed attribution set (order = reporting order)
BUCKETS = ("step", "compile", "checkpoint", "eval", "input_wait")

# Published per-chip peak dense-matmul throughput (bf16/f32 as trained
# here).  Sources: Google Cloud TPU system-architecture tables (public).
# Matched by substring of jax's device_kind.  Single source of truth —
# bench.py imports these.
PEAK_FLOPS_BY_KIND = {
    "v5 lite": 197e12,   # v5e: 197 TFLOP/s bf16
    "v5litepod": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,   # v6e (Trillium)
    "v6e": 918e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}

# Peak HBM bandwidth per chip (B/s), same public tables, keyed identically
# — the roofline's second axis must match the chip the FLOPs table matched.
PEAK_HBM_BY_KIND = {
    "v5 lite": 819e9,
    "v5litepod": 819e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v6 lite": 1640e9,
    "v6e": 1640e9,
    "v4": 1228e9,
    "v3": 900e9,
    "v2": 700e9,
}


def peak_flops_for(device_kind: str | None = None) -> tuple[float, str]:
    """(peak FLOP/s, matched table key) for a device kind (default: this
    process's first device).  Raises ``ValueError`` for a kind the table
    does not hold — callers that may run off-TPU check the platform
    first and report no MFU there."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    for sub, val in PEAK_FLOPS_BY_KIND.items():
        if sub in kind:
            return val, sub
    raise ValueError(
        f"no published peak for device kind {device_kind!r} — add its row "
        "to telemetry.goodput.PEAK_FLOPS_BY_KIND (and PEAK_HBM_BY_KIND) "
        "with its source")


def mfu_estimate(flops_per_step: float, step_time_s: float,
                 device_kind: str | None = None) -> dict:
    """MFU = achieved FLOP/s per device / peak FLOP/s per device.

    ``flops_per_step`` is the PER-DEVICE model FLOPs of one optimizer
    step (for a whole-mesh cost, divide by the device count first);
    ``step_time_s`` is the mean wall-clock of one step.
    """
    if flops_per_step <= 0 or step_time_s <= 0:
        raise ValueError(
            f"flops_per_step and step_time_s must be > 0, got "
            f"{flops_per_step}, {step_time_s}")
    peak, source = peak_flops_for(device_kind)
    achieved = flops_per_step / step_time_s
    return {
        "mfu": achieved / peak,
        "achieved_flops_per_sec": achieved,
        "peak_flops_per_device": peak,
        "peak_source": source,
        "flops_per_step": flops_per_step,
        "step_time_s": step_time_s,
    }


def xla_step_cost(fn, *args) -> dict:
    """XLA's cost model for a jitted callable at ``args`` (concrete arrays
    or ShapeDtypeStructs): ``{"flops", "bytes"}``, None when unavailable.
    Delegates to the process-wide :mod:`telemetry.lowering` cache, so the
    MFU estimator, bench.py's roofline and the IR auditor (analysis.ir)
    all lower each program exactly once.  Shared by bench.py's roofline
    and the trainer's MFU estimator."""
    from .lowering import lower_cached

    try:
        return dict(lower_cached(fn, *args).cost())
    except Exception:
        return {"flops": None, "bytes": None}


class _Account:
    """Class-based context manager for :meth:`GoodputAccountant.account` —
    the generator-based form costs ~2x more per entry, and this sits on
    the step loop's per-iteration path (the <=2%-overhead contract)."""

    __slots__ = ("_a", "bucket", "_annotation")

    def __init__(self, a: "GoodputAccountant", bucket: str):
        if bucket not in a._seconds:
            raise ValueError(f"unknown goodput bucket {bucket!r} "
                             f"(one of {BUCKETS})")
        self._a = a
        self.bucket = bucket
        self._annotation = None

    def __enter__(self) -> "_Account":
        a = self._a
        if trace_lib.capturing():
            # one clock for host and device: while a capture records, the
            # bucket is a span on the profiler's own timeline, so a device
            # idle gap under it has a name.  Off: the one read above.
            import jax

            self._annotation = jax.profiler.TraceAnnotation(
                GOODPUT_PREFIX + self.bucket)
            self._annotation.__enter__()
        stack = a._stack()
        now = time.perf_counter()
        if stack:  # pause the outer bucket's clock
            outer, outer_t0 = stack[-1]
            a._credit(outer, now - outer_t0)
            stack[-1] = (outer, None)
        stack.append((self.bucket, now))
        with a._lock:
            a._counts[self.bucket] += 1
        return self

    def __exit__(self, *exc) -> bool:
        a = self._a
        stack = a._stack()
        now = time.perf_counter()
        _, t0 = stack.pop()
        a._credit(self.bucket, now - t0)
        if stack:  # resume the outer bucket's clock
            stack[-1] = (stack[-1][0], now)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        return False


#: shared stateless no-op for disabled accountants
_NOOP = contextlib.nullcontext()


class GoodputAccountant:
    """Wall-clock attribution over :data:`BUCKETS`, exclusive + nested.

    >>> acct = GoodputAccountant()
    >>> with acct.account("eval"):
    ...     with acct.account("checkpoint"):   # pauses the eval clock
    ...         save()
    >>> acct.report()["buckets"]               # sums to total (with idle)

    ``reset(enabled=False)`` turns every ``account()`` into a shared
    no-op context — the disable path the <=2%-overhead contract is
    measured against.
    """

    def __init__(self, registry: MetricsRegistry | None = None,
                 enabled: bool = True):
        self._registry = registry
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.enabled = enabled
        self._t0 = time.perf_counter()
        self._seconds = {b: 0.0 for b in BUCKETS}
        self._counts = {b: 0 for b in BUCKETS}

    # ------------------------------------------------------------ lifecycle
    def reset(self, enabled: bool = True) -> None:
        """Zero the books and restart the wall clock (call at fit start)."""
        with self._lock:
            self.enabled = enabled
            self._t0 = time.perf_counter()
            self._seconds = {b: 0.0 for b in BUCKETS}
            self._counts = {b: 0 for b in BUCKETS}

    # ---------------------------------------------------------- attribution
    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _credit(self, bucket: str, seconds: float) -> None:
        with self._lock:
            self._seconds[bucket] += seconds

    def account(self, bucket: str):
        """Attribute the enclosed wall-clock to ``bucket`` (exclusive of
        any nested ``account`` regions, whose time goes to themselves).
        Returns a context manager; a shared no-op when disabled."""
        if not self.enabled:
            return _NOOP
        return _Account(self, bucket)

    def snapshot(self) -> dict:
        """Current per-bucket seconds, no derived fields, no publishing —
        the cheap read the feed governor's tick differences against its
        previous snapshot (one lock, one dict copy; safe at the log
        cadence)."""
        with self._lock:
            return dict(self._seconds)

    # ------------------------------------------------------------- reporting
    def report(self, publish: bool = True) -> dict:
        """Breakdown since the last reset.  ``idle`` is derived (total -
        tracked, clamped at 0), so in single-threaded use the buckets sum
        to ``total_s`` exactly; concurrent threads can push tracked time
        past wall-clock (two threads, one clock) — ``overlap_s`` exposes
        the excess instead of hiding it.

        ``publish`` mirrors the breakdown into registry gauges
        (``goodput_seconds{bucket=...}``, ``goodput_ratio``) so the serve
        front's ``/metrics`` exports train goodput too."""
        with self._lock:
            total = time.perf_counter() - self._t0
            seconds = dict(self._seconds)
            counts = dict(self._counts)
        tracked = sum(seconds.values())
        seconds["idle"] = max(0.0, total - tracked)
        rep = {
            "total_s": total,
            "buckets": seconds,
            "counts": counts,
            "goodput": (seconds["step"] / total) if total > 0 else 0.0,
            "overlap_s": max(0.0, tracked - total),
        }
        if publish:
            reg = self._registry or get_registry()
            for b, v in seconds.items():
                reg.gauge("goodput_seconds",
                          "wall-clock attributed per goodput bucket",
                          labels={"bucket": b}).set(v)
            reg.gauge("goodput_ratio",
                      "fraction of wall-clock in productive steps"
                      ).set(rep["goodput"])
        return rep


class FeedWindow:
    """Bounded ring of per-tick ``(busy_s, input_wait_s)`` samples — the
    windowed view of the input-stall signal the feed governor
    (data/governor.py) acts on.

    The source is the EXISTING exclusive goodput attribution: callers
    difference :meth:`GoodputAccountant.snapshot` between ticks (the log
    cadence the trainer already pays — no new host syncs) and push the
    deltas here.  ``busy_s`` is productive device-side wall-clock of the
    interval (step + compile); ``input_wait_s`` is host time blocked on
    the data pipeline.  The rolling stall fraction is
    ``sum(wait) / sum(wait + busy)`` over the ring — a per-step fraction
    would whipsaw on echo/multi-step configs where waits land on a
    subset of ticks.
    """

    def __init__(self, size: int = 16):
        if size < 1:
            raise ValueError(f"window size must be >= 1, got {size}")
        self._ring: collections.deque = collections.deque(maxlen=int(size))
        self.dropped = 0

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def size(self) -> int:
        return self._ring.maxlen

    def push(self, busy_s: float, input_wait_s: float) -> None:
        if busy_s < 0 or input_wait_s < 0:
            # clock skew / accountant reset between snapshots: drop, never
            # poison the window — but COUNT the drop (a silently shrinking
            # sample base looked exactly like a healthy feed), so /metrics
            # and the doctor can tell "no stalls" from "no samples"
            self.dropped += 1
            get_registry().counter(
                "telemetry_dropped_deltas_total",
                "goodput deltas dropped for being negative "
                "(accountant reset raced the feed window)").inc()
            return
        self._ring.append((float(busy_s), float(input_wait_s)))

    def reset(self) -> None:
        self._ring.clear()

    def totals(self) -> tuple[float, float]:
        """(busy_s, input_wait_s) summed over the ring."""
        busy = sum(b for b, _ in self._ring)
        wait = sum(w for _, w in self._ring)
        return busy, wait

    def stall_fraction(self) -> float | None:
        """Rolling input-stall fraction over the ring; None until a
        sample with nonzero tracked time lands."""
        busy, wait = self.totals()
        total = busy + wait
        if total <= 0:
            return None
        return wait / total


#: process-wide accountant (reset at each fit; checkpoint/eval wiring
#: reaches it from their own modules without plumbing)
_ACCOUNTANT = GoodputAccountant()


def get_accountant() -> GoodputAccountant:
    return _ACCOUNTANT
