"""Ops surface of the inference service: counters + latency percentiles.

A batcher that silently sheds or silently retraces is indistinguishable
from a healthy one at the API — the metrics are the only place the
difference shows.  Everything here is cheap host-side accounting sampled
on the request path (no device work), snapshot-read by the ``/stats`` and
``/healthz`` endpoints and by ``bench.py --serve``.

Storage lives in the process-wide telemetry registry
(:mod:`telemetry.registry`) under stable Prometheus names
(``serve_requests_total``, ``serve_batches_total{bucket=...}``,
``serve_latency_seconds``, ...), so ``GET /metrics`` exports the serve
counters and the train-side goodput gauges from ONE surface.  The
:class:`ServeMetrics` view stays per-service: each instance snapshots the
registry values at construction and reports deltas, preserving the
"monotonic since service start" contract even when several services (or
test cases) share one process — the registry keeps process-lifetime
totals, the service reports its own.

Latency is end-to-end request latency (submit -> mask handed back), the
number a client actually experiences: queue wait + batching wait + forward
+ paste-back.  Percentiles use the nearest-rank rule shared with the train
side (:func:`utils.profiling.percentile` — an observed sample, never an
interpolation)
over a bounded reservoir of the most recent samples, so a long-lived
service reports its CURRENT tail, not a mush of every request since boot.
"""

from __future__ import annotations

import collections
import threading

from ..telemetry.registry import MetricsRegistry, get_registry
from ..utils.profiling import percentile

#: counter slug -> help string (also fixes the exported metric set)
_COUNTERS = {
    "requests": "requests accepted into the queue",
    "completed": "requests answered with a mask",
    "failed": "requests answered with an error",
    "shed_queue_full": "requests rejected at the front door (queue full)",
    "shed_session_lane": "requests rejected because one session "
                         "overfilled its per-session lane",
    "shed_deadline": "requests dropped at drain time (deadline blown)",
    "batches": "compiled-forward dispatches",
    "retrace_failures": "steady-state recompiles the watchdog caught",
}


class ServeMetrics:
    """Per-service view over registry-backed counters + a bounded latency
    reservoir.

    Counters (monotonic since service start; process-lifetime totals live
    in the registry as ``serve_<name>_total``):

    * ``requests``        — accepted into the queue
    * ``completed``       — answered with a mask
    * ``failed``          — answered with an error (bad input, model error)
    * ``shed_queue_full`` — rejected at the front door (bounded queue full;
      backpressure instead of unbounded latency)
    * ``shed_deadline``   — dropped at drain time (deadline already blown;
      forwarding them would waste a lane on an answer nobody is waiting for)
    * ``batches``         — compiled-forward dispatches
    * ``retrace_failures``— steady-state recompiles the CompileWatchdog
      caught (any non-zero value means the bucket invariant broke)
    """

    def __init__(self, reservoir: int = 2048,
                 registry: MetricsRegistry | None = None):
        self._registry = registry or get_registry()
        self._lock = threading.Lock()
        self._c = {name: self._registry.counter(f"serve_{name}_total", help)
                   for name, help in _COUNTERS.items()}
        #: registry values at service start — the delta IS this service
        self._base = {name: c.value for name, c in self._c.items()}
        #: per-bucket dispatch counts {bucket_size: batches} (per-service;
        #: mirrored into serve_batches_total{bucket=...})
        self.batch_buckets: collections.Counter = collections.Counter()
        #: per-bucket real-lane totals (padding waste = bucket*batches - this)
        self.batch_lanes: collections.Counter = collections.Counter()
        self._hist = self._registry.histogram(
            "serve_latency_seconds",
            "end-to-end request latency (submit -> mask)",
            reservoir=reservoir)
        self._latencies = collections.deque(maxlen=reservoir)
        #: per-bucket registry children, cached — the bucket ladder is a
        #: small fixed set and the dispatch path must not pay two
        #: registry get-or-create lookups per batch
        self._bucket_children: dict[int, tuple] = {}

    def __getattr__(self, name: str) -> int:
        # counter reads (metrics.requests, .shed_deadline, ...) — delta
        # against the service-start baseline.  __getattr__ only fires for
        # names not found normally, so real attributes stay fast.
        c = self.__dict__.get("_c", {}).get(name)
        if c is None:
            raise AttributeError(name)
        return int(c.value - self.__dict__["_base"][name])

    def count(self, name: str, n: int = 1) -> None:
        self._c[name].inc(n)

    def observe_batch(self, bucket: int, lanes: int) -> None:
        children = self._bucket_children.get(bucket)
        if children is None:
            children = self._bucket_children[bucket] = (
                self._registry.counter(
                    "serve_batch_dispatches_total",
                    "dispatches per bucket size",
                    labels={"bucket": bucket}),
                self._registry.counter(
                    "serve_batch_lanes_total",
                    "real lanes per bucket size",
                    labels={"bucket": bucket}))
        self._c["batches"].inc()
        children[0].inc()
        children[1].inc(lanes)
        with self._lock:
            self.batch_buckets[bucket] += 1
            self.batch_lanes[bucket] += lanes

    def observe_latency(self, seconds: float) -> None:
        self._hist.observe(seconds)
        with self._lock:
            self._latencies.append(seconds)

    def snapshot(self) -> dict:
        """One snapshot dict for /stats, /healthz, and the serve bench.
        Counter reads are lock-free against the registry, so adjacent
        fields can tear by a request under concurrent load (e.g.
        ``batch_buckets`` momentarily summing one past ``batches``) —
        each value is individually exact, the set is not a barrier."""
        with self._lock:
            lat = list(self._latencies)
            buckets = dict(self.batch_buckets)
            lanes = dict(self.batch_lanes)
        out = {name: int(self._c[name].value - self._base[name])
               for name in _COUNTERS}
        out["batch_buckets"] = buckets
        out["batch_lanes"] = lanes
        if lat:
            out["latency_ms"] = {
                "p50": round(percentile(lat, 50.0) * 1e3, 3),
                "p99": round(percentile(lat, 99.0) * 1e3, 3),
                "max": round(max(lat) * 1e3, 3),
                "samples": len(lat),
            }
        dispatched = sum(b * c for b, c in out["batch_buckets"].items())
        if dispatched:
            out["pad_fraction"] = round(
                1.0 - sum(out["batch_lanes"].values()) / dispatched, 4)
        return out
