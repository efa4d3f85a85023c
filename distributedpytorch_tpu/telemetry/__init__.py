"""Unified telemetry: registry, spans, goodput/MFU, traces, Prometheus.

One process-wide surface for "what is this process doing":

* :mod:`registry`   — thread-safe counters/gauges/histograms
  (:func:`get_registry` is the process singleton);
* :mod:`spans`      — nested host spans, mirrored into XPlane device
  traces via ``jax.profiler.TraceAnnotation`` while a capture records;
* :mod:`goodput`    — wall-clock attribution ({step, compile,
  checkpoint, eval, input_wait, idle}) + MFU estimation with the
  device-kind peak-FLOPs table;
* :mod:`feed`       — the input feed's two pairs of counters (batches
  handed out and found built, fetches and found placed) and the
  ``input/*`` spans of the loader's producer and the placement thread;
* :mod:`prometheus` — text exposition for ``GET /metrics``;
* :mod:`trace`      — on-demand bounded ``jax.profiler`` capture
  (SIGUSR2 / ``POST /debug/trace``) without restarting the process;
* :mod:`scopes`     — the join of a traced device op and the part of the
  step it belongs to (backbone / head / loss / optimizer / kernel, forward
  or backward), written beside every capture as ``scope_summary.json``;
* :mod:`lowering`   — process-wide trace/lower/compile cache shared by
  the MFU estimator and the IR auditor (``analysis.ir``), so each hot
  program is lowered exactly once;
* :mod:`events`     — the flight recorder: one crash-safe, append-only
  run-event log (``run_dir/events/<host>.<pid>.jsonl``) every subsystem
  publishes into without changing its own ledger;
* :mod:`timeline`   — merges a run dir's event files across process
  generations and hosts into one causally-ordered timeline with typed
  episodes (divergence→rollback→replay, preempt→resume, …);
* :mod:`doctor`     — ``dptpu-doctor``: the diagnosis CLI over the
  timeline (goodput breakdown, episode recovery times, anomaly findings
  with the exact config-knob remedy).

Every future perf PR reports into this layer; the train loop, the
checkpoint manager, the evaluator and the serve front are already wired.
"""

from . import (events, feed, goodput, lowering, prometheus, registry, scopes,
               spans, timeline, trace)
from .events import EventLog, events_block
from .timeline import Timeline, load_timeline
from .goodput import (
    BUCKETS,
    FeedWindow,
    GoodputAccountant,
    get_accountant,
    mfu_estimate,
    peak_flops_for,
)
from .lowering import LoweredProgram, lower_cached
from .prometheus import render_text
from .registry import MetricsRegistry, get_registry, is_enabled, set_enabled
from .spans import span
from .trace import TraceCapture

__all__ = [
    "BUCKETS", "EventLog", "FeedWindow", "GoodputAccountant",
    "LoweredProgram", "MetricsRegistry", "Timeline",
    "TraceCapture", "events", "events_block", "feed",
    "get_accountant", "get_registry",
    "goodput", "is_enabled", "load_timeline", "lower_cached", "lowering",
    "mfu_estimate",
    "peak_flops_for", "prometheus", "registry", "render_text",
    "scopes", "set_enabled", "span", "spans", "timeline", "trace",
]
