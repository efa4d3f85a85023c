"""Host-side spans that nest — and appear in device traces by the same name.

A :class:`span` is a context manager that (1) times the enclosed host
region, (2) records the duration into the registry histogram
``span_seconds{span="<path>"}`` where ``<path>`` is the slash-joined
nesting (``fit/epoch/checkpoint``), and (3) while a capture records
(``telemetry.trace.capturing()``, the rule ``GoodputAccountant.account``
has) enters a ``jax.profiler.TraceAnnotation`` with the same path, so the
identical names show up inside XPlane device traces (xprof / tensorboard)
next to the ops they bracket.  One name, three views: registry percentiles,
Prometheus summary, device timeline.  With no capture a span costs its two
clock reads and its histogram observation and makes no JAX call.

The names a reader of a trace may hold on to are declared in
``telemetry/scopes.py`` (``PROGRAM_SPAN_PREFIXES``): a span path starts
with one of them.

Nesting is thread-local: concurrent threads (the val-overlap thread, the
serve worker) each carry their own span stack, so paths never interleave
across threads.
"""

from __future__ import annotations

import threading
import time

from . import trace as trace_lib
from .registry import MetricsRegistry, get_registry, is_enabled

_tls = threading.local()


class span:
    """Time a named, nestable host region; mirror it into a recording trace.

    >>> with span("epoch"):
    ...     with span("checkpoint"):   # records span="epoch/checkpoint"
    ...         ckpt.save(...)

    ``with span(...) as path`` binds the nested path.  With telemetry
    disabled (:func:`registry.set_enabled`) the whole span is a no-op.  A
    class and not a generator: the generator form costs about twice as much
    per entry (``goodput._Account``).
    """

    __slots__ = ("name", "_registry", "_path", "_t0", "_annotation")

    def __init__(self, name: str, registry: MetricsRegistry | None = None):
        self.name = name
        self._registry = registry
        self._path = None  # None: telemetry was off at entry

    def __enter__(self) -> str:
        if not is_enabled():
            return self.name
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        path = "/".join((*stack, self.name))
        # the profiler first: if it raises, the stack is as it was
        self._annotation = trace_lib.annotation(path)
        self._annotation.__enter__()
        stack.append(self.name)
        self._path = path
        self._t0 = time.perf_counter()
        return path

    def __exit__(self, *exc) -> bool:
        if self._path is None:
            return False
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        _tls.stack.pop()
        (self._registry or get_registry()).histogram(
            "span_seconds", "host-side span durations by nested path",
            labels={"span": self._path}).observe(dt)
        self._path = None
        return False
