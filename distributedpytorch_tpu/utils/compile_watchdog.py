"""Compile watchdog: count XLA compilations, fail on steady-state recompiles.

The runtime complement of the :mod:`analysis` (jaxlint) static rules: the
linter catches recompile *hazards* in the source; this context manager
catches the recompiles that actually happen.  A steady-state train step
that recompiles (shape drift from a ragged batch, a donation mismatch, a
Python branch on a tracer) costs seconds-to-minutes of XLA work per
occurrence and is invisible in wall-clock-only logging — three rounds of
this repo's perf work chased overheads that a compile counter
would have attributed instantly.

Built on ``jax.log_compiles()``: with it enabled, every in-memory jit-cache
miss logs ``Compiling <fn> with global shapes and types ...`` from the
lowering path — BEFORE the persistent compilation cache is consulted, so
the count is cache-state-independent (a persistent-cache hit is still a
retrace + relink the step loop should not be paying).

>>> with CompileWatchdog(match="step_fn", max_compiles=1) as wd:
...     for batch in batches:
...         state, loss = step(state, batch)
>>> wd.counts            # {"step_fn": 1}

``max_compiles`` arms the watchdog: leaving the block raises
:class:`RecompileError` if any single matching function compiled more than
that many times.  Without it the watchdog only counts.
"""

from __future__ import annotations

import logging
import re
from collections import Counter

import jax

#: the lowering-path log line both pjit and pmap emit per compilation
_COMPILE_RE = re.compile(r"Compiling ([^\s]+) with global shapes")


class RecompileError(AssertionError):
    """A watched function compiled more often than the declared budget."""


class _CountingHandler(logging.Handler):
    def __init__(self, watchdog: "CompileWatchdog"):
        super().__init__(level=logging.DEBUG)
        self._watchdog = watchdog

    def emit(self, record: logging.LogRecord) -> None:
        try:
            m = _COMPILE_RE.search(record.getMessage())
        except Exception:   # a foreign record whose args don't format
            return
        if m is not None:
            self._watchdog._record(m.group(1))


class CompileWatchdog:
    """Count XLA compilations per jitted-function name within a region.

    ``match``: substring filter on the jitted function's name — only
    matching compilations count (and only they can trip the budget), so a
    step-loop watchdog isn't tripped by unrelated one-off jits (jnp.zeros,
    metrics) compiling nearby.  ``max_compiles``: per-function budget
    enforced at block exit (a primary exception propagating out of the
    block takes precedence — the watchdog never masks it).

    ``mute_jax_logs=False`` keeps the ``jax`` logger propagating while the
    watchdog is active.  The default pause is right for a short test
    region (log_compiles' WARNING spam would flood the console), but a
    LONG-LIVED watchdog — the serve batcher holds one open for the
    service's lifetime — would otherwise silence every jax warning/error
    process-wide for as long as it runs.
    """

    def __init__(self, match: str | None = None,
                 max_compiles: int | None = None,
                 mute_jax_logs: bool = True):
        self.match = match
        self.max_compiles = max_compiles
        self.mute_jax_logs = mute_jax_logs
        self.counts: Counter[str] = Counter()
        self._handler: _CountingHandler | None = None
        self._log_ctx = None

    def _record(self, name: str) -> None:
        if self.match is None or self.match in name:
            self.counts[name] += 1

    @property
    def total(self) -> int:
        return sum(self.counts.values())

    def __enter__(self) -> "CompileWatchdog":
        self._handler = _CountingHandler(self)
        # the "Compiling ..." records come from jax._src.* child loggers;
        # one handler on the package root sees them all via propagation.
        # Propagation above "jax" is paused so log_compiles' WARNING spam
        # doesn't flood the console of every watched test.
        jax_logger = logging.getLogger("jax")
        jax_logger.addHandler(self._handler)
        self._prev_propagate = jax_logger.propagate
        if self.mute_jax_logs:
            jax_logger.propagate = False
        self._log_ctx = jax.log_compiles()
        self._log_ctx.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._log_ctx is not None:
            self._log_ctx.__exit__(exc_type, exc, tb)
            self._log_ctx = None
        if self._handler is not None:
            jax_logger = logging.getLogger("jax")
            jax_logger.removeHandler(self._handler)
            jax_logger.propagate = self._prev_propagate
            self._handler = None
        if exc_type is not None:
            return  # never mask the primary failure
        if self.max_compiles is not None:
            over = {name: n for name, n in self.counts.items()
                    if n > self.max_compiles}
            if over:
                detail = ", ".join(f"{k} x{v}" for k, v in over.items())
                raise RecompileError(
                    f"steady-state recompile: {detail} (budget "
                    f"{self.max_compiles} per function) — look for shape "
                    "drift in the batch, donation mismatches, or Python "
                    "control flow on tracers (run jaxlint)")
