"""On-demand, bounded ``jax.profiler`` trace capture — no restart needed.

Deciding BEFORE launch (``profile_epoch`` config) is one way in; the
interesting step regression always shows up mid-run.  :class:`TraceCapture`
is the one capture path for both: :meth:`TraceCapture.region` profiles a
whole region (``profile_epoch``), and a capture is armed from the outside
of a live process —
``SIGUSR2`` on the trainer, ``POST /debug/trace?steps=N`` on the serve
front — and the owning loop drives it with one cheap :meth:`tick` per
step/batch: the next tick after a request starts the trace, N ticks later
it stops, and the XPlane files land under the run dir
(``trace_on_demand/trace_NNN``) for tensorboard/xprof.

Every capture comes with its answer: where the owning loop has named the
step program that ran (:meth:`note_program`), ``scope_table.json`` (each
instruction of that program put down to a layer, a module path and
forward / backward / optimizer, :mod:`telemetry.scopes`) and
``scope_summary.json`` (device milliseconds per step by layer, by layer and
phase, the heaviest module paths, the longest idle gaps by host span) land
beside the XPlane files, and one ``trace_summary`` event goes into the
flight recorder.  While a capture is active :func:`capturing` is true:
``GoodputAccountant.account``, ``telemetry.span``, the input feed and the
trainer's dispatch then annotate the profiler's own timeline
(:func:`annotation`), and make no profiler call otherwise.

Safety properties, each deliberate:

* **Bounded.**  Steps are clamped to ``max_steps`` and a wall-clock
  ``max_seconds`` backstop closes a trace even if the step flow stalls
  (a serve instance that goes idle mid-capture must not profile
  forever — unbounded traces fill disks).
* **Signal-safe arming.**  :meth:`request` only assigns plain attributes
  (no locks): it is safe to call from a signal handler interrupting
  arbitrary code.  All real work happens in :meth:`tick` on the owning
  loop's thread.
* **One at a time.**  ``jax.profiler`` supports a single active trace
  per process; a request while one is active or armed is refused
  (returns None) rather than queued.
* **Never fatal.**  Profiler failures are counted
  (``trace_capture_failures_total``) and printed, never raised into the
  train loop.
"""

from __future__ import annotations

import contextlib
import json
import os
import signal
import threading
import time

from .registry import MetricsRegistry, get_registry

#: a capture is recording in this process (``jax.profiler`` allows one)
_capturing = False


def capturing() -> bool:
    """Whether a :class:`TraceCapture` is recording right now: the one read
    that every host annotation of the program (``goodput/<bucket>``, the
    train step, ``telemetry.span`` paths, the input feed's ``input/*``) is
    behind."""
    return _capturing


#: what :func:`annotation` hands back while nothing records
_NO_ANNOTATION = contextlib.nullcontext()


def annotation(name: str, **args):
    """A span on the profiler's own timeline while a capture records
    (``jax.profiler.TraceAnnotation(name, **args)``; ``args`` show beside
    the event), a shared no-op otherwise: off, this is the one module
    attribute read and no JAX call."""
    if not _capturing:
        return _NO_ANNOTATION
    import jax

    return jax.profiler.TraceAnnotation(name, **args)


class TraceCapture:
    """Armed-from-outside bounded device trace; driven by ``tick``.

    ``tick(n)`` means "n more steps are about to run": the owning loop
    calls it immediately before each dispatch (the trainer passes its
    steps-per-dispatch; the serve worker passes 1 per batch and 0 on
    idle polls so the time backstop still runs).
    """

    def __init__(self, log_dir: str, default_steps: int = 20,
                 max_steps: int = 200, max_seconds: float = 120.0,
                 registry: MetricsRegistry | None = None):
        self.log_dir = log_dir
        self.default_steps = default_steps
        self.max_steps = max_steps
        self.max_seconds = max_seconds
        self._registry = registry
        # armed-request slot: written by request() (possibly from a signal
        # handler), consumed by tick() on the owning thread.  The arm
        # itself is guarded by a NON-BLOCKING try-lock: concurrent HTTP
        # threads cannot both claim the slot, and a signal handler that
        # finds the lock held simply refuses (acquire(False) never blocks,
        # so it can never deadlock against interrupted code).
        self._arm_lock = threading.Lock()
        self._want = 0
        # active-capture state: owned exclusively by the tick()er's thread
        self._active = False
        self._remaining = 0   # None: a region, closed by its ``with``
        self._started = 0.0
        self._dir = ""
        self._captures = 0
        #: the step program that ran in this capture: (jitted, abstract
        #: args), named by the owning loop (note_program)
        self._program = None

    # ------------------------------------------------------------- arming
    @property
    def active(self) -> bool:
        return self._active

    def request(self, steps: int | None = None) -> str | None:
        """Arm a capture of ``steps`` (clamped to [1, max_steps]); the
        next STEP tick starts it.  Returns the directory the trace will
        land in, or None when one is already armed/active (refused, not
        queued).  Safe to call from signal handlers and HTTP threads."""
        if not self._arm_lock.acquire(blocking=False):
            return None  # concurrent arm in flight — refuse, never block
        try:
            if self._active or self._want:
                return None
            n = self.default_steps if steps is None else int(steps)
            target = os.path.join(self.log_dir,
                                  f"trace_{self._captures:03d}")
            # write the target BEFORE arming: tick() may fire between the
            # two assignments and must already see where to write
            self._pending_dir = target
            self._want = max(1, min(self.max_steps, n))
            return target
        finally:
            self._arm_lock.release()

    def install_signal(self, signum: int | None = None):
        """Install a SIGUSR2 (default) handler that arms a default
        capture; returns an uninstall callable.  Off the main thread
        (where ``signal.signal`` raises) this degrades to a no-op —
        ``request()`` still works programmatically."""
        if signum is None:
            signum = getattr(signal, "SIGUSR2", None)
            if signum is None:  # platform without SIGUSR2
                return lambda: None
        try:
            prev = signal.signal(signum, lambda s, f: self.request())
        except ValueError:
            return lambda: None
        return lambda: signal.signal(signum, prev)

    # ------------------------------------------------------------- driving
    def tick(self, n: int = 1) -> None:
        """Advance by ``n`` imminent steps (0 = just service the time
        backstop).  Called from exactly one thread — the step loop."""
        if self._active:
            if self._remaining is None:
                return  # a region: its ``with`` closes it
            if self._remaining <= 0 or \
                    time.perf_counter() - self._started > self.max_seconds:
                self._stop()
            else:
                self._remaining -= n
        elif self._want and n > 0:
            # start only on a REAL step tick: an idle tick(0) opening the
            # trace would burn the wall-clock backstop on idle time and
            # could close a serve capture having traced zero batches
            steps = self._want
            self._want = 0
            self._start(steps)
            self._remaining = steps - n
        # else: idle — one attribute read, the per-step cost when unarmed

    def close(self) -> None:
        """Stop any in-flight capture (call at fit end / service stop)."""
        if self._active:
            self._stop()

    @contextlib.contextmanager
    def region(self, log_dir: str):
        """Capture the enclosed region into ``log_dir``, however long it
        runs (``profile_epoch``).  Same start, stop and scope files as an
        armed capture; refused (a no-op) while another is active."""
        ours = not self._active
        if ours:
            self._pending_dir = log_dir
            self._start(None)
            self._remaining = None
        try:
            yield self
        finally:
            if ours and self._active:
                self._stop()

    def note_program(self, jitted, args) -> None:
        """Name the step program this capture is tracing, so that the
        capture can be attributed when it stops.  ``args`` may be concrete:
        only their shapes and dtypes are kept.  The owning loop calls it at
        each dispatch while :attr:`active`; the first program named stays."""
        if not self._active or self._program is not None:
            return
        import jax

        self._program = (jitted, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))

    # ------------------------------------------------------------ internals
    def _reg(self) -> MetricsRegistry:
        return self._registry or get_registry()

    def _failed(self, doing: str, e: Exception) -> None:
        """Counted and printed, never raised into the owning loop."""
        self._reg().counter("trace_capture_failures_total",
                            "on-demand trace captures that failed").inc()
        print(f"telemetry: trace capture failed to {doing}: {e!r}",
              flush=True)

    def _start(self, steps: int | None) -> None:
        import jax

        global _capturing
        self._dir = getattr(self, "_pending_dir", None) or os.path.join(
            self.log_dir, f"trace_{self._captures:03d}")
        self._pending_dir = None
        self._program = None
        try:
            os.makedirs(self._dir, exist_ok=True)
            opts = None
            if steps is None:
                # a region (a whole epoch) without the Python tracer: its
                # interpreter calls would swamp the trace, and the program's
                # own spans name the time; a bounded capture keeps its frames
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=opts)
        except Exception as e:  # another trace active, or profiler error
            self._failed("start", e)
            return
        self._active = True
        _capturing = True
        self._started = time.perf_counter()
        what = "a region's" if steps is None else f"{steps}-step"
        print(f"telemetry: capturing {what} trace -> {self._dir}",
              flush=True)

    def _stop(self) -> None:
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:
            self._failed("stop", e)
        else:
            self._reg().counter("trace_captures_total",
                                "on-demand trace captures completed").inc()
            print(f"telemetry: trace written -> {self._dir}", flush=True)
            self._write_scope_files()
        global _capturing
        _capturing = False
        self._active = False
        self._captures += 1

    def _write_scope_files(self) -> None:
        """``scope_table.json`` and ``scope_summary.json`` beside the trace,
        and the ``trace_summary`` event.  Nothing where no program was named
        (the serve front: the served forward has no table yet).  A failure
        is counted and printed, never raised."""
        if self._program is None:
            return
        from . import events, scopes

        t0 = time.perf_counter()
        try:
            jitted, args = self._program
            # never recompile under a live loop: a table from a cache entry
            # older than this tree's scopes is written marked ``stale``
            table = scopes.table_for(jitted, *args, allow_recompile=False)
            summary = scopes.summarize_capture(
                scopes.read_device_events(self._dir), table)
            summary["trace_dir"] = self._dir
            for name, doc in (("scope_table.json", table.to_json()),
                              ("scope_summary.json", summary)):
                with open(os.path.join(self._dir, name), "w") as f:
                    json.dump(doc, f)
            events.emit("telemetry", "trace_summary", payload={
                k: summary.get(k) for k in (
                    "trace_dir", "stale", "devices", "steps",
                    "busy_ms_per_step", "ms_per_step_by_layer",
                    "mixed_share", "unresolved_share", "idle_gaps")})
            print(f"telemetry: scope summary -> {self._dir}/"
                  f"scope_summary.json ({time.perf_counter() - t0:.1f} s)",
                  flush=True)
        except Exception as e:
            self._failed("write its scope summary", e)


#: serve-side convenience: arm via HTTP thread, driven by the worker loop
def query_steps(query: str, default: int | None = None) -> int | None:
    """Parse ``steps=N`` out of a raw query string (bad values -> default)."""
    from urllib.parse import parse_qs

    try:
        vals = parse_qs(query).get("steps")
        return int(vals[0]) if vals else default
    except (ValueError, TypeError):
        return default
