"""Shared XLA lowering/compile cache: one lower per program per process.

Two independent consumers need the lowered form of the hot compiled
programs — the MFU estimator (:func:`goodput.xla_step_cost` wants
``cost_analysis`` FLOPs of the exact step) and the IR auditor
(:mod:`analysis.ir` wants the ClosedJaxpr, the compiled HLO and
``memory_analysis``).  Each ``fn.lower(*args)`` is a full re-trace and —
absent the persistent compile cache — a re-compile, so letting every
consumer lower privately multiplies the single most expensive host
operation in the process.  This module is the one place a program gets
lowered: entries are keyed by ``(fn identity, abstract arg signature)``,
so a caller holding concrete arrays and a caller holding
``ShapeDtypeStruct`` templates of the same program share one entry.

The cache holds strong references to ``fn`` (which also keeps the ``id``
key stable) and to the traced/lowered/compiled stages; programs audited
or costed are the long-lived steps of the process, so this is bounded by
the number of distinct compiled programs — the same bound jax's own jit
cache already lives under.
"""

from __future__ import annotations

import threading


class LoweredProgram:
    """One program's trace → lower → compile pipeline, each stage computed
    once and memoized.  ``traced`` is None on jax versions without the
    AOT ``fn.trace`` API (everything downstream still works; only
    jaxpr-level auditing degrades)."""

    __slots__ = ("fn", "traced", "lowered", "_compiled", "_cost")

    def __init__(self, fn, traced, lowered):
        self.fn = fn
        self.traced = traced
        self.lowered = lowered
        self._compiled = None
        self._cost = None

    @property
    def compiled(self):
        if self._compiled is None:
            self._compiled = self.lowered.compile()
        return self._compiled

    def cost(self) -> dict:
        """XLA's cost model: ``{"flops", "bytes"}``, None when the backend
        has no cost model (same contract as the old goodput helper)."""
        if self._cost is None:
            try:
                cost = self.compiled.cost_analysis()
                self._cost = {
                    "flops": float(cost["flops"]),
                    "bytes": float(cost.get("bytes accessed", 0.0)) or None,
                }
            except Exception:
                self._cost = {"flops": None, "bytes": None}
        return self._cost


_LOCK = threading.Lock()
_CACHE: dict = {}


def _leaf_signature(leaf) -> tuple:
    """Abstract signature of one arg leaf: concrete jax/numpy arrays and
    ShapeDtypeStructs of the same shape/dtype hash identically, so the
    trainer's concrete-state lowering and the auditor's struct-only
    lowering share an entry.  ``weak_type`` is part of the signature —
    jax's own jit cache distinguishes it (promotion, and therefore the
    traced program, differs), so colliding the two would hand one
    caller the other's jaxpr."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype),
                bool(getattr(leaf, "weak_type", False)))
    return ("py", type(leaf).__name__, repr(leaf)[:64])


def program_key(fn, args: tuple) -> tuple:
    import jax

    leaves, treedef = jax.tree.flatten(args)
    return (id(fn), str(treedef), tuple(_leaf_signature(x) for x in leaves))


def lower_cached(fn, *args) -> LoweredProgram:
    """The (memoized) lowered form of ``fn`` at ``args`` (concrete arrays
    or ShapeDtypeStructs).  Raises whatever trace/lower raises — callers
    that must never fail (the MFU estimator) wrap it."""
    key = program_key(fn, args)
    with _LOCK:
        prog = _CACHE.get(key)
    if prog is not None:
        return prog
    if hasattr(fn, "trace"):  # AOT API: keeps the ClosedJaxpr + args_info
        traced = fn.trace(*args)
        lowered = traced.lower()
    else:
        traced = None
        lowered = fn.lower(*args)
    prog = LoweredProgram(fn, traced, lowered)
    with _LOCK:
        # a racing thread may have lowered the same program; keep the
        # first entry so every consumer shares one executable
        prog = _CACHE.setdefault(key, prog)
    return prog


def cache_info() -> dict:
    with _LOCK:
        return {"entries": len(_CACHE)}


def clear_cache() -> None:
    """Tests only: drop every cached stage (frees the executables)."""
    with _LOCK:
        _CACHE.clear()
