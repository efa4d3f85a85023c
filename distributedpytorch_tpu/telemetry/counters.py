"""Counters a model hands back beside its loss.

A module ``sow``s a scalar into the :data:`COLLECTION` collection under a
name it has declared here, with the way values of that name combine: over
the layers that sow it, the micro-batches of an accumulated step, the steps
of one dispatch.  The train step (``parallel/step.py``) reduces whatever a
model sowed and hands ``{name: scalar}`` back beside the loss, whenever a
model sowed anything; the trainer logs it.  Neither knows a counter's name:
the layer that counts declares it (``parallel/moe.py``)."""

from __future__ import annotations

import jax.numpy as jnp
from flax.core import unfreeze
from flax.traverse_util import flatten_dict

COLLECTION = "counters"

_HOW = {"sum": jnp.sum, "max": jnp.max}
_declared: dict[str, str] = {}


def declare(name: str, how: str) -> str:
    """Register counter ``name`` as combining by ``how`` (sum | max);
    returns the name, for the declaring module to sow under."""
    if how not in _HOW:
        raise ValueError(f"counter {name!r}: how={how!r} (sum | max)")
    if _declared.setdefault(name, how) != how:
        raise ValueError(f"counter {name!r} is already declared as "
                         f"{_declared[name]!r}")
    return name


def combine(name: str, values):
    """All of ``values`` (stacked on their first axis) as one scalar."""
    if name not in _declared:
        raise KeyError(f"counter {name!r} was sown but never declared "
                       "(telemetry.counters.declare)")
    return _HOW[_declared[name]](jnp.asarray(values), axis=0)


def reduce_sown(sown) -> dict:
    """``{name: scalar}`` of a :data:`COLLECTION` collection: every value
    sown under a name, by whichever layers, combined."""
    by_name: dict = {}
    for path, values in flatten_dict(unfreeze(sown)).items():
        by_name.setdefault(path[-1], []).extend(values)  # sow keeps tuples
    return {name: combine(name, jnp.stack(vals))
            for name, vals in sorted(by_name.items())}
