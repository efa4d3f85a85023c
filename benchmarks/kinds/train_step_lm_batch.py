"""Traffic kind ``train_step_lm_batch``: ``train_step_lm`` with the step's
batch made by the configuration's plain reference (``reference/<name>.py::
make_batch(key, cfg, sequences, seq_len)``) in the place of uniform ids — a
token cell whose objective reads more of the batch than ``{tokens}`` (a
block-diffusion loss: the noised copy and the loss weights, one draw of the
noise from ``--seed``, resident with the ids, so that the plain reference
follows the very batch the program trains on and the step that the cell
compiles holds no draw).  It names nothing of one architecture.

Everything else — the program, the window, the counters, the reference's
runner and ``correct`` — is ``kinds/train_step_lm.py``'s, taken through
``harness.load_module`` with its one function ``make_inputs`` replaced:
this file holds no loop.  ``tools/control_lm.py`` reads ``cell_layout``,
``reference_runner`` and ``counter_numbers`` from whichever kind the cell's
traffic file names (``tools/compile_check_lm.py``: ``reference_of``,
``make_inputs``, ``build_program``), so their readings for such a cell come
from here.  The
traffic file is ``train_step_lm``'s."""

from __future__ import annotations

import functools

import harness


def make_inputs(lo, hi, cfg: dict, sequences: int, seq_len: int, ref):
    """``(params, state_key, batch)`` from the two words of ``--seed``."""
    import jax

    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
    kw, kx, kr = jax.random.split(key, 3)
    return (ref.make_weights(kw, cfg), kr,
            ref.make_batch(kx, cfg, sequences, seq_len))


@functools.lru_cache(maxsize=None)
def _lm_of(bench_dir: str):
    lm = harness.load_module(bench_dir, "kinds", "train_step_lm")
    lm.make_inputs = make_inputs
    return lm


def _lm(ctx):
    """A ``train_step_lm`` of its own (``load_module`` executes the file
    anew), whose inputs are made here."""
    return _lm_of(ctx.bench_dir)


def reference_of(ctx):
    return _lm(ctx).reference_of(ctx)


def build_program(ctx, mesh, n_chips):
    return _lm(ctx).build_program(ctx, mesh, n_chips)


def cell_layout(ctx, devices):
    return _lm(ctx).cell_layout(ctx, devices)


def reference_runner(ctx, shardings, **variant):
    return _lm(ctx).reference_runner(ctx, shardings, **variant)


def counter_numbers(ctx, counters: dict) -> dict:
    return _lm(ctx).counter_numbers(ctx, counters)


def run(ctx) -> dict:
    return _lm(ctx).run(ctx)
