"""Device self time per step, in milliseconds, of the ops that the program's
own scope table (``distributedpytorch_tpu.telemetry.scopes``) puts down to
the layers, phase or module path that the metric's file names.

The trace names an op by HLO instruction and the benchmark keeps nothing
else of an event, so the join comes from the program: the cell's step is
built once more as the kind builds it, on shapes alone, and
``scopes.table_for`` gives ``{instruction: (layer, path, phase, mixed,
opcode)}`` of the executable that the persistent cache has just been handed
by the run.  One table per process (kept on ``reading``), shared by every
metric that reads it.  A program without that module (the parent of the PR
that brought it) gives nothing to read; a table that is stale, or that does
not hold the ops of more than 1% of the busy time, raises: a wrong split is
worse than none."""

import functools
import sys

UNRESOLVED_LIMIT = 0.01


def build_table(ctx):
    """The scope table of the cell's step program, or ``None`` where the
    program has no ``telemetry.scopes`` or the cell's kind builds no step."""
    try:
        from distributedpytorch_tpu.telemetry import scopes
    except ImportError:
        return None
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributedpytorch_tpu.parallel.step import TrainState

    import harness
    import inputs

    kind = harness.load_module(ctx.bench_dir, "kinds", ctx.traffic["kind"])
    if not hasattr(kind, "cell_layout") or not hasattr(kind, "build_program"):
        return None  # a kind of traffic that drives no train step
    mesh, (repl, data), rows, _ = kind.cell_layout(ctx, ctx.devices)
    shapes = jax.eval_shape(
        functools.partial(inputs.make_inputs, cfg=ctx.config, rows=rows),
        np.uint32(0), np.uint32(0))

    def on(sharding, tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    params, stats, rng = on(repl, shapes[:3])
    with mesh:
        _, tx, make_step = kind.build_program(ctx, mesh, len(ctx.devices))
        state = TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
            params=params, batch_stats=stats,
            opt_state=on(repl, jax.eval_shape(tx.init, params)), rng=rng)
        # the run wrote the executable to the cache a minute ago; an entry
        # older than this tree's scopes is compiled once more, past the cache
        table = scopes.table_for(make_step(state), state,
                                 on(data, shapes[3]), allow_recompile=True)
    if table.stale:
        raise RuntimeError(f"scope table is stale: {table.differing}")
    print("scope_table", {"instructions": len(table.table),
                          "recompiled": table.recompiled}, file=sys.stderr)
    return table.table


def joined(ctx, reading):
    """Per device of the traced steps ``(steps, [(self_ns, scope)],
    attribution)``, or ``None``; the guard on what the table does not hold
    is applied here.  ``reading["scope_table"]`` stands in for the program's
    table where there is one (the benchmark's own tests)."""
    if "_scope_join" in reading:
        return reading["_scope_join"]
    table = reading.get("scope_table") or build_table(ctx)
    out = None
    if table is not None:
        from distributedpytorch_tpu.telemetry import scopes

        out = []
        for dev in reading["summary"]["devices"]:
            a = scopes.attribute(dev["ops"], table)
            if a["unresolved_s"] > UNRESOLVED_LIMIT * a["busy_s"]:
                raise RuntimeError(
                    "the scope table is not of the executable that ran: "
                    f"ops it does not hold took {a['unresolved_s']:.6f} s of "
                    f"{a['busy_s']:.6f} s busy on {dev.get('plane')}")
            timed = ((ns, table.get(scopes.event_instruction(name)))
                     for name, ns in scopes.self_times(dev["ops"]).items())
            rows = [(ns, s) for ns, s in timed if s is not None]
            out.append((dev["steps"], rows, a))
        # the whole table once, for the ledger's breakdown: by layer and
        # phase, and the collectives' part of each layer (BatchNorm's
        # cross-replica reductions sit under their modules)
        steps, _, first = out[0]
        for key, name in (("by_layer_phase", "scope_ms_per_step"),
                          ("collective_by_layer",
                           "scope_collective_ms_per_step")):
            reading.setdefault("breakdown", {})[name] = {
                k: round(1e3 * v / steps, 4)
                for k, v in sorted(first[key].items())}
    reading["_scope_join"] = out
    return out


def per_step_ms(devices, keep):
    """Mean over devices of the self time per step of the ops ``keep(scope)``
    holds, in milliseconds."""
    per_dev = [sum(ns for ns, s in rows if keep(s)) / 1e6 / steps
               for steps, rows, _ in devices]
    return sum(per_dev) / len(per_dev)


def read(ctx, reading, layers=None, not_layers=None, phase=None, mixed=None,
         path_has=None):
    devices = joined(ctx, reading)
    if devices is None:
        return None

    def keep(s):
        return (layers is None or s.layer in layers) \
            and (not_layers is None or s.layer not in not_layers) \
            and (phase is None or s.phase == phase) \
            and (mixed is None or s.mixed == mixed) \
            and (path_has is None or path_has in s.path.split("/"))

    return per_step_ms(devices, keep)
