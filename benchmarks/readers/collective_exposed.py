"""Device time per step, in milliseconds, that collectives under the named
layers hold the ops line: a synchronous ``all-reduce`` for its length, an
asynchronous pair for its ``-done`` only (the ``-start`` issues and returns;
what the ``-done`` then waits is what the compute did not hide).  Self time,
mean over chips.  Nothing on one chip, where the compiler leaves no
collective in the program."""

import harness


def read(ctx, reading, layers):
    scope_ms = harness.load_module(ctx.bench_dir, "readers", "scope_ms")
    devices = scope_ms.joined(ctx, reading)
    if devices is None:
        return None
    from distributedpytorch_tpu.telemetry import scopes

    def keep(s):
        return s.layer in layers and scopes.is_collective(s.opcode)

    if not any(keep(s) for _, rows, _ in devices for _, s in rows):
        return None
    return scope_ms.per_step_ms(devices, keep)
