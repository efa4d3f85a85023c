"""Traffic kind ``train_step``: the trainer's compiled step, driven on a
batch that stays on the device.

The step is built as ``bench.py`` and ``Trainer`` build it (planner -> mesh ->
``make_train_step``), lowered and compiled once, and that one executable with
its one state goes through the first training steps in set-up (which the
reference follows afterwards, to decide ``correct``) and straight on into the
timed window.  The traffic file gives ``per_chip_batch``, ``reduce_buckets``,
``check_steps``, ``in_flight`` and ``trace_steps``."""

from __future__ import annotations

import collections
import functools
import gc
import sys
import time

import compare
import harness
import inputs
from reference import nets


def build_program(ctx, mesh, n_chips):
    """The program under test: model, optimizer, and the jitted train step
    made through the planner, exactly as the trainer makes it."""
    import optax
    from distributedpytorch_tpu.models import build_model
    from distributedpytorch_tpu.parallel import plan as plan_lib
    from distributedpytorch_tpu.train.precision import precision_policy

    cfg, rb = ctx.config, int(ctx.traffic["reduce_buckets"])
    policy = precision_policy(cfg["precision"])
    model = build_model(
        cfg["architecture"], nclass=cfg["num_classes"],
        backbone=f"resnet{cfg['backbone_depth']}",
        output_stride=cfg["output_stride"],
        dtype=(policy.compute_dtype if policy else cfg["precision"]),
        bn_cross_replica_axis=("data" if rb else None),
        **cfg.get("build_model", {}))
    opt = cfg["optimizer"]
    tx = optax.sgd(opt["learning_rate"], momentum=opt["momentum"])
    plan = plan_lib.resolve_plan("dp", n_devices=n_chips)

    def make_step(state):
        return plan.make_train_step(
            model, tx, mesh=mesh, state=state, loss_type=cfg["loss"],
            precision=policy, reduce_buckets=rb)

    return plan, tx, make_step


def cell_layout(ctx, devices):
    """``(mesh, (replicated, batch-split) shardings, rows, make)`` of a cell on
    these devices; ``make(seed)`` puts that seed's weights, norm statistics,
    state key and batch there in one jitted call."""
    import jax
    from distributedpytorch_tpu.parallel import mesh as mesh_lib
    from distributedpytorch_tpu.parallel import plan as plan_lib

    n = len(devices)
    mesh = plan_lib.resolve_plan("dp", n_devices=n).make_mesh(devices)
    repl = mesh_lib.replicated_sharding(mesh)
    data = mesh_lib.batch_sharding(mesh)
    rows = int(ctx.traffic["per_chip_batch"]) * n
    make_jit = jax.jit(
        functools.partial(inputs.make_inputs, cfg=ctx.config, rows=rows),
        out_shardings=(repl, repl, repl, data))
    return mesh, (repl, data), rows, \
        lambda seed: make_jit(*inputs.seed_words(seed))


def initial_state(tx, params, stats, rng, repl):
    """The trainer's state at step 0 over these weights."""
    import jax
    import jax.numpy as jnp
    from distributedpytorch_tpu.parallel.step import TrainState

    return TrainState(
        step=jax.device_put(jnp.zeros((), jnp.int32), repl),
        params=params, batch_stats=stats,
        opt_state=jax.jit(tx.init, out_shardings=repl)(params), rng=rng)


def build_step(make_step, state, batch):
    """One executable for the cell's one shape.  (The benchmark's fault
    tests put a broken step in here.)"""
    return make_step(state).lower(state, batch).compile()


def leaf_names(cfg) -> list:
    """Parameter leaves by path, in the order ``jax.tree.leaves`` gives."""
    import jax

    spec = nets.param_spec(cfg)
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(spec, is_leaf=nets._is_leaf)]


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for x in jax.tree.leaves(tree)])


def leaf_change_norms(new, old):
    import jax

    return leaf_norms(jax.tree.map(lambda a, b: a - b, new, old))


def reference_step(cfg, shardings, q=None, rows=None):
    """The plain reference's training step, jitted over ``(params, stats,
    momentum trace, key, batch)`` on the cell's layout.  ``q`` computes it in
    a lower type (the control), ``rows`` leaves rows out (a fault)."""
    import jax

    repl, data = shardings
    return jax.jit(
        functools.partial(nets.train_step, cfg, cfg["optimizer"], q=q,
                          rows=rows),
        in_shardings=(repl, repl, repl, repl, data),
        out_shardings=repl, donate_argnums=(0, 1, 2))


def reference_runner(ctx, shardings, **variant):
    """``run(make) -> readings`` of the plain reference over the weights,
    key and batch that ``make()`` gives: the first steps' losses, the first
    gradient's leaf norms, the leaf norms of the parameters' change.
    ``variant`` is :func:`reference_step`'s.  Compiled at the first call,
    reused for every seed after it."""
    import jax
    import jax.numpy as jnp

    jitted = reference_step(ctx.config, shardings, **variant)
    norms, change = jax.jit(leaf_norms), jax.jit(leaf_change_norms)
    compiled = []

    def run(make):
        params, stats, rng, batch = make()
        params0 = jax.tree.map(jnp.copy, params)
        trace = jax.tree.map(jnp.zeros_like, params)
        t0 = time.perf_counter()
        if not compiled:
            lowered = jitted.lower(params, stats, trace, rng, batch)
            t1 = time.perf_counter()
            compiled.append(lowered.compile())
            print("reference_stages",
                  [["trace_lower", round(t1 - t0, 3)],
                   ["compile_or_cache_load",
                    round(time.perf_counter() - t1, 3)]], file=sys.stderr)
        step = compiled[0]
        out = {"loss": []}
        for i in range(int(ctx.traffic["check_steps"])):
            params, stats, trace, rng, loss = step(params, stats, trace, rng,
                                                   batch)
            out["loss"].append(loss)
            if i == 0:
                out["gnorm"] = norms(trace)
        out["dnorm"] = change(params, params0)
        return jax.device_get(out)

    return run


def run(ctx) -> dict:
    stages = [("process_start_to_kind", time.time() - ctx.t_start)]
    mark = time.perf_counter()

    def stage(name):
        nonlocal mark
        now = time.perf_counter()
        stages.append((name, now - mark))
        mark = now

    devices = ctx.acquire_devices()
    import jax
    import jax.numpy as jnp
    import numpy as np

    ctx.enable_cache()
    cfg, traffic = ctx.config, ctx.traffic
    n_chips = len(devices)
    check_steps = int(traffic["check_steps"])
    mesh, (repl, data), rows, make_seed = cell_layout(ctx, devices)

    def make():
        return make_seed(ctx.seed)

    stage("imports_and_devices")
    with mesh:
        _, tx, make_step = build_program(ctx, mesh, n_chips)
        stage("program_imports_and_model")
        params, stats, rng, batch = make()
        params0 = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(params)
        jax.block_until_ready(params0)
        stage("weights_and_batch_on_device")
        state = initial_state(tx, params, stats, rng, repl)
        jax.block_until_ready(state.params)
        stage("optimizer_state")
        step = build_step(make_step, state, batch)
        stage("trace_lower_compile_or_cache_load")
        norms, change = jax.jit(leaf_norms), jax.jit(leaf_change_norms)

        # set-up: the first training steps, through the window's own call
        got = {"loss": []}
        for i in range(check_steps):
            state, loss = step(state, batch)
            got["loss"].append(loss)
            if i == 0:
                got["gnorm"] = norms(state.opt_state)
        got["dnorm"] = change(state.params, params0)
        del params0, params, stats
        jax.block_until_ready(state.params)
        got = jax.device_get(got)
        stage("first_steps")

        # the timed window
        tracer = harness.Tracer() if ctx.trace else None
        trace_at = int(traffic["trace_after_steps"])
        trace_steps = int(traffic["trace_steps"])
        in_flight = int(traffic["in_flight"])
        losses, pending = [], collections.deque()
        # the interpreter's collector walks every object that the imports and
        # the tracing left behind, for a tenth of a second at a time: longer
        # than a step, so the device would drain.  Nothing made before the
        # window is garbage it could free.
        gc.collect()
        gc.freeze()
        setup_s = time.time() - ctx.t_start
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            if tracer and len(losses) == trace_at:
                jax.block_until_ready(state.params)
                pending.clear()
                tracer.start()
                for _ in range(trace_steps):
                    with jax.profiler.TraceAnnotation("bench_dispatch_step"):
                        state, loss = step(state, batch)
                    losses.append(loss)
                with jax.profiler.TraceAnnotation("bench_wait_params"):
                    jax.block_until_ready(state.params)
                tracer.stop()
                continue
            if len(pending) >= in_flight:
                pending.popleft().block_until_ready()
            state, loss = step(state, batch)
            losses.append(loss)
            pending.append(loss)
        jax.block_until_ready(state.params)
        window_s = time.perf_counter() - t0
        gc.unfreeze()

        loss_values = np.asarray(jax.device_get(losses), np.float64)
        memory_peak, memory_detail = ctx.memory_peak_bytes()
        out = {
            "setup_stages": [[n, round(t, 3)] for n, t in stages],
            "attempted": len(losses),
            "failed": int((~np.isfinite(loss_values)).sum()),
            "memory_peak_bytes": memory_peak,
            "end_to_end": {
                "setup_s": setup_s,
                "train_imgs_per_s_per_chip":
                    len(losses) * rows / window_s / n_chips,
            },
        }
        if tracer:
            import xtrace

            raw = tracer.read(ctx)
            summary = xtrace.summarize(raw, traffic["step_module_pattern"])
            out["reading"] = {
                "summary": summary, "images_per_step": rows,
                "chips": n_chips, "memory_peak_bytes": memory_peak,
                "memory_detail": memory_detail,
                "breakdown": xtrace.breakdown(summary),
            }

        # the program's state goes before the reference takes the chip
        del state, step, batch, losses, pending, loss
        t_ref = time.perf_counter()
        want = reference_runner(ctx, (repl, data))(make)
        out["reference_s"] = time.perf_counter() - t_ref

    nums = compare.numbers(got, want)
    names = leaf_names(cfg)
    for what, key in (("grad", "gnorm"), ("change", "dnorm")):
        i = nums.pop(f"{what}_worst_leaf")
        if 0 <= i < len(names):
            print(f"{what} worst leaf {names[i]}: program {got[key][i]!r} "
                  f"reference {want[key][i]!r} (median reference "
                  f"{float(np.median(want[key]))!r})", file=sys.stderr)
    print("memory_stats", memory_detail, file=sys.stderr)
    print("setup_stages", out["setup_stages"], file=sys.stderr)
    out["correct"], out["compared"] = compare.judge(nums, ctx.limits)
    out["correct"] = out["correct"] and out["failed"] == 0 \
        and out["attempted"] > 0
    out["numbers"] = nums
    return out
