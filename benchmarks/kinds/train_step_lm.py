"""Traffic kind ``train_step_lm``: the trainer's compiled step of any token
model (``train.task=tokens``), driven on a batch of token ids that stays on
the device.  It names nothing of one architecture.

As ``train_step`` and with its window (first timed dispatch to
``block_until_ready`` on the updated parameters), and what a token cell
differs in kept here: the batch is ``{tokens: int32 (B, S)}`` drawn uniformly
over the vocabulary rows held; the plain reference is the module that the
configuration names (``"reference": "<name>"`` -> ``reference/<name>.py``:
``param_spec``, ``make_weights``, ``train_step`` with its variants), the
weights are that module's and there are no norm statistics; the loss weights
and the weight of the model's sown ``losses`` come from the model
(``loss_weights``, ``aux_loss_weight``); the step hands back ``(loss,
counters)`` and EVERY counter goes into the reading, combined over the
window's steps by the rule its declaration gives
(``telemetry/counters.py``); a counter that the cell's limits name is judged,
and one that the traffic file expects a value of (``expected_counters``) is
judged by its distance from it (``<name>_off_expected``).  A traced run puts
the scope table of the executable it ran into the reading, so that the
readers never rebuild the step.  ``train_imgs_per_s_per_chip`` counts
*sequences* (one "image" = one packed sequence of ``seq_len`` tokens).

The traffic file gives ``per_chip_batch``, ``seq_len``, ``reduce_buckets``
(0), ``check_steps``, ``in_flight``, ``trace_after_steps``, ``trace_steps``,
and, for ``tools/control_lm.py``, the ``faults`` of the reference that the
limits have to catch.
"""

from __future__ import annotations

import collections
import functools
import gc
import sys
import time

import compare
import harness
import inputs


def reference_of(ctx):
    """The plain reference the configuration names."""
    return harness.load_module(ctx.bench_dir, "reference",
                               ctx.config["reference"])


def _shared(ctx):
    """What ``kinds/train_step.py`` exposes and fits a token cell as it is:
    ``leaf_norms``, ``leaf_change_norms``, ``build_step``, ``initial_state``."""
    return harness.load_module(ctx.bench_dir, "kinds", "train_step")


def build_program(ctx, mesh, n_chips):
    """The program under test: model, optimizer, and the jitted train step
    made through the planner, exactly as the trainer makes it."""
    import optax
    from distributedpytorch_tpu.models import build_model
    from distributedpytorch_tpu.parallel import plan as plan_lib
    from distributedpytorch_tpu.train.precision import precision_policy

    cfg = ctx.config
    if int(ctx.traffic["reduce_buckets"]):
        raise ValueError("a token cell runs the GSPMD step: reduce_buckets 0")
    policy = precision_policy(cfg["precision"])
    model = build_model(
        cfg["architecture"], lm_config=cfg,
        dtype=(policy.compute_dtype if policy else cfg["precision"]),
        **cfg.get("build_model", {}))
    opt = cfg["optimizer"]
    tx = optax.sgd(opt["learning_rate"], momentum=opt["momentum"])
    plan = plan_lib.resolve_plan("dp", n_devices=n_chips)

    def make_step(state):
        return plan.make_train_step(
            model, tx, mesh=mesh, state=state, loss_type=cfg["loss"],
            loss_weights=model.loss_weights,
            aux_loss_weight=getattr(model, "aux_loss_weight", 0.0),
            precision=policy)

    return plan, tx, make_step


def make_inputs(lo, hi, cfg: dict, sequences: int, seq_len: int, ref):
    """``(params, state_key, batch)`` from the two words of ``--seed``."""
    import jax

    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
    kw, kx, kr = jax.random.split(key, 3)
    tokens = jax.random.randint(kx, (sequences, seq_len), 0,
                                cfg["vocab_size"], dtype="int32")
    return ref.make_weights(kw, cfg), kr, {"tokens": tokens}


def cell_layout(ctx, devices):
    """``(mesh, (replicated, batch-split) shardings, rows, make)``; ``rows``
    counts the step's *tokens* (what a "half batch" fault halves) and
    ``make(seed)`` puts that seed's weights, state key and batch on the
    devices in one jitted call."""
    import jax
    from distributedpytorch_tpu.parallel import mesh as mesh_lib
    from distributedpytorch_tpu.parallel import plan as plan_lib

    n = len(devices)
    mesh = plan_lib.resolve_plan("dp", n_devices=n).make_mesh(devices)
    repl = mesh_lib.replicated_sharding(mesh)
    data = mesh_lib.batch_sharding(mesh)
    sequences = int(ctx.traffic["per_chip_batch"]) * n
    seq_len = int(ctx.traffic["seq_len"])
    make_jit = jax.jit(
        functools.partial(make_inputs, cfg=ctx.config, sequences=sequences,
                          seq_len=seq_len, ref=reference_of(ctx)),
        out_shardings=(repl, repl, data))
    return mesh, (repl, data), sequences * seq_len, \
        lambda seed: make_jit(*inputs.seed_words(seed))


def leaf_names(ctx) -> list:
    import jax

    ref = reference_of(ctx)
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(ref.param_spec(ctx.config),
                                                is_leaf=ref._is_leaf)]


def reference_runner(ctx, shardings, **variant):
    """``run(make) -> readings`` of the plain reference over the weights and
    batch that ``make()`` gives: the first steps' losses, the first
    gradient's leaf norms, the leaf norms of the parameters' change.
    ``variant``: keyword arguments of the reference's ``train_step`` — ``q``
    computes it in a lower type (the control), the others are its faults
    (the traffic file's ``faults``).  The starting weights are made a second time for the
    change's norms: a copy kept through the steps would not fit beside the
    float32 gradients."""
    import jax
    import jax.numpy as jnp

    repl, data = shardings
    shared = _shared(ctx)
    jitted = jax.jit(
        functools.partial(reference_of(ctx).train_step, ctx.config,
                          ctx.config["optimizer"], **variant),
        in_shardings=(repl, repl, data), out_shardings=repl,
        donate_argnums=(0, 1))
    norms = jax.jit(shared.leaf_norms)
    change = jax.jit(shared.leaf_change_norms)
    compiled = []

    def run(make):
        params, _, batch = make()
        trace = jax.tree.map(jnp.zeros_like, params)
        t0 = time.perf_counter()
        if not compiled:
            lowered = jitted.lower(params, trace, batch)
            t1 = time.perf_counter()
            compiled.append(lowered.compile())
            print("reference_stages",
                  [["trace_lower", round(t1 - t0, 3)],
                   ["compile_or_cache_load",
                    round(time.perf_counter() - t1, 3)]], file=sys.stderr)
        step = compiled[0]
        out = {"loss": []}
        for i in range(int(ctx.traffic["check_steps"])):
            params, trace, loss = step(params, trace, batch)
            out["loss"].append(loss)
            if i == 0:
                out["gnorm"] = norms(trace)
        del trace, batch
        out["dnorm"] = change(params, make()[0])
        return jax.device_get(out)

    return run


def window_counters(seen: list) -> dict:
    """Every counter the step handed back, over the window's steps, by the
    rule its declaration gives (a sum of sums, a maximum of maxima)."""
    import numpy as np
    from distributedpytorch_tpu.telemetry import counters as counters_lib

    return {name: float(counters_lib.combine(
                name, np.stack([np.asarray(c[name], np.float64)
                                for c in seen])))
            for name in sorted(seen[0])} if seen else {}


def counter_numbers(ctx, counters: dict) -> dict:
    """The numbers a cell's limits may name: every counter under its own
    name, and under ``<name>_off_expected`` its distance from the value the
    traffic file expects of it."""
    nums = dict(counters)
    for name, value in ctx.traffic.get("expected_counters", {}).items():
        nums[name + "_off_expected"] = abs(
            counters.get(name, float("inf")) - value)
    return nums


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
    import numpy as np

    ctx.enable_cache()
    cfg, traffic = ctx.config, ctx.traffic
    n_chips = len(devices)
    check_steps = int(traffic["check_steps"])
    shared = _shared(ctx)
    mesh, (repl, data), tokens_per_step, make_seed = cell_layout(ctx, devices)
    sequences = tokens_per_step // int(traffic["seq_len"])

    def make():
        return make_seed(ctx.seed)

    stage("imports_and_devices")
    with mesh:
        _, tx, make_step = build_program(ctx, mesh, n_chips)
        stage("program_imports_and_model")
        params, rng, batch = make()
        jax.block_until_ready(params)
        stage("weights_and_batch_on_device")
        state = shared.initial_state(tx, params, {}, rng, repl)
        jax.block_until_ready(state.params)
        del params
        stage("optimizer_state")
        step = shared.build_step(make_step, state, batch)
        stage("trace_lower_compile_or_cache_load")
        norms = jax.jit(shared.leaf_norms)
        change = jax.jit(shared.leaf_change_norms)

        # set-up: the first training steps, through the window's own call
        got = {"loss": []}
        for i in range(check_steps):
            state, (loss, counters) = step(state, batch)
            got["loss"].append(loss)
            if i == 0:
                got["gnorm"] = norms(state.opt_state)
        # the starting weights a second time (a copy kept through the steps
        # would sit beside the step's own peak)
        got["dnorm"] = change(state.params, make()[0])
        jax.block_until_ready(state.params)
        got = jax.device_get(got)
        stage("first_steps")

        # the timed window
        tracer = harness.Tracer() if ctx.trace else None
        trace_at = int(traffic["trace_after_steps"])
        trace_steps = int(traffic["trace_steps"])
        in_flight = int(traffic["in_flight"])
        losses, seen, pending = [], [], collections.deque()
        # as train_step: nothing made before the window is garbage that the
        # collector could free, and its walks are longer than a dispatch
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
                        state, (loss, counters) = step(state, batch)
                    losses.append(loss)
                    seen.append(counters)
                with jax.profiler.TraceAnnotation("bench_wait_params"):
                    jax.block_until_ready(state.params)
                tracer.stop()
                continue
            if len(pending) >= in_flight:
                pending.popleft().block_until_ready()
            state, (loss, counters) = step(state, batch)
            losses.append(loss)
            seen.append(counters)
            pending.append(loss)
        jax.block_until_ready(state.params)
        window_s = time.perf_counter() - t0
        gc.unfreeze()

        loss_values = np.asarray(jax.device_get(losses), np.float64)
        seen = jax.device_get(seen)
        counters = window_counters(seen)
        memory_peak, memory_detail = ctx.memory_peak_bytes()
        out = {
            "setup_stages": [[n, round(t, 3)] for n, t in stages],
            "attempted": len(losses),
            "failed": int((~np.isfinite(loss_values)).sum()),
            "memory_peak_bytes": memory_peak,
            "end_to_end": {
                "setup_s": setup_s,
                "train_imgs_per_s_per_chip":
                    len(losses) * sequences / window_s / n_chips,
            },
            "counters": counters,
        }
        if tracer:
            import xtrace

            raw = tracer.read(ctx)
            summary = xtrace.summarize(raw, traffic["step_module_pattern"])
            out["reading"] = {
                "summary": summary, "images_per_step": sequences,
                "chips": n_chips, "memory_peak_bytes": memory_peak,
                "memory_detail": memory_detail, "counters": counters,
                "breakdown": xtrace.breakdown(summary),
            }

        # the program's state goes before the reference takes the chip
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            (state, batch))
        ran_executable = hasattr(step, "as_text")
        del state, step, batch, losses, pending, loss
        if tracer and ran_executable:
            from distributedpytorch_tpu.telemetry import scopes

            # the table of the executable that ran (the compile cache hands
            # it back), not of a step rebuilt from other inputs; an entry
            # older than this tree's scopes is compiled once more, past the
            # cache, inside table_for
            table = scopes.table_for(make_step(shapes[0]), *shapes,
                                     allow_recompile=True)
            if table.stale:
                raise RuntimeError(
                    f"scope table is stale: {table.differing}")
            out["reading"]["scope_table"] = table.table
            print("scope_table", {"instructions": len(table.table),
                                  "recompiled": table.recompiled},
                  file=sys.stderr)
        t_ref = time.perf_counter()
        want = reference_runner(ctx, (repl, data))(make)
        out["reference_s"] = time.perf_counter() - t_ref

    nums = compare.numbers(got, want)
    names = leaf_names(ctx)
    for what, key in (("grad", "gnorm"), ("change", "dnorm")):
        i = nums.pop(f"{what}_worst_leaf")
        if 0 <= i < len(names):
            print(f"{what} worst leaf {names[i]}: program {got[key][i]!r} "
                  f"reference {want[key][i]!r} (median reference "
                  f"{float(np.median(want[key]))!r})", file=sys.stderr)
    print("losses", [float(x) for x in got["loss"]],
          [float(x) for x in want["loss"]],
          "window", float(loss_values[0]), float(loss_values[-1]),
          file=sys.stderr)
    print("counters", counters, file=sys.stderr)
    print("memory_stats", memory_detail, file=sys.stderr)
    print("setup_stages", out["setup_stages"], file=sys.stderr)
    nums.update(counter_numbers(ctx, counters))
    out["correct"], out["compared"] = compare.judge(nums, ctx.limits)
    out["correct"] = out["correct"] and out["failed"] == 0 \
        and out["attempted"] > 0
    out["numbers"] = nums
    return out
