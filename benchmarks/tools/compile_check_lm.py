"""``compile_check.py`` for a cell of the ``train_step_lm`` kind: compile
the step program, and the reference's step, at the cell's real size for one
described v5e chip, with no chip attached, and print what the compiler says
they hold.  A compile that passes is not a chip run.  From the repo's root:

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_check_lm.py <cell> [program|reference|both]
"""

import argparse
import functools
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH),
                os.path.join(BENCH, "tools")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import harness  # noqa: E402
from compile_check import report  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("what", nargs="?", default="both")
    ap.add_argument("--dump", default=None,
                    help="write the program's HLO text here")
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    root = os.path.dirname(BENCH)
    ns = argparse.Namespace(workload=args.cell, seed=0, seconds=1, trace=0)
    ctx = harness.Context.load(root, ns, allow_cpu=True, t_start=time.time())
    chips = ctx.cell["chips"]
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    devices = list(topo.devices)[:chips]

    from distributedpytorch_tpu.models import danet as danet_mod
    from distributedpytorch_tpu.parallel import mesh as mesh_lib
    from distributedpytorch_tpu.parallel import plan as plan_lib
    from distributedpytorch_tpu.parallel.step import TrainState
    # the program asks the default backend, which is the CPU here, whether
    # it runs on a TPU: it is compiled for one
    danet_mod._on_tpu = lambda: True
    kind = harness.load_module(ctx.bench_dir, "kinds", ctx.traffic["kind"])
    ref = kind.reference_of(ctx)
    mesh = plan_lib.resolve_plan("dp", n_devices=chips).make_mesh(
        np.asarray(devices))
    repl, data = NamedSharding(mesh, P()), mesh_lib.batch_sharding(mesh)
    shapes = jax.eval_shape(functools.partial(
        kind.make_inputs, cfg=ctx.config,
        sequences=int(ctx.traffic["per_chip_batch"]) * chips,
        seq_len=int(ctx.traffic["seq_len"]), ref=ref),
        np.uint32(0), np.uint32(0))

    def on(sharding, tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    params, rng = on(repl, shapes[:2])
    batch = on(data, shapes[2])
    with mesh:
        if args.what in ("program", "both"):
            _, tx, make_step = kind.build_program(ctx, mesh, chips)
            state = TrainState(
                step=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
                params=params, batch_stats={},
                opt_state=on(repl, jax.eval_shape(tx.init, params)), rng=rng)
            t0 = time.time()
            compiled = harness.load_module(
                ctx.bench_dir, "kinds", "train_step").build_step(
                    make_step, state, batch)
            report("program", compiled, t0)
            if args.dump:
                with open(args.dump, "w") as f:
                    f.write(compiled.as_text())
        if args.what in ("reference", "both"):
            step = jax.jit(
                functools.partial(ref.train_step, ctx.config,
                                  ctx.config["optimizer"]),
                in_shardings=(repl, repl, data), out_shardings=repl,
                donate_argnums=(0, 1))
            t0 = time.time()
            report("reference", step.lower(params, params, batch).compile(),
                   t0)


if __name__ == "__main__":
    main()
