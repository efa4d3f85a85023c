"""Compile a cell's step program, and the reference's step, at the cell's
real size for one described v5e chip, with no chip attached, and print what
the compiler says they hold (``memory_analysis()``).  A compile that passes
is not a chip run.  Usage, from the root of the repo:

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_check.py <cell> [program|reference|both]
"""

import argparse
import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import harness  # noqa: E402
import inputs  # noqa: E402


def report(tag, compiled, t0):
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    print(json.dumps({
        "what": tag, "compile_s": round(time.time() - t0, 1),
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "argument_gb": ma.argument_size_in_bytes / 1e9,
        "output_gb": ma.output_size_in_bytes / 1e9,
        "alias_gb": ma.alias_size_in_bytes / 1e9,
        "generated_code_gb": ma.generated_code_size_in_bytes / 1e9,
        "tpu_custom_calls": hlo.count('custom_call_target="tpu_custom_call"'),
    }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("what", nargs="?", default="both")
    ap.add_argument("--chips", type=int, default=None)
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    root = os.path.dirname(BENCH)
    ns = argparse.Namespace(workload=args.cell, seed=0, seconds=1, trace=0)
    ctx = harness.Context.load(root, ns, allow_cpu=True, t_start=time.time())
    chips = args.chips or ctx.cell["chips"]
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    devices = list(topo.devices)[:chips]

    from distributedpytorch_tpu.models import danet as danet_mod
    from distributedpytorch_tpu.parallel import mesh as mesh_lib
    from distributedpytorch_tpu.parallel import plan as plan_lib
    from distributedpytorch_tpu.parallel.step import TrainState

    danet_mod._on_tpu = lambda: True  # 'auto' resolves as on a TPU host
    kind = harness.load_module(ctx.bench_dir, "kinds", ctx.traffic["kind"])
    mesh = plan_lib.resolve_plan("dp", n_devices=chips).make_mesh(
        np.asarray(devices))
    repl, data = NamedSharding(mesh, P()), mesh_lib.batch_sharding(mesh)
    rows = int(ctx.traffic["per_chip_batch"]) * chips
    shapes = jax.eval_shape(functools.partial(
        inputs.make_inputs, cfg=ctx.config, rows=rows),
        np.uint32(0), np.uint32(0))

    def on(sharding, tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    params, stats, rng = on(repl, shapes[:3])
    batch = on(data, shapes[3])
    with mesh:
        if args.what in ("program", "both"):
            _, tx, make_step = kind.build_program(ctx, mesh, chips)
            state = TrainState(
                step=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
                params=params, batch_stats=stats,
                opt_state=on(repl, jax.eval_shape(tx.init, params)), rng=rng)
            t0 = time.time()
            report("program", kind.build_step(make_step, state, batch), t0)
        if args.what in ("reference", "both"):
            step = kind.reference_step(ctx.config, (repl, data))
            t0 = time.time()
            report("reference", step.lower(params, stats, params, rng,
                                           batch).compile(), t0)


if __name__ == "__main__":
    main()
