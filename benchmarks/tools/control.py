"""Readings that the limits of ``correct`` are set from, taken at the cell's
own size: for each seed, the plain reference against itself computed in the
nearest precision below the configuration's (the control; 8-bit floats for a
bfloat16 configuration, bfloat16 for a float32 one), and against itself with
half of the batch left out (a fault).  One process, no program under test.

    python3 benchmarks/tools/control.py <cell> <seed> [<seed> ...]
"""

import argparse
import functools
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import compare  # noqa: E402
import harness  # noqa: E402
from reference import nets  # noqa: E402

BELOW = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def main(argv=None, allow_cpu=False, root=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    ns = argparse.Namespace(workload=args.cell, seed=0, seconds=1, trace=0)
    ctx = harness.Context.load(root or os.path.dirname(BENCH), ns,
                               allow_cpu=allow_cpu, t_start=time.time())
    devices = ctx.acquire_devices()
    ctx.enable_cache()
    kind = harness.load_module(ctx.bench_dir, "kinds", ctx.traffic["kind"])
    mesh, shardings, rows, make = kind.cell_layout(ctx, devices)
    precision = ctx.config["precision"]
    variants = [("control", {"q": nets.Rounding(BELOW[precision])}),
                ("half_batch", {"rows": rows // 2})]
    if precision != "float32":
        # a second witness: the reference computed as the configuration
        # states, against itself in float32
        variants.append(("witness_" + precision,
                         {"q": nets.Rounding(precision)}))
    out = []
    with mesh:
        # one compile per variant, every seed through it
        want = {}
        for name, kw in [("reference", {})] + variants:
            run = kind.reference_runner(ctx, shardings, **kw)
            for seed in args.seeds:
                got = run(functools.partial(make, seed))
                if name == "reference":
                    want[seed] = got
                    continue
                line = {"cell": args.cell, "seed": seed, "what": name,
                        "numbers": compare.numbers(got, want[seed])}
                print(json.dumps(line), flush=True)
                out.append(line)
    return out


if __name__ == "__main__":
    main()
