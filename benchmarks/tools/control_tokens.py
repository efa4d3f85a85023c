"""``control.py``'s readings for a cell of the ``train_step_tokens`` kind, at
the cell's own size: for each seed, the plain reference against itself
computed in the nearest precision below the configuration's (the control;
8-bit floats for a bfloat16 configuration), as the configuration states it
(a witness), with half of the tokens left out and with the routed experts
left out (faults).  Each variant stands in the program's place and is judged
by the cell's own limits (``compare.judge``, as ``kinds/train_step_tokens.py``
judges the program): the line says ``correct`` and which limits it broke.
The control and every fault have to read not ``correct``, the witness
``correct``.  One process, no program under test; a state left unchanged
needs no run (its change gap reads 1 by construction).

    python3 benchmarks/tools/control_tokens.py <cell> <seed> [<seed> ...]
"""

import argparse
import functools
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH),
                os.path.join(BENCH, "tools")]

import compare  # noqa: E402
import harness  # noqa: E402
from control import BELOW  # noqa: E402
from reference import nets  # noqa: E402


def main(argv=None, allow_cpu=False, root=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--only", nargs="*", default=None,
                    help="variants to run (default: all)")
    args = ap.parse_args(argv)
    ns = argparse.Namespace(workload=args.cell, seed=0, seconds=1, trace=0)
    ctx = harness.Context.load(root or os.path.dirname(BENCH), ns,
                               allow_cpu=allow_cpu, t_start=time.time())
    devices = ctx.acquire_devices()
    ctx.enable_cache()
    kind = harness.load_module(ctx.bench_dir, "kinds", ctx.traffic["kind"])
    mesh, shardings, tokens, make = kind.cell_layout(ctx, devices)
    precision = ctx.config["precision"]
    variants = [("control", {"q": nets.Rounding(BELOW[precision])}),
                ("half_tokens", {"rows": tokens // 2}),
                ("no_routed_experts", {"drop_routed": True})]
    if precision != "float32":
        variants.append(("witness_" + precision,
                         {"q": nets.Rounding(precision)}))
    if args.only is not None:
        variants = [v for v in variants if v[0] in args.only]
    out = []
    with mesh:
        want = {}
        for name, kw in [("reference", {})] + variants:
            run = kind.reference_runner(ctx, shardings, **kw)
            for seed in args.seeds:
                got = run(functools.partial(make, seed))
                if name == "reference":
                    want[seed] = got
                    continue
                nums = compare.numbers(got, want[seed])
                # the reference has no buffer to fall off
                nums["moe_tokens_dropped"] = 0.0
                ok, table = compare.judge(nums, ctx.limits)
                line = {"cell": args.cell, "seed": seed, "what": name,
                        "correct": ok,
                        "broke": sorted(k for k, (v, lim) in table.items()
                                        if not v <= lim),
                        "numbers": nums,
                        "loss": [float(x) for x in got["loss"]]}
                print(json.dumps(line), flush=True)
                out.append(line)
    return out


if __name__ == "__main__":
    main()
