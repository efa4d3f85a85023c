"""``control.py``'s readings for a cell of the ``train_step_lm`` kind, at the
cell's own size: for each seed, the plain reference against itself computed
in the nearest precision below the configuration's (the control; 8-bit
floats for a bfloat16 configuration), as the configuration states it (a
witness), and with each of the faults that the cell's traffic file lists
(``faults``: a name and the keyword arguments of the reference's
``train_step``; ``"rows": "half"`` stands for half of the step's tokens).
Each variant stands in the program's place and is judged by the cell's own
limits (``compare.judge``, as ``kinds/train_step_lm.py`` judges the program):
the line says ``correct`` and which limits it broke.  The control and every
fault have to read not ``correct``, the witness ``correct``.  One process, no
program under test; the reference counts nothing, so every counter reads as
a sound run's (0, or the value the traffic file expects).

    python3 benchmarks/tools/control_lm.py <cell> <seed> [<seed> ...]
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
    variants = [("control", {"q": nets.Rounding(BELOW[precision])})]
    for name, kw in ctx.traffic.get("faults", {}).items():
        variants.append((name, {k: tokens // 2 if (k, v) == ("rows", "half")
                                else v for k, v in kw.items()}))
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
                # the reference has no buffer to fall off and counts no key
                sound = dict(ctx.traffic.get("expected_counters", {}))
                nums.update({k: 0.0 for k in ctx.limits if k not in nums})
                nums.update(kind.counter_numbers(ctx, sound))
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
