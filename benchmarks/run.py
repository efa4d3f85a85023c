"""The benchmark's entry: ``python3 benchmarks/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``, run from the root of a checkout.

Everything about a cell is data: ``BENCHMARK.json`` names the cell's
configuration and traffic mix, ``configs/`` and ``traffic/`` hold their
files, the traffic file names the kind of run (``kinds/<kind>.py``), and each
per-layer metric has ``metrics/<name>.json`` naming its reader in
``readers/``.  A later PR adds files and manifest entries and edits nothing
here.  The last line of standard output is the result."""

from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import harness  # noqa: E402


def main(argv=None, root: str | None = None, allow_cpu: bool = False) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.abspath(root or os.path.dirname(HERE))
    if root not in sys.path:
        sys.path.insert(0, root)  # the program under test lives there
    ctx = harness.Context.load(root, args, allow_cpu=allow_cpu,
                               t_start=harness.process_start(_T_IMPORT))
    kind = harness.load_module(ctx.bench_dir, "kinds", ctx.traffic["kind"])
    result = harness.finish(ctx, kind.run(ctx))
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name} = {value!r} (limit {limit!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
