"""The look behind the limits of ``correct``: where, step by step and leaf by
leaf, the timed program and the plain reference part.  For each seed it
drives the cell's compiled step and then the reference through the first
steps, keeps the momentum trace and the parameters after every step on the
host, and prints per step the gaps of the leaf norms (worst, 90th centile and
median leaf, as ``compare.leaf_gaps`` measures them), the same for the norm
of the difference (which the comparison does not use: it shows how much of a
gap hides in the direction), and how many float32 spacings a parameter moves.

    python3 benchmarks/tools/look_steps.py <cell> <seed> [<seed> ...]
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import compare  # noqa: E402
import harness  # noqa: E402


def stats(gaps):
    import numpy as np

    return {"worst": float(gaps.max()), "p90": float(np.quantile(gaps, 0.9)),
            "median": float(np.median(gaps)), "worst_leaf": int(gaps.argmax())}


def main(argv=None, allow_cpu=False, root=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    for seed in args.seeds:
        ns = argparse.Namespace(workload=args.cell, seed=seed, seconds=1,
                                trace=0)
        ctx = harness.Context.load(root or os.path.dirname(BENCH), ns,
                                   allow_cpu=allow_cpu, t_start=time.time())
        devices = ctx.acquire_devices()
        ctx.enable_cache()
        kind = harness.load_module(ctx.bench_dir, "kinds", ctx.traffic["kind"])
        n = len(devices)
        cfg = ctx.config
        mesh, (repl, data), _, make = kind.cell_layout(ctx, devices)
        steps = int(ctx.traffic["check_steps"])
        flat = lambda t: [np.asarray(x, np.float32)  # noqa: E731
                          for x in jax.tree.leaves(jax.device_get(t))]
        with mesh:
            _, tx, make_step = kind.build_program(ctx, mesh, n)
            params, stats_, rng, batch = make(seed)
            p0 = flat(params)
            state = kind.initial_state(tx, params, stats_, rng, repl)
            step = kind.build_step(make_step, state, batch)
            got = []
            for _ in range(steps):
                state, loss = step(state, batch)
                got.append((float(loss), flat(state.opt_state),
                            flat(state.params)))
            del state, step, params, stats_
            params, stats_, rng, batch = make(seed)
            trace = jax.tree.map(jnp.zeros_like, params)
            ref = kind.reference_step(cfg, (repl, data))
            want = []
            for _ in range(steps):
                params, stats_, trace, rng, loss = ref(params, stats_, trace,
                                                       rng, batch)
                want.append((float(loss), flat(trace), flat(params)))
            del params, stats_, trace, ref

        def norms(leaves, base=None):
            return np.array([np.linalg.norm((a if base is None else a - b)
                                            .astype(np.float64).ravel())
                             for a, b in zip(leaves, base or leaves)])

        names = kind.leaf_names(cfg)
        for i in range(steps):
            (gl, gt, gp), (wl, wt, wp) = got[i], want[i]
            prev_g = p0 if i == 0 else got[i - 1][2]
            prev_w = p0 if i == 0 else want[i - 1][2]
            wn = norms(wt)
            line = {
                "cell": args.cell, "seed": seed, "step": i + 1,
                "loss": [gl, wl],
                "trace_norm_gap": stats(compare.leaf_gaps(norms(gt), wn)),
                "trace_diff_norm": stats(
                    norms(gt, wt) / np.maximum(wn, np.median(wn))),
                "step_change_norm_gap": stats(compare.leaf_gaps(
                    norms(gp, prev_g), norms(wp, prev_w))),
                "change_norm_gap": stats(compare.leaf_gaps(
                    norms(gp, p0), norms(wp, p0))),
                "median_leaf_norm": {"trace": float(np.median(wn)),
                                     "change": float(np.median(norms(wp, p0)))},
                "spacings_moved_median_leaf": float(np.median(
                    [np.median(np.abs(a - b) / np.spacing(np.abs(b) + 1e-30))
                     for a, b in zip(wp, prev_w)])),
            }
            line["worst_leaf_names"] = {
                k: names[line[k]["worst_leaf"]] for k in
                ("trace_norm_gap", "change_norm_gap")}
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
