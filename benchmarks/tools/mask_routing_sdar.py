"""How an ``sdar_lm`` cell routes its mask token, program against plain
reference, at the cell's own size: for each seed and expert layer, the
positions whose top-k experts differ between the float32 reference and (a)
the program's forward pass, (b) the reference computed in the
configuration's own type (the witness), counted apart for the positions that
hold the mask token and for the others, and how many of those differences
touch an expert held here; the reference's margin at the mask positions
(k-th logit less the next: its mean and its spread over the positions)
beside the distance between the program's logits and the reference's there;
and the experts the mask token goes to that are held here.  With
``--train`` also the witness's numbers over the first steps, as
``tools/control_lm.py`` judges them.  ``--drawn`` leaves the mask token's
embedding row as it was drawn (``make_weights(sharpen_mask_row=False)``):
what PERF.md section 2b's counts were read with.

    python3 benchmarks/tools/mask_routing_sdar.py <cell> [--drawn] \
        [--train] <seed> [<seed> ...]
"""

import argparse
import functools
import json
import os
import sys
import time
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH),
                os.path.join(BENCH, "tools")]

import compare  # noqa: E402
import harness  # noqa: E402
import inputs  # noqa: E402
from reference import nets  # noqa: E402


def reference_logits(ref, cfg, params, batch, q=None):
    """(layers, 2L, experts): the router's logits of every expert layer, by
    the plain reference's own functions (``reference/sdar_lm.py::forward``
    with the logits kept)."""
    import jax
    import jax.numpy as jnp

    eps, s = cfg["rms_norm_eps"], ref.dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.concatenate(
            [batch["tokens"], batch["noised"]], axis=1)]
        out = []
        for i in range(s["layers"]):
            pa = params[ref.layer_name(2 * i)]
            pm = params[ref.layer_name(2 * i + 1)]
            x = ref._r(q, x + ref.attention(
                pa, ref._r(q, ref.rms_norm(x, pa["norm"], eps)), cfg, q))
            u = ref._r(q, ref.rms_norm(x, pm["norm"], eps))
            out.append(u.reshape(-1, s["d"]).astype(jnp.float32)
                       @ pm["router"])
            x = ref._r(q, x + ref.gated_moe(pm, u, cfg, q))
    return jnp.stack(out)


def program_logits(cfg, params, batch):
    """The same of the program's forward pass (its own attention and expert
    layers in the configuration's precision; the router's product as
    ``GatedMoE`` takes it, from the state each expert block is handed)."""
    import jax.numpy as jnp
    from distributedpytorch_tpu.models import build_model
    from distributedpytorch_tpu.models.keye_lm import _dot32
    from distributedpytorch_tpu.models.nemotron_h import layer_name, rms_norm
    from distributedpytorch_tpu.train.precision import precision_policy

    policy = precision_policy(cfg["precision"])
    model = build_model(
        cfg["architecture"], lm_config=cfg,
        dtype=(policy.compute_dtype if policy else cfg["precision"]),
        remat=False)
    layers = cfg["num_hidden_layers"]
    attn = {layer_name(2 * i) for i in range(layers)}
    _, kept = model.apply(
        {"params": params}, batch["tokens"], batch["noised"],
        capture_intermediates=lambda m, _: m.name in attn,
        mutable=["intermediates"])
    out = []
    for i in range(layers):
        u = kept["intermediates"][layer_name(2 * i)]["__call__"][0]
        pm = params[layer_name(2 * i + 1)]
        x = rms_norm(u, pm["norm"], cfg["rms_norm_eps"])
        out.append(_dot32(x.reshape(-1, x.shape[-1]), pm["router"]))
    return jnp.stack(out)


def differences(want, got, k: int, held: range, mask):
    """Per layer: positions whose top-k set differs, among the mask's and
    among the others, and those of them where an expert held here comes or
    goes."""
    import numpy as np

    rows = []
    for zw, zg in zip(np.asarray(want), np.asarray(got)):
        tw = np.argsort(-zw, axis=-1)[:, :k]
        tg = np.argsort(-zg, axis=-1)[:, :k]
        n = zw.shape[-1]
        sw = np.zeros(zw.shape, bool)
        sg = np.zeros(zw.shape, bool)
        np.put_along_axis(sw, tw, True, -1)
        np.put_along_axis(sg, tg, True, -1)
        differ = (sw != sg).any(-1)
        here = (sw != sg)[:, held.start:min(held.stop, n)].any(-1)
        srt = -np.sort(-zw[mask], axis=-1)
        margin = srt[:, k - 1] - srt[:, k]
        common = np.bincount(tw[mask].ravel(), minlength=n) \
            > mask.sum() // 2
        rows.append({
            "mask_differ": int((differ & mask).sum()),
            "mask_differ_held": int((here & mask).sum()),
            "other_differ": int((differ & ~mask).sum()),
            "other_differ_held": int((here & ~mask).sum()),
            "mask_margin_mean": float(margin.mean()),
            "mask_margin_std": float(margin.std()),
            "mask_logit_rms_gap": float(
                np.sqrt(np.mean((zg[mask] - zw[mask]) ** 2))),
            "mask_experts_held": [int(e) for e in np.flatnonzero(common)
                                  if e in held],
        })
    return rows


def main(argv=None, allow_cpu=False, root=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--drawn", action="store_true")
    ap.add_argument("--train", action="store_true")
    args = ap.parse_args(argv)
    ns = argparse.Namespace(workload=args.cell, seed=0, seconds=1, trace=0)
    ctx = harness.Context.load(root or os.path.dirname(BENCH), ns,
                               allow_cpu=allow_cpu, t_start=time.time())
    devices = ctx.acquire_devices()
    import jax
    import numpy as np

    ctx.enable_cache()
    cfg = ctx.config
    kind = harness.load_module(ctx.bench_dir, "kinds", ctx.traffic["kind"])
    mesh, (repl, data), _, _ = kind.cell_layout(ctx, devices)
    ref = kind.reference_of(ctx)
    weights = types.SimpleNamespace(
        make_weights=functools.partial(ref.make_weights,
                                       sharpen_mask_row=not args.drawn),
        make_batch=ref.make_batch)
    make_jit = jax.jit(
        functools.partial(
            kind.make_inputs, cfg=cfg, ref=weights,
            sequences=int(ctx.traffic["per_chip_batch"]) * len(devices),
            seq_len=int(ctx.traffic["seq_len"])),
        out_shardings=(repl, repl, data))

    def make(seed):
        return make_jit(*inputs.seed_words(seed))

    s = ref.dims(cfg)
    held = range(s["e_off"], s["e_off"] + s["e_held"])
    type_q = None if cfg["precision"] == "float32" \
        else nets.Rounding(cfg["precision"])
    forwards = {
        "reference": jax.jit(functools.partial(reference_logits, ref, cfg)),
        "witness": jax.jit(functools.partial(reference_logits, ref, cfg,
                                             q=type_q)),
        "program": jax.jit(functools.partial(program_logits, cfg)),
    }
    out = []

    def say(line):
        print(json.dumps(line), flush=True)
        out.append(line)

    with mesh:
        for seed in args.seeds:
            params, _, batch = make(seed)
            z = {name: np.asarray(f(params, batch), np.float64)
                 for name, f in forwards.items()}
            noised = np.asarray(batch["noised"]).ravel()
            mask = np.concatenate([np.zeros_like(noised, bool),
                                   noised == cfg["vocab_size"] - 1])
            for name in ("program", "witness"):
                say({"cell": args.cell, "seed": seed, "drawn": args.drawn,
                     "what": name + "_against_reference",
                     "mask_positions": int(mask.sum()),
                     "layers": differences(z["reference"], z[name],
                                           s["per_tok"], held, mask)})
            del params, batch, z
        if args.train:
            want = kind.reference_runner(ctx, (repl, data))
            got = kind.reference_runner(ctx, (repl, data), q=type_q)
            for seed in args.seeds:
                one = functools.partial(make, seed)
                nums = compare.numbers(got(one), want(one))
                nums.pop("grad_worst_leaf", None)
                nums.pop("change_worst_leaf", None)
                say({"cell": args.cell, "seed": seed, "drawn": args.drawn,
                     "what": "witness_train_steps", "numbers": nums})
    return out


if __name__ == "__main__":
    main()
