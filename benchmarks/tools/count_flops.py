"""Derive ``model_flops_per_image`` of a configuration from the plain
reference: XLA's ``cost_analysis()`` of the lowered ``value_and_grad`` of the
reference's loss at batch 1, float32, no rematerialisation, no kernels.  Runs
on any backend (``JAX_PLATFORMS=cpu python benchmarks/tools/count_flops.py
benchmarks/configs/<name>.json``); the number goes into the configuration's
file by hand, with this derivation beside it."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from reference import nets  # noqa: E402


def main(path):
    with open(path) as f:
        cfg = json.load(f)
    size, c = cfg["crop_size"], cfg["in_channels"]
    params, stats = jax.eval_shape(
        lambda k: nets.make_weights(k, cfg), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, size, size, c), jnp.float32)
    y = jax.ShapeDtypeStruct((1, size, size), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    def loss(p, s, x, y, k):
        outs, _ = nets.forward(cfg, p, s, x, k, None, remat=False)
        return nets.loss_of(cfg, outs, y)

    lowered = jax.jit(jax.value_and_grad(loss)).lower(params, stats, x, y, key)
    cost = lowered.cost_analysis()
    n_params = sum(v.size for v in jax.tree.leaves(params))
    print(json.dumps({"config": cfg["name"], "flops_fwd_bwd_batch1":
                      cost["flops"], "parameters": n_params,
                      "backend": jax.default_backend()}))


if __name__ == "__main__":
    main(sys.argv[1])
