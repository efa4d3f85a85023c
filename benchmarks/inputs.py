"""Weights, optimizer key and batch of a run, all from ``--seed``, made on the
device in one jitted call.  The program under test and the plain reference
are both handed what this makes; neither makes its own."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import nets


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """``--seed`` may exceed 31 bits: split it into two words for the key."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must not be negative")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def make_inputs(lo, hi, cfg: dict, rows: int):
    """``(params, batch_stats, state_key, batch)``.  Image channels are whole
    numbers 0..255 as decoded pixels are (exact in bfloat16), every row
    differs, DANet's target is a 30% foreground mask and the semantic target
    has 5% void (255) pixels."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
    kw, kx, ky, kv, kr = jax.random.split(key, 5)
    params, stats = nets.make_weights(kw, cfg)
    size, c = cfg["crop_size"], cfg["in_channels"]
    x = jnp.floor(jax.random.uniform(kx, (rows, size, size, c)) * 256.0)
    if cfg["loss"] == "multi_sigmoid":
        y = (jax.random.uniform(ky, (rows, size, size)) > 0.7)
    else:
        y = jax.random.randint(ky, (rows, size, size), 0, cfg["num_classes"])
        y = jnp.where(jax.random.uniform(kv, y.shape) < 0.05, 255, y)
    batch = {"concat": x.astype(jnp.float32), "crop_gt": y.astype(jnp.float32)}
    return params, stats, kr, batch
