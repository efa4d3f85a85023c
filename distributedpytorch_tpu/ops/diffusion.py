"""The noise of a block-diffusion objective: data, drawn once a step by the
token task's device stage (``train/tasks.py::TOKENS``), never by the model
or the loss.

For a sequence ``x0`` of ``L`` ids in blocks of ``b`` (masked diffusion over
blocks, linear schedule ``alpha_t = 1 - t``): every block ``j`` draws its own
level ``t_j = eps + (1 - eps) u_j``, ``u_j ~ U[0, 1)``; every token ``i`` is
masked with that probability, ``m_i = [u'_i < t_(i // b)]``; the noised copy
holds ``mask_id`` where ``m`` and ``x0`` elsewhere; the loss weighs a masked
token by ``1 / t`` of its block.  The masked set is ``m`` — carried by the
weight, which is positive exactly there — and never ``noised == mask_id``: a
clean token may be the mask's id.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..telemetry import counters

#: the batch keys the stage adds beside ``tokens``
NOISED_KEY = "noised"
LOSS_WEIGHT_KEY = "loss_weight"
#: the floor of a block's noise level (a weight is at most ``1 / eps``)
NOISE_EPS = 1e-3
#: masked tokens over the sequence's length, the fullest sequence
COUNTER_MASKED_SHARE = counters.declare("diffusion_masked_share", "max")


def block_noise(key, tokens: jax.Array, block: int,
                mask_id: int) -> tuple[jax.Array, jax.Array]:
    """``(noised, loss_weight)`` of ``tokens`` (B, L) int32: the noised copy
    and ``m / t`` (float32, 0 where the token stays), one level a block."""
    b, length = tokens.shape
    if length % block:
        raise ValueError(f"blocks of {block} do not tile a sequence of "
                         f"{length}")
    k_level, k_token = jax.random.split(key)
    level = NOISE_EPS + (1.0 - NOISE_EPS) * jax.random.uniform(
        k_level, (b, length // block), jnp.float32)
    level = jnp.repeat(level, block, axis=1)
    masked = jax.random.uniform(k_token, (b, length), jnp.float32) < level
    noised = jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens)
    return noised, jnp.where(masked, 1.0 / level, 0.0)


def noise_stage(block: int, mask_id: int):
    """The step's device stage ``(batch, rng) -> batch`` (``make_train_step``'s
    ``augment``): ``{tokens}`` gains ``noised`` and ``loss_weight``."""
    def stage(batch, rng):
        noised, weight = block_noise(rng, batch["tokens"], block, mask_id)
        return {**batch, NOISED_KEY: noised, LOSS_WEIGHT_KEY: weight}
    return stage


def masked_share(loss_weight: jax.Array) -> jax.Array:
    """:data:`COUNTER_MASKED_SHARE` of a batch's weights."""
    return jnp.max(jnp.mean((loss_weight > 0).astype(jnp.float32), axis=-1))
