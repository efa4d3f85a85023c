"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.5 marks PP "ABSENT" —
its DANet replica fits one GPU), but a complete distributed story needs the
third classic axis next to data (parallel.step) and tensor (parallel.tp)
parallelism, so this framework makes it first-class.

TPU-native construction — no send/recv, no process ranks, no schedules-as-
threads.  A ``pipe`` mesh axis holds one *stage* per device; stage parameters
are one stacked pytree whose leading dim is sharded over that axis (the same
stacked-layer layout LLM pipelining uses for repeated blocks).  Inside
``shard_map`` each device owns its stage's slice, and the GPipe schedule is a
single ``lax.scan`` over ``n_micro + n_stages - 1`` ticks:

* tick t: stage 0 ingests microbatch t (while one exists), every stage applies
  its block to its current activation, and ``lax.ppermute`` shifts activations
  one hop along the ICI ring to the next stage;
* the last stage scatters each finished microbatch into an output buffer;
  a ``psum`` at the end replicates the assembled output (all other stages
  contribute zeros);
* the pipeline bubble (stages idling for ``n_stages - 1`` ticks) is the usual
  GPipe cost — amortized by ``n_micro >> n_stages``.

Everything in the schedule (``scan``, ``ppermute``, masked writes) is
differentiable, so ``jax.grad`` through :func:`make_pipeline_apply` yields
pipeline-parallel *training*: the backward pass runs the ring in reverse
(``ppermute``'s transpose is the inverse permutation) with grads landing on
each stage's own parameter shard.  :func:`make_pipeline_train_step` packages
that into the framework's usual ``(state, batch) -> (state, loss)`` contract.

Stages must be shape-preserving ((mb, ...) -> (mb, ...)) so activations can
ride a fixed ppermute buffer — true for the repeated-block use case this
targets; put shape-changing stems/heads outside the pipelined body (they are
cheap and replicated).
"""

from __future__ import annotations

import functools
from collections.abc import Mapping
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import make_mesh_1d

#: canonical pipeline-stage axis name
PIPE_AXIS = "pipe"


def make_pipe_mesh(stages: int, devices=None) -> Mesh:
    """A 1-D ``(pipe,)`` mesh of ``stages`` devices — each device one stage,
    neighbouring stages ICI neighbours so the per-tick activation shift is a
    single-hop ``collective_permute``."""
    return make_mesh_1d(stages, PIPE_AXIS, devices)


def stage_param_specs(stacked_params: Any) -> Any:
    """PartitionSpec pytree for stacked stage params: leading (stage) dim
    sharded over ``pipe``, rest replicated."""
    return jax.tree.map(
        lambda x: P(*([PIPE_AXIS] + [None] * (x.ndim - 1))), stacked_params)


def pipeline_apply_local(stage_fn: Callable[[Any, jax.Array], jax.Array],
                         stacked_params: Any, microbatches: jax.Array,
                         axis_name: str = PIPE_AXIS) -> jax.Array:
    """Per-device GPipe body.  Call inside ``shard_map``; use
    :func:`make_pipeline_apply` for the meshed wrapper.

    ``stacked_params``: this device's stage slice, leading dim 1 (the
    shard_map split of the (S, ...) stack) — squeezed before ``stage_fn``.
    ``microbatches``: (M, mb, ...) — replicated; every device sees all
    microbatches but only stage 0 ingests them.
    Returns (M, mb, ...) — the last stage's outputs, replicated via psum.
    """
    n_stages = jax.lax.axis_size(axis_name)
    stage_idx = jax.lax.axis_index(axis_name)
    params = jax.tree.map(lambda x: x[0], stacked_params)
    n_micro = microbatches.shape[0]
    n_ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        acts, outputs = carry
        # Stage 0 pulls microbatch t from the feed; later stages consume the
        # activation ppermuted in from their predecessor last tick.
        feed = microbatches[jnp.clip(t, 0, n_micro - 1)]
        inp = jnp.where(stage_idx == 0, feed, acts)
        out = stage_fn(params, inp)
        # The last stage finishes microbatch t-(S-1); masked scatter keeps
        # the write static-shaped (invalid ticks rewrite an existing row).
        out_idx = t - (n_stages - 1)
        safe = jnp.clip(out_idx, 0, n_micro - 1)
        valid = (stage_idx == n_stages - 1) & (out_idx >= 0)
        row = jnp.where(valid, out, outputs[safe])
        outputs = jax.lax.dynamic_update_index_in_dim(outputs, row, safe, 0)
        acts = jax.lax.ppermute(out, axis_name, perm)
        return (acts, outputs), None

    mb_shape = microbatches.shape[1:]
    acts0 = jnp.zeros(mb_shape, microbatches.dtype)
    out0 = jnp.zeros((n_micro,) + mb_shape, microbatches.dtype)
    (_, outputs), _ = jax.lax.scan(
        tick, (acts0, out0), jnp.arange(n_ticks))
    # Only the last stage wrote anything; psum replicates it everywhere.
    return jax.lax.psum(
        jnp.where(stage_idx == n_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)


def _meshed_apply(mesh: Mesh, stage_fn: Callable[[Any, jax.Array], jax.Array],
                  stacked_params: Any, microbatches: jax.Array,
                  axis_name: str) -> jax.Array:
    """The (unjitted) meshed pipeline forward shared by
    :func:`make_pipeline_apply` and :func:`make_pipeline_train_step`."""
    specs = stage_param_specs(stacked_params)
    fn = jax.shard_map(
        functools.partial(pipeline_apply_local, stage_fn,
                          axis_name=axis_name),
        mesh=mesh, in_specs=(specs, P()), out_specs=P(),
        check_vma=False,
    )
    return fn(stacked_params, microbatches)


def make_pipeline_apply(mesh: Mesh,
                        stage_fn: Callable[[Any, jax.Array], jax.Array],
                        axis_name: str = PIPE_AXIS):
    """Jitted ``(stacked_params, microbatches) -> outputs`` over global
    arrays: params' stage dim sharded on ``axis_name``, microbatches and
    outputs replicated.  Differentiable — wrap in ``jax.grad`` for
    pipeline-parallel training."""

    def global_fn(stacked_params, microbatches):
        return _meshed_apply(mesh, stage_fn, stacked_params, microbatches,
                             axis_name)

    return jax.jit(global_fn)


def sequential_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                     stacked_params: Any, x: jax.Array) -> jax.Array:
    """Ground truth for the pipeline: fold ``stage_fn`` over the stage dim on
    one device.  (M, mb, ...) in/out, matching :func:`make_pipeline_apply`."""
    n_stages = jax.tree.leaves(stacked_params)[0].shape[0]
    for s in range(n_stages):
        params = jax.tree.map(lambda p: p[s], stacked_params)
        x = jax.vmap(lambda mb: stage_fn(params, mb))(x)
    return x


def init_stacked_stage_params(rng: jax.Array, block, n_stages: int,
                              sample_input: jax.Array,
                              all_collections: bool = False) -> Any:
    """Stacked params for ``n_stages`` copies of a Flax ``block``: every leaf
    gains a leading stage dim (shard it with :func:`stage_param_specs`).

    Each stage gets its own init key; the block must be shape-preserving.
    ``all_collections=True`` stacks the block's full variables dict (params
    AND e.g. frozen BatchNorm ``batch_stats``) — how the real backbone's
    bottleneck blocks pipeline in inference mode; the default stacks only
    ``params`` (stateless blocks: GroupNorm/LayerNorm).  Pair with
    :func:`flax_stage_fn` using the same flag.
    """
    rngs = jax.random.split(rng, n_stages)

    def init_one(r):
        variables = block.init(r, sample_input)
        return dict(variables) if all_collections else variables["params"]

    return jax.vmap(init_one)(rngs)


def flax_stage_fn(block, all_collections: bool = False
                  ) -> Callable[[Any, jax.Array], jax.Array]:
    """Adapt a Flax module to the ``(stage_params, x) -> y`` contract of
    :func:`make_pipeline_apply` / :func:`make_pipeline_train_step`."""

    def stage_fn(params, x):
        variables = params if all_collections else {"params": params}
        return block.apply(variables, x)

    return stage_fn


def make_pipeline_train_step(mesh: Mesh,
                             stage_fn: Callable[[Any, jax.Array], jax.Array],
                             loss_fn: Callable[[jax.Array, jax.Array],
                                               jax.Array],
                             tx, axis_name: str = PIPE_AXIS):
    """Pipeline-parallel ``((params, opt_state), micro_x, micro_y) ->
    ((params, opt_state), loss)`` step: forward through the GPipe schedule,
    backward through its transpose, optimizer update on each stage's own
    parameter shard (optimizer state inherits the stage sharding — per-stage
    optimizer memory, the PP analogue of tp.py's sharded momentum).

    Every leaf of the stage params is trained — pass only the ``params``
    collection (stateless-norm blocks).  ``all_collections=True`` stacks are
    inference-only and rejected here: the optimizer would silently update
    the frozen BatchNorm running stats they carry.
    """

    def step(carry, micro_x, micro_y):
        params, opt_state = carry
        # A full variables stack (dict OR FrozenDict) always carries a
        # top-level 'params' collection; a bare params tree never does
        # (flax auto-names are Conv_0/BatchNorm_0/...).  Rejecting on that
        # key covers batch_stats and any other non-trainable collection.
        if isinstance(params, Mapping) and "params" in params:
            raise ValueError(
                "stage params look like a full variables dict "
                "(all_collections=True stack) — the optimizer would update "
                "its non-trainable collections (e.g. frozen BN "
                "batch_stats); train with the 'params' collection only "
                "(use stateless norms in pipelined blocks)")

        def objective(p):
            return loss_fn(_meshed_apply(mesh, stage_fn, p, micro_x,
                                         axis_name), micro_y)

        loss, grads = jax.value_and_grad(objective)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), loss

    return jax.jit(step, donate_argnums=(0,))
