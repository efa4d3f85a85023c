"""The compiled train/eval steps — the framework's hot loop.

One function, ``make_train_step``, replaces the reference's whole per-batch
body (reference train_pascal.py:185-226: H2D copy → ``DataParallel`` scatter →
forward → multi-output loss → backward → SGD step) with a single ``jit``'d
program over the mesh:

* the batch arrives batch-dim sharded (``mesh.shard_batch``); every op on it
  is partitioned by GSPMD, so the forward/backward run data-parallel with the
  gradient all-reduce inserted by the compiler — the "DDP" of the reference's
  checklist (train_pascal.py:1-8) with no NCCL code;
* loss, grads, optimizer update and BatchNorm running-stat updates all happen
  on device inside one XLA executable — nothing bounces to host between
  micro-steps;
* gradient accumulation (the reference's ``nAveGrad`` knob whose loop
  machinery was commented out, train_pascal.py:67,215-225) is a
  ``lax.scan`` over micro-batches inside the same program, so accumulation
  costs no extra dispatches;
* under batch sharding, BatchNorm's batch-mean is a mean over a
  GSPMD-partitioned axis — the compiler turns it into a cross-replica
  reduction automatically, so BN statistics are *global-batch* by
  construction.  (The reference used per-replica BN only because syncing was
  hard on GPUs — ``sync_bn=False``, train_pascal.py:85; on TPU the synced
  version is the free default.)

Donation: the previous ``TrainState`` buffers are donated to the step, so
params/opt-state are updated in place in HBM — peak memory is one set of
params + grads, not two.
"""

from __future__ import annotations

from typing import Any, Callable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from flax.core import unfreeze

from ..ops import (
    multi_output_loss,
    next_token_xent,
    se_presence_loss,
    softmax_xent_ignore,
    weighted_token_xent,
)
from ..ops import diffusion
from ..telemetry import counters as counters_lib
from ..telemetry import scopes
from . import mesh as mesh_lib

Batch = Mapping[str, jax.Array]


def _to_compute_dtype(batch: Batch) -> dict:
    """Dequantize uint8 wire-format leaves to float32 on device.

    The host may ship batches as uint8 (data.uint8_transfer: 4x fewer bytes
    over the H2D link, 4x less host memcpy) — values are integer-
    valued [0,255] image channels and {0,1} masks, so the cast is lossless.
    Inside jit the cast fuses into the first consumer and costs ~nothing."""
    return {k: (v.astype(jnp.float32) if v.dtype == jnp.uint8 else v)
            for k, v in batch.items()}


def _unpack_mask_bits(batch: Batch) -> dict:
    """Inverse of the host's ``np.packbits`` wire (data.packbits_masks).

    ``crop_gt`` arrives as ``(B, ceil(H*W/8))`` uint8; H and W come
    statically from the ``concat`` tensor's shape, so everything here is
    shape-static under jit.  MSB-first shifts mirror np.packbits'
    big-endian bit order.  The whole unpack is broadcast/bitwise/reshape —
    XLA fuses it into the mask's first consumer; the win is the 8x smaller
    H2D transfer that already happened."""
    packed = batch[TARGET_KEY]
    h, w = batch[INPUT_KEY].shape[1:3]
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    bits = (packed[:, :, None] >> shifts) & jnp.uint8(1)
    flat = bits.reshape(packed.shape[0], -1)[:, :h * w]
    out = dict(batch)
    out[TARGET_KEY] = flat.reshape(packed.shape[0], h, w, 1)
    return out

#: batch keys consumed by the step — the reference's stringly-typed contract
#: (``sample['concat']`` / ``sample['crop_gt']``, train_pascal.py:187) made
#: explicit in one place.
INPUT_KEY = "concat"
TARGET_KEY = "crop_gt"

#: the batch key of the ``tokens`` task: ``(B, S)`` int32 token ids, input
#: and target at once (what else a token loss reads — a noised copy, a
#: weight — the task's device stage adds: ``ops/diffusion.py``)
TOKENS_KEY = "tokens"
#: the token task's loss types (:data:`LOSSES`): their models read ids and
#: carry no ``batch_stats``
NEXT_TOKEN = "next_token"
BLOCK_DIFFUSION = "block_diffusion"

#: key under which a coalesced batch ships (data.coalesce_wire)
WIRE_KEY = "wire"

#: the device-bound train keys, in wire order — everything else a loader
#: yields (meta, host-side lists) stays on host
DEVICE_KEYS = ("concat", "crop_gt", "crop_void")


def pack_wire(batch: Mapping, keys: tuple[str, ...]) -> tuple[dict, tuple]:
    """Coalesce ``keys`` of a host batch into one ``(B, bytes)`` uint8 buffer.

    One buffer = ONE H2D transfer instead of one per leaf — the fixed
    per-transfer cost is paid once per batch.  Leaves are flattened
    per-sample and concatenated along axis 1, so the batch dim stays the
    leading (sharded) axis.  Returns ``({WIRE_KEY: buf}, spec)`` where ``spec`` is the static
    ``((key, per_sample_shape), ...)`` layout ``unpack_wire`` inverts; a
    batch whose shapes match the spec of a previous call round-trips
    exactly (uint8 is bit-preserved).

    All leaves must already be uint8 — the data.uint8_transfer wire format
    (validated at Trainer init; float leaves would need a bitcast whose
    semantics this deliberately avoids).
    """
    parts, spec = [], []
    for k in keys:
        if k not in batch:
            continue
        v = np.asarray(batch[k])
        if v.dtype != np.uint8:
            raise ValueError(
                f"data.coalesce_wire: leaf {k!r} is {v.dtype}, not uint8 — "
                "the coalesced wire requires data.uint8_transfer's uint8 "
                "batch format")
        parts.append(v.reshape(v.shape[0], -1))
        spec.append((k, tuple(v.shape[1:])))
    if not parts:
        raise ValueError(
            f"pack_wire: none of {keys} present in the batch "
            f"(batch keys: {sorted(batch)})")
    buf = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=1)
    return {WIRE_KEY: np.ascontiguousarray(buf)}, tuple(spec)


def unpack_wire(batch: Batch, spec: tuple) -> dict:
    """Inverse of :func:`pack_wire`, inside jit: static strided slices of
    the ``(B, bytes)`` buffer back into the per-key uint8 leaves.  XLA
    fuses each slice+reshape into the leaf's first consumer, so the
    round-trip costs nothing on device — the win is the single H2D RPC
    that already happened."""
    buf = batch[WIRE_KEY]
    out = {k: v for k, v in batch.items() if k != WIRE_KEY}
    off = 0
    for key, shape in spec:
        n = 1
        for d in shape:
            n *= d
        out[key] = buf[:, off:off + n].reshape((buf.shape[0],) + shape)
        off += n
    if off != buf.shape[1]:
        # a spec from a different wire layout underrunning the buffer
        # would otherwise slice misaligned leaves silently
        raise ValueError(
            f"unpack_wire: spec covers {off} bytes/sample but the buffer "
            f"carries {buf.shape[1]} — spec and wire were built from "
            "different batch layouts")
    return out


def bucket_grad_leaves(leaves: list, n_buckets: int) -> list[list[int]]:
    """Partition gradient-leaf INDICES into ``n_buckets`` byte-balanced
    buckets in reverse-topological order.

    The flattened param tree sorts backbone-before-head; backward
    produces gradients output-side first, so the REVERSED flat order
    approximates the order grads become available during the backward
    pass.  Bucket 0 therefore holds the head/classifier grads — the
    ones ready earliest — and its all-reduce is schedulable while the
    backbone backward is still computing: the bucketed-overlap recipe
    of "Efficient Training of CNNs on Large Distributed Systems"
    (arxiv 1711.00705), expressed as dataflow XLA's latency-hiding
    scheduler can exploit.  Buckets are cut at byte-balanced boundaries
    so no single reduce dominates the tail."""
    if n_buckets < 1:
        raise ValueError(f"reduce_buckets must be >= 1 (got {n_buckets})")
    order = list(range(len(leaves)))[::-1]
    sizes = [int(np.prod(leaves[i].shape, dtype=np.int64))
             * jnp.dtype(leaves[i].dtype).itemsize for i in order]
    total = sum(sizes)
    n_buckets = min(n_buckets, len(order)) or 1
    per = max(1, total // n_buckets)
    buckets: list[list[int]] = []
    cur: list[int] = []
    acc = 0
    for i, s in zip(order, sizes):
        cur.append(i)
        acc += s
        if acc >= per and len(buckets) < n_buckets - 1:
            buckets.append(cur)
            cur, acc = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def _bucketed_psum(grads, n_buckets: int, axis_name: str):
    """All-reduce a gradient pytree in reverse-topo buckets: one
    ``lax.psum`` per bucket (independent equations — no dataflow edge
    forces bucket K to wait for bucket K-1, so async lowerings overlap
    them with each other and with the still-running backward that feeds
    the later buckets)."""
    leaves, treedef = jax.tree.flatten(grads)
    out = list(leaves)
    for k, bucket in enumerate(bucket_grad_leaves(leaves, n_buckets)):
        with jax.named_scope(scopes.bucket_scope(k)):
            reduced = jax.lax.psum([leaves[i] for i in bucket], axis_name)
        for i, g in zip(bucket, reduced):
            out[i] = g
    return jax.tree.unflatten(treedef, out)


class TrainState(struct.PyTreeNode):
    """Everything that evolves during training, as one pytree.

    Unlike the reference — which persisted only ``net.state_dict()`` and lost
    optimizer/epoch/RNG state on every restart (train_pascal.py:301-304, §3.5
    of SURVEY.md) — the full state is one checkpointable object.
    """

    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: optax.OptState
    rng: jax.Array


def create_train_state(
    rng: jax.Array,
    model,
    tx: optax.GradientTransformation,
    input_shape: tuple[int, ...],
    mesh=None,
    shard_params: bool = False,
    shard_opt_state: bool = False,
    input_dtype=jnp.float32,
) -> TrainState:
    """Initialize params/batch-stats with a dummy batch and wrap with the
    optimizer state.  ``input_shape`` is (N, H, W, C) — NHWC, the TPU-native
    layout (the reference's NCHW ``ToTensor`` transpose has no analogue
    here; conv layouts are XLA's concern).

    With ``mesh``, every leaf is created directly as a *global* array.
    Multi-host this is required: a host-local single-device array is
    neither a valid input to the sharded train step nor serializable by
    Orbax's coordinated save.

    ``shard_params=True`` turns on tensor parallelism: kernel output
    channels are partitioned over the ``model`` axis (see
    :mod:`parallel.tp`); momentum inherits the layout through propagation.

    ``shard_opt_state=True`` is the ZeRO-1 layout: optimizer-state leaves
    partitioned over the ``data`` axis (:mod:`parallel.zero`), composing
    with the TP layout when both are on.  Default is fully replicated —
    the reference-parity data-parallel state.

    ``input_dtype``: ``int32`` for a token model, whose ``input_shape`` is
    (N, S) token ids.
    """
    if shard_opt_state and mesh is None:
        raise ValueError("shard_opt_state requires a mesh (the data axis "
                         "it shards over)")
    init_rng, state_rng = jax.random.split(rng)

    def make_state():
        variables = model.init(init_rng, jnp.zeros(input_shape, input_dtype),
                               train=False)
        params = unfreeze(variables["params"])
        batch_stats = unfreeze(variables.get("batch_stats", {}))
        opt_state = tx.init(params)
        from .tp import constrain, tp_param_specs
        opt_base = None
        if mesh is not None and shard_params:
            params = constrain(params, mesh, tp_param_specs(params, mesh))
            # Momentum traces share the kernels' shapes, so the same
            # shape-based rule shards optimizer memory identically.
            opt_base = tp_param_specs(opt_state, mesh)
        if mesh is not None and shard_opt_state:
            from .zero import zero_opt_specs
            # ZeRO-1 on top of whatever TP pinned: `data` goes on each
            # leaf's largest still-free divisible dimension.
            opt_state = constrain(
                opt_state, mesh,
                zero_opt_specs(opt_state, mesh, base_specs=opt_base))
        elif opt_base is not None:
            opt_state = constrain(opt_state, mesh, opt_base)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
            rng=state_rng,
        )

    if mesh is None:
        return make_state()
    if not (shard_params or shard_opt_state):
        return jax.jit(make_state,
                       out_shardings=mesh_lib.replicated_sharding(mesh))()
    # Sharded layouts: let XLA propagate the constrained layouts; pin the
    # small unconstrained leaves (step/rng/batch_stats) to replicated
    # afterwards via an identity reshard where needed.
    with mesh:
        state = jax.jit(make_state)()
    repl = mesh_lib.replicated_sharding(mesh)
    fixed = state.replace(
        step=jax.device_put(state.step, repl),
        rng=jax.device_put(state.rng, repl),
        batch_stats=jax.tree.map(
            lambda x: jax.device_put(x, repl), state.batch_stats),
    )
    if not shard_params:
        # ZeRO-only: params must stay replicated (XLA may have propagated
        # the opt-state layout backward into the init graph)
        fixed = fixed.replace(params=jax.tree.map(
            lambda x: jax.device_put(x, repl), fixed.params))
    return fixed


def _check_weights(weights, outputs) -> None:
    if weights is not None and len(weights) != len(outputs):
        # zip would silently truncate — e.g. EncNet's (map, aux, se) tuple
        # under loss_weights=[1.0,0.4] would drop the SE-presence loss and
        # never train the context-encoding branch
        raise ValueError(
            f"model.loss_weights has {len(weights)} entries but the model "
            f"emits {len(outputs)} outputs — give every output a weight")


def _multi_sigmoid_loss(outputs, batch: Batch, weights):
    """The reference's weighted multi-output balanced BCE (binary
    interactive segmentation, SegmentationMultiLosses semantics)."""
    _check_weights(weights, outputs)
    inputs, target = batch[INPUT_KEY], batch[TARGET_KEY]
    void = batch.get("crop_void")
    if target.ndim == inputs.ndim - 1:  # (B,H,W) vs (B,H,W,C) logits
        target = target[..., None]
    if void is not None and void.ndim == inputs.ndim - 1:
        void = void[..., None]
    return multi_output_loss(outputs, target, void=void, weights=weights)


def _multi_softmax_loss(outputs, batch: Batch, weights):
    """Per-output softmax CE with ignore_index=255 (the multi-class
    DeepLabV3 configs; aux outputs default to 0.4 weight)."""
    _check_weights(weights, outputs)
    labels = batch[TARGET_KEY]
    if labels.ndim == outputs[0].ndim:  # squeeze trailing channel axis
        labels = labels[..., 0]
    labels = labels.astype(jnp.int32)
    if weights is None:
        # map aux heads 0.4 (DeepLab recipe); a 2D SE output gets the
        # EncNet paper's 0.2
        weights = (1.0,) + tuple(
            0.2 if o.ndim == 2 else 0.4 for o in outputs[1:])
    total = jnp.float32(0.0)
    for out, w in zip(outputs, weights):
        if out.ndim == 2:
            # (B, C) vector head: EncNet's semantic-encoding branch —
            # class-presence BCE, not a per-pixel CE
            total = total + w * se_presence_loss(out, labels)
        else:
            total = total + w * softmax_xent_ignore(out, labels)
    return total


def _next_token_loss(outputs, batch: Batch, weights):
    """A token model's ``(logits, mtp_logits...)``: output ``k`` is scored
    against the token ``k + 1`` places on; the weights are ``(1, lambda,
    ...)`` and every multi-token-prediction output needs one."""
    if weights is None:
        weights = (1.0,) * len(outputs)
    if len(weights) != len(outputs):
        raise ValueError(
            f"loss weights {tuple(weights)} for {len(outputs)} token "
            "outputs — give the next-token head and every "
            "multi-token-prediction head a weight")
    tokens = batch[TOKENS_KEY]
    total = jnp.float32(0.0)
    for k, (out, w) in enumerate(zip(outputs, weights)):
        total = total + w * next_token_xent(out, tokens, shift=k + 1)
    return total


def _block_diffusion_loss(outputs, batch: Batch, weights):
    """A block-diffusion model's one output, the logits over the noised
    copy's positions: position ``i`` is scored against token ``i`` with the
    weight the noise gave it (``ops/diffusion.py``), no shift."""
    (logits,) = outputs
    return weighted_token_xent(logits, batch[TOKENS_KEY],
                               batch[diffusion.LOSS_WEIGHT_KEY])


class Loss(NamedTuple):
    """What a loss type is to the steps."""
    #: the batch keys handed to the model, in order
    inputs: tuple
    #: ``(outputs, batch, weights) -> scalar`` over the model's output tuple
    loss: Callable
    #: a token model's: ids in (nothing to cast), no ``batch_stats``, the
    #: GSPMD step only, an evaluation that hands back the loss alone
    tokens: bool = False
    #: ``(batch) -> {declared counter: scalar}`` of the batch itself, handed
    #: back beside what the model sowed
    counters: Callable | None = None


#: loss type -> :class:`Loss`: the one table that ``_compute_loss``,
#: ``_loss_and_updates``, ``make_train_step`` and ``make_eval_step`` read; a
#: new objective is one entry
LOSSES = {
    "multi_sigmoid": Loss((INPUT_KEY,), _multi_sigmoid_loss),
    "multi_softmax": Loss((INPUT_KEY,), _multi_softmax_loss),
    NEXT_TOKEN: Loss((TOKENS_KEY,), _next_token_loss, tokens=True),
    BLOCK_DIFFUSION: Loss(
        (TOKENS_KEY, diffusion.NOISED_KEY), _block_diffusion_loss,
        tokens=True,
        counters=lambda batch: {
            diffusion.COUNTER_MASKED_SHARE: diffusion.masked_share(
                batch[diffusion.LOSS_WEIGHT_KEY])}),
}


def loss_of(loss_type: str) -> Loss:
    if loss_type not in LOSSES:
        raise ValueError(f"unknown loss_type: {loss_type!r} "
                         f"({' | '.join(LOSSES)})")
    return LOSSES[loss_type]


def _compute_loss(outputs, batch: Batch, weights, loss_type: str):
    """Loss over a model's output tuple, by the entry of :data:`LOSSES`."""
    return loss_of(loss_type).loss(outputs, batch, weights)


def _loss_and_updates(model, params, batch_stats, batch: Batch, rng,
                      loss_weights, train: bool, loss_type: str,
                      aux_loss_weight: float = 0.0, precision=None):
    """Forward + loss; returns (loss, new_batch_stats, counters).

    ``counters``: what the model sowed into its ``counters`` collection
    (``telemetry/counters.py``), each name combined over the layers that
    sowed it; ``{}`` for a model that sows none.

    ``aux_loss_weight`` scales any auxiliary losses the model ``sow``s into
    its ``losses`` collection (e.g. the MoE router's load-balancing term,
    parallel/moe.py) into the training objective.

    ``precision`` (train.precision policy): the two declared dtype
    boundaries of the mixed regime live HERE — inputs cast down to the
    compute dtype before the model (halving the input tensor's HBM
    read; the first conv would cast anyway, after paying f32 bytes) and
    outputs cast up to the loss dtype after it (the explicit
    bf16-compute → f32-loss accumulation seam JA002 audits).  The loss
    kernels upcast defensively regardless; under a policy the boundary
    is explicit and auditable.
    """
    variables = {"params": params, "batch_stats": batch_stats}
    counters = {}
    entry = loss_of(loss_type)
    inputs = tuple(batch[k] for k in entry.inputs)
    if precision is not None and not entry.tokens:  # ids: nothing to cast
        inputs = tuple(map(precision.cast_to_compute, inputs))
    if train:
        outputs, mutated = model.apply(
            variables, *inputs, train=True,
            mutable=["batch_stats", "losses", counters_lib.COLLECTION],
            rngs={"dropout": rng},
        )
        new_stats = unfreeze(mutated.get("batch_stats", {}))
        aux = sum((jnp.sum(x) for x in
                   jax.tree.leaves(mutated.get("losses", {}))),
                  jnp.float32(0.0))
        counters = counters_lib.reduce_sown(
            mutated.get(counters_lib.COLLECTION, {}))
        if entry.counters is not None:
            counters.update(entry.counters(batch))
    else:
        outputs = model.apply(variables, *inputs, train=False)
        new_stats = batch_stats
        aux = jnp.float32(0.0)
    with jax.named_scope(scopes.LOSS):
        if precision is not None:
            outputs = precision.cast_to_loss(outputs)
        loss = _compute_loss(outputs, batch, loss_weights, loss_type)
        if aux_loss_weight:
            loss = loss + aux_loss_weight * aux
    return loss, new_stats, counters


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    loss_weights: tuple[float, ...] | None = None,
    accum_steps: int = 1,
    mesh=None,
    donate: bool = True,
    loss_type: str = "multi_sigmoid",
    augment: Callable[[Batch, jax.Array], Batch] | None = None,
    state_shardings=None,
    aux_loss_weight: float = 0.0,
    loss_scale: float = 1.0,
    steps_per_call: int = 1,
    packbits_masks: bool = False,
    wire_spec: tuple | None = None,
    sentinel_metrics: bool = False,
    precision=None,
    reduce_buckets: int = 0,
) -> Callable[..., tuple[TrainState, jax.Array]]:
    """Build the jitted ``(state, batch) -> (state, loss)`` train step.

    ``state_shardings``: a sharding pytree shaped like the state (e.g.
    ``tp.state_shardings(state)``) for tensor-parallel layouts; ``None``
    keeps the replicated data-parallel default.

    With ``accum_steps > 1`` the global batch is split into that many
    micro-batches and scanned, averaging gradients — BASELINE.json config 5's
    "grad-accum to global batch 256" path.  The micro-batch dim stays sharded
    over ``data``, so each scan iteration is itself data-parallel.

    ``augment`` is an optional on-device ``(batch, rng) -> batch`` stage
    (see ops.augment) traced into the same program — flip/crop/normalize
    fuse into the forward pass and cost ~nothing.

    ``loss_scale`` (static loss scaling, optim.loss_scale): the backward
    pass differentiates ``loss * scale`` and the gradients are divided back
    — numerically a no-op in exact arithmetic, but it lifts tiny
    activations-gradients above the underflow floor in low-precision
    regimes.  The returned loss is always unscaled.

    ``steps_per_call > 1`` returns a MULTI-step program instead:
    ``(state, b1, ..., bK) -> (state, (K,) losses)`` — K full optimizer
    steps scanned inside one executable (data.steps_per_dispatch), cutting
    per-step dispatch overhead K-fold on dispatch-bound hosts.

    ``wire_spec`` (data.coalesce_wire): the step consumes a coalesced
    ``{WIRE_KEY: (B, bytes) uint8}`` batch and restores the named leaves
    with :func:`unpack_wire` before any other stage — composes with
    ``packbits_masks`` (the packed row rides the buffer) and with the
    multi-step program (the scan body unpacks each step's buffer).

    ``sentinel_metrics`` (sentinel.monitor_grads): the step's second
    output becomes ``(loss, aux)`` with ``aux = [grad_norm,
    ||update||/||param||]`` — the divergence signals the step-health
    sentinel judges.  Both norms are computed from arrays the update
    already produced, so the cost is a handful of fused reductions; the
    readback stays on the trainer's existing loss-fetch boundary (no
    extra host syncs).  Multi-step programs return ``((K,), (K, 2))``.

    A token loss type (the ``tokens`` task; :data:`LOSSES`): the batch is
    ``{"tokens": (B, S) int32}`` — with ``noised`` and ``loss_weight`` under
    ``block_diffusion``, which ``augment`` (the task's device stage) or the
    caller has added — and the model has no ``batch_stats``.

    A model that sows into its ``counters`` collection
    (``telemetry/counters.py``; an expert layer's dropped tokens and load)
    makes the step's second output ``(loss, counters)`` — the dict of every
    name sown, combined over layers and micro-batches — after ``aux`` where
    ``sentinel_metrics`` is on too.  A model that sows none leaves the
    output as it was.

    ``precision`` (train.precision policy, train/precision.py): the
    mixed-precision dtype boundaries — inputs cast to the compute dtype
    at the model, outputs upcast to f32 at the loss.  The model itself
    must be built with ``dtype=policy.compute_dtype`` (the trainer
    couples both from one knob); grads/optimizer math stay f32 because
    the master params are f32 — nothing here to get wrong.

    ``reduce_buckets > 0`` (train.reduce_buckets): the gradient
    all-reduce is restructured for comm/compute overlap.  The
    forward+backward run per-device inside a ``shard_map`` over the
    ``data`` axis (each device differentiates ITS batch shard — local
    grads, exactly DDP's structure) and the grads are then explicitly
    ``psum``-reduced in ``reduce_buckets`` reverse-topological buckets:
    bucket 0 (head params, produced earliest in backward) has no
    dataflow dependence on the backbone backward still running, so an
    async-collective backend (TPU: all-reduce-start/-done + the
    latency-hiding scheduler) overlaps its reduce with the remaining
    compute instead of serializing one fused all-reduce after the whole
    backward.  Semantics shift to DDP's: the loss is the mean of
    per-shard losses (per-shard normalization — balanced-BCE
    denominators are shard-local), dropout draws per-device streams,
    and BN batch stats must psum explicitly — the model MUST be built
    with ``bn_cross_replica_axis='data'`` (validated).  Composes with
    accum/echo/multi-step/wire stages AND with ZeRO-1
    (``plan.BUCKET_COMPATIBLE``: the shard_map region owns only the
    replicated params and the batch shard, while ZeRO's data-sharded
    optimizer leaves live entirely in the update OUTSIDE it — GSPMD
    partitions that elementwise update over the shards as usual).  NOT
    with tensor parallelism or ring PAM: model-axis-sharded params
    cannot enter the region's replicated in_specs, and per-device
    fwd/bwd over sharded kernels would be a different algorithm, not a
    layout — rejected through the planner with the nearest supported
    strategy named.
    """
    if reduce_buckets:
        from .plan import PlanError, reduce_buckets_conflict, \
            shardings_use_axis

        if loss_of(loss_type).tokens:
            raise PlanError(
                "train.reduce_buckets is the convolutional nets' bucketed "
                "reduce (cross-replica BatchNorm inside shard_map); the "
                "tokens task runs the GSPMD step — drop "
                "train.reduce_buckets")
        if mesh is None:
            raise ValueError("reduce_buckets needs a mesh (the data axis "
                             "the buckets psum over)")
        if mesh_lib.MODEL_AXIS in mesh.shape and \
                mesh.shape[mesh_lib.MODEL_AXIS] > 1:
            raise PlanError(
                "train.reduce_buckets needs a 1-wide model axis: the "
                "shard_map region owns the data axis and would "
                "silently replicate compute across a live model axis "
                f"(mesh is {dict(mesh.shape)}) — use "
                "parallel.strategy=dp or dp_zero1, or drop "
                "train.reduce_buckets for model-axis plans")
        if state_shardings is not None and \
                shardings_use_axis(state_shardings, mesh_lib.MODEL_AXIS):
            # TP layout: route the rejection through the planner so the
            # error names the nearest strategy that keeps the buckets
            raise reduce_buckets_conflict(
                "dp_tp_zero1" if shardings_use_axis(
                    state_shardings, mesh_lib.DATA_AXIS) else "dp_tp")
        if getattr(model, "bn_cross_replica_axis", None) != \
                mesh_lib.DATA_AXIS:
            raise ValueError(
                "reduce_buckets runs the forward per-device inside "
                "shard_map, so BatchNorm batch stats must reduce "
                "explicitly: build the model with "
                f"bn_cross_replica_axis={mesh_lib.DATA_AXIS!r} (the "
                "trainer couples this automatically)")

    def grads_of(params, batch_stats, batch, rng):
        def loss_fn(p):
            loss, new_stats, counters = _loss_and_updates(
                model, p, batch_stats, batch, rng, loss_weights, train=True,
                loss_type=loss_type, aux_loss_weight=aux_loss_weight,
                precision=precision)
            return loss * loss_scale, (loss, new_stats, counters)
        (_, (loss, new_stats, counters)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if loss_scale != 1.0:
            with jax.named_scope(scopes.OPTIMIZER):
                grads = jax.tree.map(lambda g: g / loss_scale, grads)
        return (loss, counters), new_stats, grads

    def accum_grads_of(params, batch_stats, batch, rng):
        """(loss, new_stats, grads) over the (possibly accumulated)
        batch — the whole differentiation stage, shared verbatim by the
        GSPMD path and the shard_map body (where ``batch`` is the
        device-local shard and the grads come back unreduced)."""
        if accum_steps == 1:
            return grads_of(params, batch_stats, batch, rng)
        # (B, ...) -> (accum, B/accum, ...): scan carries running grad
        # sum + evolving BN stats; XLA keeps it one fused program.
        def resh(x):
            return x.reshape((accum_steps, x.shape[0] // accum_steps)
                             + x.shape[1:])
        micro = jax.tree.map(resh, dict(batch))
        rngs = jax.random.split(rng, accum_steps)
        zero_grads = jax.tree.map(jnp.zeros_like, params)

        def body(carry, xs):
            gsum, stats = carry
            mb, r = xs
            loss, new_stats, g = grads_of(params, stats, mb, r)
            gsum = jax.tree.map(jnp.add, gsum, g)
            return (gsum, new_stats), loss

        (gsum, new_stats), (losses, counters) = jax.lax.scan(
            body, (zero_grads, batch_stats), (micro, rngs))
        grads = jax.tree.map(lambda g: g / accum_steps, gsum)
        counters = {k: counters_lib.combine(k, v)
                    for k, v in counters.items()}
        return (losses.mean(), counters), new_stats, grads

    def bucketed_grads_of(params, batch_stats, batch, rng):
        """The shard_map twin of :func:`accum_grads_of`: per-device
        fwd+bwd over the local batch shard, then the reverse-topo
        bucketed psum.  Gradients come back pmean'd (psum / axis size —
        DDP averaging), the loss as the mean of per-shard losses; BN
        stats reduced inside the model (bn_cross_replica_axis)."""
        from jax.sharding import PartitionSpec as P

        def body(params, batch_stats, batch, rng):
            # de-correlate per-device dropout/augment draws: each shard
            # is a different slice of the batch and must not share masks
            rng = jax.random.fold_in(
                rng, jax.lax.axis_index(mesh_lib.DATA_AXIS))
            (loss, _), new_stats, grads = accum_grads_of(
                params, batch_stats, batch, rng)
            n = jax.lax.axis_size(mesh_lib.DATA_AXIS)
            with jax.named_scope(scopes.GRAD_REDUCE):
                grads = _bucketed_psum(grads, reduce_buckets,
                                       mesh_lib.DATA_AXIS)
                grads = jax.tree.map(lambda g: g / n, grads)
                loss = jax.lax.pmean(loss, mesh_lib.DATA_AXIS)
            # new_stats are already identical across devices (the model's
            # cross-replica BN pmean'd them) — returned replicated as-is
            return loss, new_stats, grads

        loss, new_stats, grads = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(mesh_lib.DATA_AXIS), P()),
            out_specs=(P(), P(), P()),
            check_vma=False)(params, batch_stats, batch, rng)
        return (loss, {}), new_stats, grads

    def step_fn(state: TrainState, batch: Batch):
        if wire_spec is not None:
            # first: the coalesced buffer (data.coalesce_wire) restores the
            # named leaves every later stage keys on
            batch = unpack_wire(batch, wire_spec)
        if packbits_masks:
            # before the dtype pass: the packed row must stay integer for
            # the bit shifts (data.packbits_masks wire)
            batch = _unpack_mask_bits(batch)
        batch = _to_compute_dtype(batch)
        rng, new_rng = jax.random.split(state.rng)
        if augment is not None:
            rng, aug_rng = jax.random.split(rng)
            batch = augment(batch, aug_rng)
        differentiate = bucketed_grads_of if reduce_buckets \
            else accum_grads_of
        (loss, counters), new_stats, grads = differentiate(
            state.params, state.batch_stats, dict(batch), rng)

        with jax.named_scope(scopes.OPTIMIZER):
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            new_params = optax.apply_updates(state.params, updates)
            if sentinel_metrics:
                # sentinel.monitor_grads: global grad norm + the
                # update/param ratio (a single update rewriting a
                # macroscopic fraction of the weights is divergence even at
                # a plausible loss)
                gnorm = optax.global_norm(grads)
                ratio = optax.global_norm(updates) / (
                    optax.global_norm(state.params) + 1e-12)
                loss = (loss, jnp.stack([gnorm, ratio]))
        if len(counters):   # which names were sown is structure, not value
            loss = (*loss, counters) if sentinel_metrics \
                else (loss, counters)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt,
            rng=new_rng,
        )
        return new_state, loss

    if steps_per_call > 1:
        # Multi-step dispatch: K optimizer steps in ONE compiled call — a
        # lax.scan over K batches passed as separate (batch-sharded) args
        # and stacked at trace time.  Per-step dispatch overhead drops
        # K-fold; losses come back as a (K,) vector.  The scan body IS
        # step_fn, so semantics (BN stats, RNG advance, schedules, accum)
        # are exactly K sequential steps.
        def multi_fn(state: TrainState, *batches: Batch):
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)

            def body(st, b):
                st, loss = step_fn(st, b)
                return st, loss

            state, losses = jax.lax.scan(body, state, stacked)
            return state, losses
    else:
        multi_fn = None

    if mesh is None:
        if multi_fn is not None:
            return jax.jit(multi_fn, donate_argnums=(0,) if donate else ())
        return jax.jit(step_fn, donate_argnums=(0,) if donate else ())

    repl = mesh_lib.replicated_sharding(mesh)
    data = mesh_lib.batch_sharding(mesh)
    if state_shardings is None:
        state_in, state_out = repl, repl
    else:
        # TP (or any custom layout): consume and produce the state exactly
        # as created — params stay model-axis sharded across steps.
        state_in = state_out = state_shardings
    if multi_fn is not None:
        return jax.jit(
            mesh_lib.traced_on(mesh, multi_fn),
            in_shardings=(state_in,) + (data,) * steps_per_call,
            out_shardings=(state_out, repl),
            donate_argnums=(0,) if donate else (),
        )
    return jax.jit(
        mesh_lib.traced_on(mesh, step_fn),
        in_shardings=(state_in, data),
        out_shardings=(state_out, repl),
        donate_argnums=(0,) if donate else (),
    )


def make_eval_step(model, loss_weights: tuple[float, ...] | None = None,
                   mesh=None, loss_type: str = "multi_sigmoid",
                   preprocess: Callable[[Batch], Batch] | None = None,
                   state_shardings=None, packbits_masks: bool = False):
    """Jitted ``(state, batch) -> (outputs, loss)`` inference step
    (reference val loop body, train_pascal.py:245-254).  Outputs are the
    model's logit tuple; sigmoid/thresholding happen in the evaluator, which
    needs probabilities host-side for the full-res paste-back anyway.

    ``packbits_masks`` mirrors the train step's 1-bit ``crop_gt`` wire for
    the prepared val path (data.val_prepared + data.packbits_masks): the
    mask is 25% of the 3-channel uint8 val batch's bytes.

    A token loss type: ``(state, {"tokens", ...}) -> ((), loss)`` — the
    token task's loss alone over the model's evaluation outputs (no
    prediction module, no logits handed back: they are the vocabulary times
    the batch); ``preprocess`` adds what the loss reads beside the ids (a
    block-diffusion loss's noise, from a fixed key)."""
    entry = loss_of(loss_type)

    def token_step_fn(state: TrainState, batch: Batch):
        if preprocess is not None:
            batch = preprocess(batch)
        outputs = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            *(batch[k] for k in entry.inputs), train=False)
        with jax.named_scope(scopes.LOSS):
            return (), entry.loss(outputs, batch, None)

    def step_fn(state: TrainState, batch: Batch):
        if packbits_masks:
            batch = _unpack_mask_bits(batch)
        batch = _to_compute_dtype(batch)
        if preprocess is not None:  # must mirror the train augment's
            batch = preprocess(batch)  # deterministic normalization
        variables = {"params": state.params,
                     "batch_stats": state.batch_stats}
        outputs = model.apply(variables, batch[INPUT_KEY], train=False)
        with jax.named_scope(scopes.LOSS):
            loss = _compute_loss(outputs, batch, loss_weights, loss_type)
        return outputs, loss

    if entry.tokens:
        step_fn = token_step_fn
    if mesh is None:
        return jax.jit(step_fn)
    repl = mesh_lib.replicated_sharding(mesh)
    data = mesh_lib.batch_sharding(mesh)
    state_in = repl if state_shardings is None else state_shardings
    return jax.jit(mesh_lib.traced_on(mesh, step_fn),
                   in_shardings=(state_in, data), out_shardings=(data, repl))
