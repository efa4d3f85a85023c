"""Device mesh construction and sharding layouts.

This module is the framework's entire "distributed communication backend".
The reference had none in-repo: its inter-device traffic lived inside
``torch.nn.DataParallel`` (reference train_pascal.py:92 — per-step replica
broadcast + scatter/gather on CUDA streams) and the NCCL/DDP backend it
planned in the comment checklist (train_pascal.py:1-8) was never built.

The TPU-native design inverts that: **the mesh is the topology and the
compiler owns communication.** We build one ``jax.sharding.Mesh`` with a
``data`` axis (batch parallelism over ICI) and a reserved ``model`` axis
(tensor parallelism — unused for reference parity but first-class in the
layout so wider models can shard without restructuring).  The train step is
``jit``-compiled with ``NamedSharding`` annotations; GSPMD inserts the
gradient all-reduces the reference's checklist called "DDP" and the
input scatter ``DataParallel`` did by hand.  There is no explicit
scatter/gather/broadcast code anywhere in this framework.

Multi-host: ``initialize_distributed`` wraps ``jax.distributed.initialize``
(the TCP rendezvous the reference sketched as "port setup",
train_pascal.py:8), and ``shard_batch`` uses
``jax.make_array_from_process_local_data`` so each host contributes only its
own shard of the global batch — the "distributed loader sampler" of
train_pascal.py:3, realized in ``data.pipeline.DataLoader``'s
process-sharded index streams.
"""

from __future__ import annotations

import functools
import math
from typing import Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..chaos import sites as chaos_sites
from ..telemetry import feed
from ..telemetry.trace import annotation
from ..telemetry.scopes import INPUT_PLACE

#: canonical axis names, in mesh order
DATA_AXIS = "data"
MODEL_AXIS = "model"


def initialize_distributed(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> None:
    """Multi-host rendezvous (no-op on a single process).

    TPU pods discover topology from the environment, so bare
    ``jax.distributed.initialize()`` is usually enough; the explicit arguments
    cover DCN / non-TPU clusters.
    """
    if num_processes is not None and num_processes > 1 or coordinator_address:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )


def make_mesh_1d(size: int, axis_name: str, devices=None) -> Mesh:
    """A 1-D mesh of ``size`` devices over one named axis, in
    ``jax.devices()`` order so neighbouring mesh coordinates are ICI
    neighbours (single-hop ``ppermute``s for pipeline/ring schedules).
    Backs ``pipeline.make_pipe_mesh`` and ``moe.make_expert_mesh``."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    if devices.size != size:
        raise ValueError(f"{devices.size} devices != {size} {axis_name}s")
    return Mesh(devices.reshape(size), (axis_name,))


def make_mesh(data: int | None = None, model: int = 1,
              devices=None) -> Mesh:
    """A 2-D ``(data, model)`` mesh over all (or the given) devices.

    ``data=None`` means "everything not claimed by ``model``".  Device order
    comes from ``jax.devices()``, which enumerates contiguously over ICI so
    neighbouring mesh coordinates are ICI neighbours and GSPMD collectives
    ride ICI, not DCN.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = devices.size
    if data is None:
        if n % model:
            raise ValueError(f"{n} devices not divisible by model={model}")
        data = n // model
    if data * model != n:
        raise ValueError(f"mesh {data}x{model} != {n} devices")
    return Mesh(devices.reshape(data, model), (DATA_AXIS, MODEL_AXIS))


def make_hybrid_mesh(slices: int, data: int | None = None, model: int = 1,
                     devices=None, process_is_granule: bool | None = None
                     ) -> Mesh:
    """A ``(data, model)`` mesh over a MULTI-SLICE topology (ICI + DCN).

    Multi-slice TPU systems (and any multi-host cluster without a single
    ICI domain) have two networks: fast ICI within a slice, slower DCN
    between slices.  The scaling recipe is hierarchical data parallelism:
    keep the ``model`` axis and the inner factor of the ``data`` axis
    within a slice, and let only the OUTER factor of ``data`` span DCN —
    GSPMD then lowers the gradient all-reduce to an intra-slice reduce
    (ICI), a small cross-slice phase (DCN), and an intra-slice broadcast.

    The returned mesh has the same ``(data, model)`` axis names as
    ``make_mesh``, so every train step, sharding rule, and checkpoint
    layout in this framework works unchanged — the hierarchy lives purely
    in the device ORDER, which ``mesh_utils.create_hybrid_device_mesh``
    arranges so that mesh coordinates varying fastest stay ICI-local.

    ``slices`` is the DCN factor of the data axis; ``data`` the per-slice
    factor (``None`` = everything left).  ``process_is_granule=None``
    auto-detects: device ``slice_index`` attributes when the runtime
    exposes them (real multi-slice TPU), else processes as granules (the
    documented fallback, also what CPU multi-process tests exercise).

    The reference has no counterpart (its parallelism never left one
    host, reference train_pascal.py:92); this completes the DCN half of
    the "NCCL/MPI backend" story TPU-natively (SURVEY.md §2.6, §5.8).
    """
    from jax.experimental import mesh_utils

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if slices < 1 or n % slices:
        raise ValueError(f"{n} devices not divisible into {slices} slices")
    per_slice = n // slices
    if data is None:
        if per_slice % model:
            raise ValueError(
                f"{per_slice} devices/slice not divisible by model={model}")
        data = per_slice // model
    if data * model != per_slice:
        raise ValueError(
            f"per-slice mesh {data}x{model} != {per_slice} devices/slice")
    if slices == 1:
        # one granule: no DCN dimension exists; the plain ICI mesh IS the
        # hybrid mesh (and create_hybrid_device_mesh would reject granule
        # detection on single-slice platforms that expose no slice_index)
        return make_mesh(data=data, model=model, devices=devices)
    if process_is_granule is None:
        # Slice granules when the runtime exposes a real multi-slice
        # structure matching the request; processes when devices carry no
        # slice structure at all (or a single degenerate slice 0, as the
        # multi-process CPU backend does).  A PRESENT-but-mismatched slice
        # structure is a misconfiguration — falling back to hosts there
        # would silently treat intra-slice ICI links as the DCN phase.
        idx = {getattr(d, "slice_index", None) for d in devices}
        on_tpu = any(getattr(d, "platform", None) == "tpu" for d in devices)
        if None in idx or (len(idx) == 1 and not on_tpu):
            # no slice structure at all, or the degenerate all-slice-0
            # of non-TPU backends (multi-process CPU): hosts are the DCN
            # granules
            process_is_granule = True
        elif len(idx) == slices:
            process_is_granule = False
        else:
            # PRESENT slice structure contradicting the request — incl.
            # a real single-slice TPU asked for slices>1, whose hosts
            # are ICI-connected, not DCN
            raise ValueError(
                f"requested slices={slices} but the devices expose "
                f"{len(idx)} distinct slice_index value(s); pass "
                "process_is_granule=True explicitly to group by host "
                "instead")
    arr = mesh_utils.create_hybrid_device_mesh(
        (data, model), (slices, 1), devices,
        process_is_granule=process_is_granule)
    # (slices*data, model): outer (DCN) factor varies slowest, so rows of
    # the data axis within one slice stay contiguous -> ICI-local
    return Mesh(arr.reshape(slices * data, model), (DATA_AXIS, MODEL_AXIS))


def traced_on(mesh: Mesh | None, fn):
    """``fn``, traced with ``mesh`` as JAX's context (abstract) mesh.

    A ``jit`` given ``in_shardings`` partitions over the mesh but does
    not tell the traced code which mesh that is.  Code that cannot be
    auto-partitioned — the Mosaic kernels in :mod:`ops.pallas_attention`
    — reads the context mesh to ``shard_map`` itself onto the local
    batch shard.  All axes stay ``Auto``, so everything else in ``fn``
    partitions under GSPMD exactly as without the context.  ``mesh=None``
    returns ``fn`` unchanged."""
    if mesh is None:
        return fn

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return traced


def batch_spec() -> P:
    """Batch arrays: leading (batch) dim split over ``data``; spatial and
    channel dims replicated (a 512×512 conv input shards naturally on batch
    only — spatial sharding is the ring-attention analogue we reserve for
    long-context work, see ``ops.attention.blocked_position_attention``)."""
    return P(DATA_AXIS)


def replicated_spec() -> P:
    """Parameters / optimizer state / scalars: fully replicated.  For
    reference parity (pure data parallel) params live on every chip; the
    ``model`` axis is where a tensor-parallel partitioning would go."""
    return P()


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, replicated_spec())


def shard_batch(mesh: Mesh, batch: Mapping[str, np.ndarray]) -> dict:
    """Place a host-local batch dict onto the mesh, batch-dim sharded.

    Single-process: a plain ``device_put`` with the batch sharding (XLA slices
    locally).  Multi-process: every host holds only its shard of the global
    batch, so assemble the global array from per-process data — the TPU
    equivalent of the reference's planned distributed sampler + DataParallel
    scatter (train_pascal.py:3,92) with zero data motion (each host's shard is
    already on its own chips).
    """
    sharding = batch_sharding(mesh)
    if jax.process_count() == 1:
        return {k: jax.device_put(v, sharding) for k, v in batch.items()}
    return {
        k: jax.make_array_from_process_local_data(sharding, np.asarray(v))
        for k, v in batch.items()
    }


def prefetch_to_device(batches, mesh: Mesh, size: int = 2,
                       keys: tuple[str, ...] | None = None,
                       transform=None, start: int = 0):
    """Iterate ``batches`` with up to ``size`` of them already placed on the
    mesh (batch-dim sharded) ahead of consumption.

    ``jax.device_put`` is asynchronous, so keeping a small window of batches
    in flight hides the H2D transfer behind the previous step's compute —
    the reference bought the same overlap with DataLoader worker processes +
    ``non_blocking=True`` H2D copies (its checklist item,
    train_pascal.py:5); here the overlap is explicit and sized.

    ``keys`` filters each dict to the device-bound arrays (eval batches
    carry ragged host-side lists that cannot be placed).  ``size=0``
    degrades to synchronous per-step placement.  ``size`` may also be a
    zero-arg callable returning the CURRENT window depth — re-read every
    iteration, so the feed governor's hot resize (data/governor.py)
    applies mid-epoch: a grow admits deeper pipelining immediately, a
    shrink just drains the window to the new bound (never below 1).

    Placement runs on a dedicated thread: ``device_put`` of a large batch
    is far from free on the calling thread (layout/copy work before the
    DMA), and done inline it serializes against the step dispatch this
    prefetcher exists to overlap.  One worker keeps placements ordered.

    ``transform`` is an optional host-side ``batch -> batch`` stage run on
    that same worker thread just before placement (after the ``keys``
    filter would be pointless — it may introduce new keys, so it runs
    first).  Used by data.coalesce_wire to keep the full-batch pack memcpy
    off the dispatch thread.

    ``start`` is the first batch's index in its epoch (a resumed epoch
    starts past 0): what the ``input/place`` span of each batch carries,
    as the loader's ``input/batch`` span of the same batch does.
    """
    import collections
    import concurrent.futures as cf

    def place(i, batch):
        with annotation(INPUT_PLACE, batch=i):
            if transform is not None:
                batch = transform(batch)
            if keys is not None:
                batch = {k: v for k, v in batch.items() if k in keys}
            # chaos seam: latency here is a slow H2D pipe, raised errors
            # are a dying transfer, poisoning tears the host batch
            # pre-placement
            batch = chaos_sites.fire("device/put", payload=batch)
            return shard_batch(mesh, batch)

    if not callable(size) and size <= 0:  # synchronous degradation
        for i, batch in enumerate(batches, start):
            yield place(i, batch)
        return
    live_size = size if callable(size) else (lambda: size)

    futures: collections.deque = collections.deque()

    def fetch():
        """The head placement, counted: was it done when the loop came?"""
        head = futures.popleft()
        feed.COUNTS.fetch += 1
        feed.COUNTS.fetch_ready += head.done()
        return head.result()

    with cf.ThreadPoolExecutor(max_workers=1) as pool:
        try:
            for i, batch in enumerate(batches, start):
                futures.append(pool.submit(place, i, batch))
                while len(futures) > max(1, int(live_size())):
                    yield fetch()
            while futures:
                yield fetch()
        finally:
            # abandoned generator (early break/exception upstream): drop
            # queued placements so shutdown doesn't run them pointlessly
            while futures:
                futures.popleft().cancel()


def pad_to_multiple(batch: Mapping[str, np.ndarray], multiple: int
                    ) -> tuple[dict, int]:
    """Pad the batch dim up to ``multiple`` (device count) by repeating the
    last sample; returns (padded batch, original size).  Needed for the val
    loader's ragged final batch — the train loader drops it instead
    (``drop_last``, matching reference train_pascal.py:161)."""
    first = next(iter(batch.values()))
    n = first.shape[0]
    target = math.ceil(n / multiple) * multiple
    if target == n:
        return dict(batch), n
    pad = target - n
    out = {}
    for k, v in batch.items():
        reps = np.concatenate([v, np.repeat(v[-1:], pad, axis=0)], axis=0)
        out[k] = reps
    return out, n
