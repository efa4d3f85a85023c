"""The sharding-strategy planner: one declarative knob for the parallelism zoo.

The axes this package grew one at a time — batch sharding over ``data``
(:mod:`mesh`), tensor parallelism over ``model`` (:mod:`tp`), ZeRO-1
optimizer-state sharding (:mod:`zero`), hierarchical multi-slice meshes
(:func:`mesh.make_hybrid_mesh`) — each work, but composing them meant
hand-wiring four low-level ``mesh.*`` booleans plus the matching
``state_shardings`` and step kwargs, and nothing validated the result.
This module makes the composition declarative: the ``parallel`` config
section names a **strategy** and the planner resolves it into a
validated, executable :class:`Plan` —

* the mesh shape (``data x model``, hybrid over DCN slices when
  ``mesh.slices > 1``);
* the composed state layout: ``tp_param_specs`` over ``model`` and
  ``zero_opt_specs`` over ``data`` merged on ONE spec tree (the two
  rules were individually green since their PRs but never combined into
  a single source of truth);
* the matching train/eval step builders (state shardings threaded, so a
  2-D plan's compiled step consumes and produces exactly the layout the
  plan created);
* a JSON-able :meth:`Plan.block` recorded in ``fit_summary.json``,
  checkpoint metas and bench records, so every artifact names the plan
  that produced it.

Strategies (the mesh-shape ladder, smallest model axis first)::

    dp            (n, 1)   replicated state, GSPMD gradient all-reduce
    dp_zero1      (n, 1)   + optimizer state sharded over `data`
    dp_tp         (d, m)   + kernels/momentum sharded over `model`
    dp_tp_zero1   (d, m)   both: opt leaves shard over data AND model
    auto                   walk the ladder with the memory model below

``strategy=auto`` estimates per-device bytes — params, grads, optimizer
state (each divided by exactly the axes its spec shards it over), the
batch shard, and an activation term (the XLA cost-analysis cache's
bytes-accessed figure when a lowered program is available, a documented
parametric bound otherwise) — against the chip's HBM and picks the
first rung that fits.  Detection is pure (no devices touched), so a CPU
host can plan a TPU-pod layout and tests pin the ladder without
hardware.

Every resolvable strategy is also a **named canonical program**
(``train_step_dp_tp``, ``train_step_dp_zero1``, ``train_step_dp_tp_zero1``
— :mod:`analysis.contracts`) with a checked-in jaxaudit contract pinning
per-mesh-axis collective counts, so a 2-D-mesh step silently regressing
to replicated is a contract failure, not a vibe.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .consensus import replicated_decision
from .mesh import DATA_AXIS, MODEL_AXIS, make_hybrid_mesh, make_mesh
from .tp import tp_param_specs
from .zero import zero_opt_specs

#: resolvable strategies, in ladder order (auto resolves to one of these)
STRATEGIES = ("dp", "dp_zero1", "dp_tp", "dp_tp_zero1")

#: which strategies shard what
_SHARD_PARAMS = {"dp_tp", "dp_tp_zero1"}
_SHARD_OPT = {"dp_zero1", "dp_tp_zero1"}

#: strategies the bucketed overlapped all-reduce (train.reduce_buckets)
#: composes with: the shard_map region owns only params (replicated) and
#: the batch shard, and ZeRO-1 lives entirely in the optimizer update
#: OUTSIDE that region — so dp and dp_zero1 compose.  TP does not: its
#: params are model-axis sharded, which the region's replicated in_specs
#: cannot express (and per-device fwd/bwd over sharded kernels is a
#: different algorithm, not a layout).
BUCKET_COMPATIBLE = ("dp", "dp_zero1")

#: reduce_buckets rejection: the nearest strategy that keeps the buckets
NEAREST_BUCKET_STRATEGY = {"dp_tp": "dp", "dp_tp_zero1": "dp_zero1"}

#: auto's activation-residency fallback when no lowered program exists in
#: the cost-analysis cache: live activation bytes ~= this many bytes per
#: input-tensor byte on the device's batch shard.  Measured on the
#: flagship step (DANet-R101 512px f32, peak_bytes_in_use minus
#: state+batch, cpu8 and TPU within ~30% of each other); deliberately a
#: conservative over-estimate — auto moving up the ladder one rung early
#: costs a little collective traffic, under-estimating OOMs the run.
ACTIVATION_BYTES_PER_INPUT_BYTE = 24.0

#: auto's HBM fallback when the backend exposes no bytes_limit (CPU dev
#: boxes): the smallest per-chip HBM of the supported TPU generations
#: (v2's 8 GiB is retired; v3 16 GiB is the floor we plan for)
DEFAULT_HBM_BYTES = 16 * 2**30


class PlanError(ValueError):
    """An unresolvable or inconsistent parallel plan — every message
    names the nearest supported alternative, so the error is a route,
    not a wall."""


def topology_fingerprint(n_devices: int | None = None) -> str:
    """The live topology's identity, ``"<platform>:<n_devices>/p<procs>"``
    (e.g. ``cpu:8/p1``) — what elastic membership change means: a plan
    stamped with one fingerprint restored under another IS a topology
    crossing, even when the *layout* normalizes equal (a legacy
    ``data=None`` dp plan resolves to "all devices" on any topology, so
    the layout alone cannot see a shrink).  Stamped into every
    :meth:`Plan.block` the trainer resolves, and thereby into every
    checkpoint meta and fit summary — the supervisor-side re-plan
    trigger reads it without Orbax."""
    import jax

    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    return f"{devs[0].platform}:{int(n_devices)}/p{jax.process_count()}"


def fingerprint_devices(fp) -> int | None:
    """The device count a :func:`topology_fingerprint` names (None for
    malformed/absent fingerprints) — lets cross-plan detection resolve a
    saved ``data=None`` layout against the topology it was SAVED under,
    not the one it is restoring onto."""
    try:
        return int(str(fp).split(":", 1)[1].split("/", 1)[0])
    except (IndexError, ValueError):
        return None


@dataclasses.dataclass(frozen=True)
class _AxisMesh:
    """Duck-typed stand-in for :class:`jax.sharding.Mesh` where only the
    axis sizes matter (``tp_param_specs`` / ``zero_opt_specs`` read
    ``mesh.shape[axis]`` and ``mesh.axis_names``) — lets the planner and
    its memory model reason about topologies this host cannot build
    (planning a tpu32 layout from a CPU box, unit tests without
    devices)."""

    shape: Mapping[str, int]

    @property
    def axis_names(self) -> tuple:
        return tuple(self.shape)


@dataclasses.dataclass(frozen=True)
class Plan:
    """One resolved, validated parallel layout.

    ``strategy`` is always concrete (never ``auto``); ``data`` may be
    ``None`` meaning "every device not claimed by ``model``" (resolved
    by ``make_mesh`` at construction).  Frozen and JSON-able via
    :meth:`block` — the form recorded in fit summaries, checkpoint metas
    and bench records.
    """

    strategy: str
    data: int | None = None
    model: int = 1
    slices: int = 1
    process_is_granule: bool | None = None
    #: the topology this plan was resolved AGAINST
    #: (:func:`topology_fingerprint`) — None for hand-built plans and
    #: planning-only resolutions; ``plan_from_config`` (the trainer
    #: entry) always stamps it, so live runs' metas carry it
    topology: str | None = None

    @property
    def shard_params(self) -> bool:
        return self.strategy in _SHARD_PARAMS

    @property
    def shard_opt_state(self) -> bool:
        return self.strategy in _SHARD_OPT

    @property
    def sharded(self) -> bool:
        """Whether the state carries any non-replicated layout (the
        condition for threading ``state_shardings`` into the steps)."""
        return self.shard_params or self.shard_opt_state

    def block(self) -> dict:
        """The JSON record block (schema-stable keys)."""
        return {
            "strategy": self.strategy,
            "data": self.data,
            "model": self.model,
            "slices": self.slices,
            "shard_params": self.shard_params,
            "shard_opt_state": self.shard_opt_state,
            "topology": self.topology,
        }

    def describe(self) -> str:
        d = self.data if self.data is not None else "*"
        s = f"{self.strategy} (data={d} x model={self.model}"
        if self.slices != 1:
            s += f" x slices={self.slices}"
        return s + ")"

    # ------------------------------------------------------------- mesh
    def make_mesh(self, devices=None) -> Mesh:
        """The plan's mesh: plain 2-D ``(data, model)``, or the hybrid
        ICI+DCN layout when the plan spans slices."""
        if self.slices != 1:
            return make_hybrid_mesh(
                self.slices, data=self.data, model=self.model,
                devices=devices,
                process_is_granule=self.process_is_granule)
        return make_mesh(data=self.data, model=self.model, devices=devices)

    def axis_sizes(self, n_devices: int | None = None) -> dict:
        """``{"data": d, "model": m}`` with ``data`` resolved against
        ``n_devices`` when the plan left it implicit.  The ``data`` size
        includes the DCN (slices) factor — hybrid meshes fold slices
        into the data axis (:func:`mesh.make_hybrid_mesh`)."""
        data = self.data
        if data is None:
            if n_devices is None:
                n_devices = len(jax.devices())
            if n_devices % (self.model * self.slices):
                raise PlanError(
                    f"{n_devices} devices not divisible by "
                    f"model={self.model} x slices={self.slices}")
            data = n_devices // (self.model * self.slices)
        return {DATA_AXIS: data * self.slices, MODEL_AXIS: self.model}

    # -------------------------------------------------------- shardings
    def state_specs(self, state: Any, mesh: Mesh | None = None) -> Any:
        """The COMPOSED ``PartitionSpec`` tree for a ``TrainState`` (or
        any pytree with ``params``/``opt_state``/``batch_stats`` attrs):
        ``tp_param_specs`` over ``model`` on params and momentum,
        ``zero_opt_specs`` over ``data`` layered on the optimizer leaves
        — the one place both rules meet one tree.  ``state`` may hold
        arrays or ``ShapeDtypeStruct`` templates; ``mesh`` may be a real
        mesh or None (axis sizes come from the plan)."""
        sizes = mesh.shape if mesh is not None else self.axis_sizes()
        am = _AxisMesh(dict(sizes))
        repl = lambda tree: jax.tree.map(lambda _: P(), tree)  # noqa: E731
        params = tp_param_specs(state.params, am) if self.shard_params \
            else repl(state.params)
        opt_base = tp_param_specs(state.opt_state, am) \
            if self.shard_params else None
        if self.shard_opt_state:
            opt = zero_opt_specs(state.opt_state, am, base_specs=opt_base)
        else:
            opt = opt_base if opt_base is not None \
                else repl(state.opt_state)
        return state.replace(
            step=P(), rng=P(), params=params,
            batch_stats=repl(state.batch_stats), opt_state=opt)

    def state_shardings(self, state: Any, mesh: Mesh) -> Any | None:
        """The sharding pytree ``make_train_step`` pins the state with:
        ``None`` for unsharded plans (the replicated default), the live
        arrays' own shardings when ``state`` holds them (exact — what
        ``create_train_state`` actually placed), the spec-derived
        ``NamedSharding`` tree for struct-only states (the canonical
        contract programs, which never initialize weights)."""
        if not self.sharded:
            return None
        leaves = jax.tree.leaves(state)
        if leaves and isinstance(leaves[0], jax.Array):
            from .tp import state_shardings as live_shardings

            return live_shardings(state)
        specs = self.state_specs(state, mesh)
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    # ---------------------------------------------------------- builders
    def build_state(self, rng, model, tx, input_shape: tuple,
                    mesh: Mesh | None = None):
        """``create_train_state`` under this plan's layout."""
        from .step import create_train_state

        mesh = mesh if mesh is not None else self.make_mesh()
        with mesh:
            return create_train_state(
                rng, model, tx, input_shape, mesh=mesh,
                shard_params=self.shard_params,
                shard_opt_state=self.shard_opt_state)

    def abstract_state(self, model, tx, input_shape: tuple,
                       mesh: Mesh | None = None):
        """Shape/dtype-only ``TrainState`` template under this plan
        (``jax.eval_shape`` — no weights initialized, no compile): what
        the memory model and the canonical contract programs consume."""
        from .step import create_train_state

        mesh = mesh if mesh is not None else self.make_mesh()
        with mesh:
            return jax.eval_shape(lambda: create_train_state(
                jax.random.PRNGKey(0), model, tx, input_shape, mesh=mesh,
                shard_params=self.shard_params,
                shard_opt_state=self.shard_opt_state))

    def make_train_step(self, model, tx, *, mesh: Mesh, state: Any,
                        **kwargs):
        """The plan-matched jitted train step: ``make_train_step`` with
        this plan's mesh and state shardings threaded.  ``state`` may be
        live or abstract (see :meth:`state_shardings`); every other
        kwarg passes through."""
        from .step import make_train_step

        return make_train_step(
            model, tx, mesh=mesh,
            state_shardings=self.state_shardings(state, mesh), **kwargs)

    def make_eval_step(self, model, *, mesh: Mesh, state: Any, **kwargs):
        from .step import make_eval_step

        return make_eval_step(
            model, mesh=mesh,
            state_shardings=self.state_shardings(state, mesh), **kwargs)


# ----------------------------------------------------------- resolution

def resolve_plan(strategy: str, n_devices: int | None = None,
            data: int | None = None, model: int = 0, slices: int = 1,
            process_is_granule: bool | None = None) -> Plan:
    """One concrete strategy -> a validated :class:`Plan`.

    ``model=0`` derives the axis: 1 for the dp family, 2 (the smallest
    live tensor-parallel degree) for the tp family.  Divisibility is
    checked here, against ``n_devices`` (default: the live device
    count), so a bad request fails at plan time with the ladder spelled
    out — not at mesh construction with a bare arithmetic error.
    """
    if strategy not in STRATEGIES:
        raise PlanError(
            f"unknown parallel.strategy {strategy!r} — pick one of "
            f"{list(STRATEGIES)} (or 'auto' to let the memory model "
            "walk that ladder)")
    wants_tp = strategy in _SHARD_PARAMS
    if model == 0:
        model = 2 if wants_tp else 1
    if wants_tp and model < 2:
        raise PlanError(
            f"strategy {strategy!r} shards params over the model axis "
            f"but model={model}; give parallel.model >= 2, or use "
            f"{'dp_zero1' if strategy == 'dp_tp_zero1' else 'dp'} for a "
            "1-wide model axis")
    if not wants_tp and model != 1:
        raise PlanError(
            f"strategy {strategy!r} has a 1-wide model axis but "
            f"parallel.model={model} — use "
            f"{'dp_tp_zero1' if strategy == 'dp_zero1' else 'dp_tp'} to "
            "make the model axis live")
    if n_devices is None:
        n_devices = len(jax.devices())
    if slices < 1 or n_devices % slices:
        raise PlanError(
            f"{n_devices} devices not divisible into {slices} slices")
    per_slice = n_devices // slices
    if per_slice % model:
        raise PlanError(
            f"model={model} does not divide the {per_slice} devices per "
            f"slice ({n_devices} total / {slices} slices) — model axes "
            f"that fit: {[m for m in _divisors(per_slice) if m > 1]}")
    if data is None:
        data = per_slice // model
    if data * model != per_slice:
        raise PlanError(
            f"plan {data}x{model} (x{slices} slices) covers "
            f"{data * model * slices} devices but {n_devices} are "
            "requested — drop parallel.data to derive it")
    return Plan(strategy=strategy, data=data, model=model, slices=slices,
                process_is_granule=process_is_granule)


def plan_from_config(cfg, n_devices: int | None = None,
                     memory_inputs: Callable[[], tuple] | None = None
                     ) -> Plan:
    """The trainer's entry: ``cfg.parallel`` -> :class:`Plan`.

    With ``parallel.strategy`` unset the legacy ``mesh.*`` knobs still
    name the layout (``shard_params``/``shard_opt_state`` map onto the
    ladder), so every run — old configs included — carries a plan.  A
    set strategy OWNS the layout: legacy sharding knobs alongside it are
    a config contradiction and fail loudly.

    ``memory_inputs`` (required for ``strategy=auto``) returns
    ``(state_struct, batch_bytes)`` — a shape-only ``TrainState`` and
    the global batch's byte count — the :func:`auto_plan` memory-model
    inputs; a third element, where the model knows it, is the live
    activation bytes on one device (a token model's batch is a few KB of
    ids, so the input-bytes rule would cost its activations at nothing).
    """
    p = cfg.parallel
    m = cfg.mesh
    if n_devices is None:
        n_devices = len(jax.devices())
    # every trainer-resolved plan is stamped with the topology it was
    # resolved against — the elastic restore path's crossing detector
    # (see topology_fingerprint; planning-only resolve_plan/auto_plan
    # calls stay unstamped, a CPU box planning a TPU pod has no live
    # fingerprint to claim)
    stamp = lambda plan: dataclasses.replace(  # noqa: E731
        plan, topology=topology_fingerprint(n_devices))
    if not p.strategy:
        strategy = {(False, False): "dp", (True, False): "dp_tp",
                    (False, True): "dp_zero1", (True, True): "dp_tp_zero1"
                    }[(m.shard_params, m.shard_opt_state)]
        if m.shard_params and m.model < 2:
            raise PlanError(
                "mesh.shard_params needs a live model axis "
                f"(mesh.model >= 2, got {m.model}) — or say it "
                "declaratively: parallel.strategy=dp_tp")
        # legacy meshes may carry a model axis the params don't shard
        # over (ring PAM's sequence parallelism) — the plan records the
        # axis; the strategy names only the STATE layout
        return stamp(Plan(strategy=strategy, data=m.data, model=m.model,
                          slices=m.slices,
                          process_is_granule=m.process_is_granule))
    if m.shard_params or m.shard_opt_state or m.model != 1 \
            or m.data is not None:
        raise PlanError(
            f"parallel.strategy={p.strategy!r} owns the mesh layout, "
            "but legacy mesh knobs are also set "
            f"(mesh.data={m.data}, mesh.model={m.model}, "
            f"shard_params={m.shard_params}, "
            f"shard_opt_state={m.shard_opt_state}) — clear them, or "
            "unset parallel.strategy to keep driving the low-level "
            "knobs")
    if getattr(cfg.model, "pam_impl", "") == "ring":
        raise PlanError(
            "model.pam_impl=ring is sequence parallelism over the model "
            "axis, not a state-sharding strategy — it is configured via "
            "the legacy mesh.model knob; unset parallel.strategy for "
            "ring-PAM runs")
    if p.strategy == "auto":
        if memory_inputs is None:
            raise PlanError(
                "strategy=auto needs the memory model's inputs "
                "(state struct + batch bytes) — construct the plan via "
                "Trainer, or call auto_plan() directly")
        state_struct, batch_bytes, *activation = memory_inputs()
        return stamp(auto_plan(
            n_devices=n_devices, state_struct=state_struct,
            batch_bytes=batch_bytes, slices=m.slices,
            activation_bytes=activation[0] if activation else None,
            hbm_bytes=(int(p.hbm_budget_gb * 2**30)
                       if p.hbm_budget_gb else None),
            process_is_granule=m.process_is_granule))
    return stamp(resolve_plan(
        p.strategy, n_devices=n_devices, data=p.data,
        model=p.model, slices=m.slices,
        process_is_granule=m.process_is_granule))


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def normalized_block(block: Mapping, n_devices: int) -> dict:
    """A :meth:`Plan.block` dict with an implicit ``data=None`` resolved
    against ``n_devices`` — the comparison form.  A legacy-derived plan
    carries ``data=None`` while ``resolve_plan`` stamps the concrete
    size; both describe the same physical layout on the same topology
    and must compare equal (cross-plan restore detection keys on this)."""
    out = dict(block)
    if out.get("data") is None:
        model = int(out.get("model") or 1)
        slices = int(out.get("slices") or 1)
        if n_devices % (model * slices) == 0:
            out["data"] = n_devices // (model * slices)
    return out


def plans_differ(saved: Mapping | None, live: Mapping | None,
                 n_devices: int) -> bool:
    """Does a restore from a checkpoint saved under ``saved`` into a run
    planned as ``live`` cross plans?  The restore-announcement
    discriminator (trainer + chaos invariants key on it).

    Layouts compare in :func:`normalized_block` form — each side's
    implicit ``data=None`` resolved against the topology IT names
    (``saved`` against its own stamped fingerprint when present, so a
    dp8 checkpoint restored on 4 devices never normalizes into a false
    match), falling back to the live count.  The ``topology``
    fingerprint joins the comparison only when BOTH sides carry one:
    metas written before the fingerprint existed must not read as a
    crossing on every resume."""
    if not saved or not live:
        return False
    a = normalized_block(saved,
                         fingerprint_devices(saved.get("topology"))
                         or n_devices)
    b = normalized_block(live, n_devices)
    if a.get("topology") is None or b.get("topology") is None:
        a.pop("topology", None)
        b.pop("topology", None)
    return a != b


# --------------------------------------------------------- memory model

def _tree_bytes(tree) -> int:
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) \
            * np.dtype(dtype).itemsize
    return total


def _sharded_tree_bytes(tree, specs, sizes: Mapping[str, int]) -> int:
    """Per-device bytes of ``tree`` under ``specs``: each leaf's bytes
    divided by the product of the axis sizes its spec shards it over."""
    total = 0.0
    spec_leaves = jax.tree.leaves(specs,
                                  is_leaf=lambda x: isinstance(x, P))
    leaves = jax.tree.leaves(tree)
    for leaf, spec in zip(leaves, spec_leaves):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        nbytes = int(np.prod(shape, dtype=np.int64)) \
            * np.dtype(dtype).itemsize
        div = 1
        for part in (spec or ()):
            for ax in (part if isinstance(part, (tuple, list))
                       else (part,)):
                if ax is not None:
                    div *= sizes.get(ax, 1)
        total += nbytes / div
    return int(math.ceil(total))


def estimate_plan_memory(plan: Plan, state_struct, batch_bytes: int,
                         n_devices: int | None = None,
                         activation_bytes: int | None = None) -> dict:
    """Per-device HBM estimate for one step under ``plan``.

    * **params / opt_state** — exact: the struct's byte counts divided by
      the axes the plan's composed specs shard each leaf over;
    * **grads** — one params-sized buffer in the params layout (GSPMD
      materializes the full gradient tree between backward and update;
      ZeRO-1 shards optimizer state, not gradients);
    * **batch** — the global batch's bytes over the data axis;
    * **activations** — ``activation_bytes`` when the caller has a real
      figure (e.g. the XLA cost-analysis cache's bytes-accessed for an
      already-lowered program, see :func:`activation_bytes_from_cost`),
      else ``ACTIVATION_BYTES_PER_INPUT_BYTE x`` the batch shard — a
      documented conservative bound.

    Pure arithmetic over shapes: no devices touched, no compile.
    """
    sizes = plan.axis_sizes(n_devices)
    # thread the RESOLVED sizes into the spec computation — a data=None
    # plan estimated for n_devices != the live host's count must shard
    # (and divide) against the caller's topology, not len(jax.devices())
    specs = plan.state_specs(state_struct, mesh=_AxisMesh(dict(sizes)))
    params = _sharded_tree_bytes(state_struct.params, specs.params, sizes)
    grads = params
    opt = _sharded_tree_bytes(state_struct.opt_state, specs.opt_state,
                              sizes)
    stats = _tree_bytes(state_struct.batch_stats)
    batch = int(math.ceil(batch_bytes / sizes[DATA_AXIS]))
    if activation_bytes is None:
        activation_bytes = int(batch * ACTIVATION_BYTES_PER_INPUT_BYTE)
    out = {"params": params, "grads": grads, "opt_state": opt,
           "batch_stats": stats, "batch": batch,
           "activations": int(activation_bytes)}
    out["total"] = sum(out.values())
    return out


def activation_bytes_from_cost(fn, args: tuple) -> int | None:
    """Activation proxy from the existing XLA cost-analysis cache
    (:mod:`telemetry.lowering`): the compiled program's bytes-accessed
    figure.  HBM *traffic* upper-bounds live residency, so this refines
    auto's parametric fallback wherever a lowered program already exists
    (bench re-planning, post-hoc analysis); ``None`` when the backend
    has no cost model."""
    from ..telemetry.lowering import lower_cached

    try:
        cost = lower_cached(fn, *args).cost()
    except Exception:
        return None
    b = cost.get("bytes")
    return int(b) if b else None


def detect_hbm_bytes() -> int | None:
    """The per-device HBM budget the live backend reports
    (``memory_stats()['bytes_limit']``); ``None`` on backends without
    memory stats (CPU)."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


def auto_plan(n_devices: int, state_struct, batch_bytes: int,
              hbm_bytes: int | None = None, slices: int = 1,
              activation_bytes: int | None = None,
              process_is_granule: bool | None = None) -> Plan:
    """``strategy=auto``: walk the mesh-shape ladder and return the
    first plan whose :func:`estimate_plan_memory` fits ``hbm_bytes``.

    The walk prefers the smallest model axis (TP pays an all-gather per
    BN boundary on convnets — :mod:`tp`'s own caveat), and at each model
    size tries the cheap memory lever first: plain layout, then ZeRO-1
    (one param-sized all-gather per step buys an optimizer-state-sized
    saving).  Nothing fitting is a loud :class:`PlanError` carrying the
    best rung's shortfall — never a silent OOM at step 1.
    """
    if hbm_bytes is None:
        hbm_bytes = detect_hbm_bytes() or DEFAULT_HBM_BYTES
    # CONSENSUS (parallel/consensus.py): the budget is DETECTED per
    # host, and hosts walking the ladder against different budgets would
    # resolve different plans — i.e. compile different collectives and
    # deadlock at the first one.  The min across hosts is the binding
    # constraint (a plan must fit the smallest chip), and the pure
    # ladder walk below is then identical everywhere by construction.
    # Single-process the gather is [hbm_bytes] and min is the identity —
    # auto ALWAYS routes through the primitive.
    hbm_bytes = int(replicated_decision(int(hbm_bytes), reduce="min",
                                        label="plan/hbm_budget"))
    per_slice = n_devices // slices
    walked = []
    for model in _divisors(per_slice):
        for strategy in (("dp", "dp_zero1") if model == 1
                         else ("dp_tp", "dp_tp_zero1")):
            plan = resolve_plan(strategy, n_devices=n_devices, model=model,
                           slices=slices,
                           process_is_granule=process_is_granule)
            mem = estimate_plan_memory(
                plan, state_struct, batch_bytes, n_devices=n_devices,
                activation_bytes=activation_bytes)
            walked.append((plan, mem["total"]))
            if mem["total"] <= hbm_bytes:
                # the verification half: every host must have picked
                # THIS rung — divergence here (a non-budget input
                # differing per host) is a loud ConsensusError, never
                # a silent per-host plan
                replicated_decision(plan.block(), reduce="same",
                                    label="plan/auto_rung")
                return plan
    best_plan, best_bytes = min(walked, key=lambda x: x[1])
    raise PlanError(
        f"strategy=auto: no rung of the ladder fits — the leanest "
        f"({best_plan.describe()}) still needs "
        f"{best_bytes / 2**30:.2f} GiB/device against a "
        f"{hbm_bytes / 2**30:.2f} GiB budget; shrink the batch/crop, "
        "enable remat, or add devices")


# --------------------------------------------------- step-compat errors

def reduce_buckets_conflict(strategy: str) -> PlanError:
    """The actionable rejection for ``train.reduce_buckets`` under a
    model-axis-sharded plan — names the nearest strategy that keeps the
    buckets (satellite of the planner: rejections route through here
    instead of bare ValueErrors)."""
    nearest = NEAREST_BUCKET_STRATEGY.get(strategy, "dp")
    return PlanError(
        f"train.reduce_buckets is incompatible with strategy "
        f"{strategy!r}: the bucketed reduce runs fwd/bwd per-device in "
        "a shard_map whose replicated in_specs cannot express "
        "model-axis-sharded params (TP keeps the GSPMD-implicit "
        f"reduce).  Nearest supported: parallel.strategy={nearest!r} "
        f"(buckets compose with {list(BUCKET_COMPATIBLE)} — ZeRO-1 "
        "lives in the optimizer update outside the shard_map region), "
        "or drop train.reduce_buckets to keep the TP layout")


def shardings_use_axis(shardings, axis: str) -> bool:
    """Whether any ``NamedSharding``/``PartitionSpec`` leaf in the tree
    shards over ``axis`` — the step's TP-vs-ZeRO discriminator."""
    def spec_of(leaf):
        if isinstance(leaf, P):
            return leaf
        return getattr(leaf, "spec", None)

    for leaf in jax.tree.leaves(
            shardings,
            is_leaf=lambda x: isinstance(x, P) or hasattr(x, "spec")):
        spec = spec_of(leaf)
        if spec is None:
            continue
        for part in spec:
            parts = part if isinstance(part, (tuple, list)) else (part,)
            if axis in parts:
                return True
    return False


def plan_record_block(plan: Plan | None) -> dict | None:
    """The bench-record ``plan`` block: ``None`` for the trivial
    single-axis pure-DP default (the schema convention precision set:
    null means "the default regime", so committed pre-planner history
    stays comparable), the full :meth:`Plan.block` otherwise."""
    if plan is None:
        return None
    if plan.strategy == "dp" and plan.model == 1 and plan.slices == 1 \
            and plan.data is None:
        return None
    if plan.strategy == "dp" and plan.model == 1 and plan.slices == 1 \
            and plan.data == len(jax.devices()):
        return None
    return plan.block()
