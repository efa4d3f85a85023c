"""The Trainer: end-to-end experiment driver.

This is the framework's replacement for the whole of the reference's
module-level script (train_pascal.py:41-309) — device setup, run-dir
management, model/optimizer/loss construction, the epoch loop with per-epoch
validation, best-checkpoint gating, metric logging and timing — rebuilt as a
class over the TPU-native subsystems:

* one ``jax.sharding.Mesh`` instead of ``nn.DataParallel`` (reference :92);
* one jitted train step (forward+loss+backward+update, grad-accum inside)
  instead of the eager per-batch body (:185-226);
* per-host sharded loaders instead of the planned distributed sampler (:3);
* Orbax full-state checkpoints instead of bare ``state_dict`` saves
  (:229-230, :301-304), with exact resume (params, optimizer, RNG, epoch,
  best-metric — all the state the reference lost on restart);
* process-0-gated logging (the "save if master process" checklist item, :4).

The default config reproduces the reference's experiment: DANet-ResNet101,
4-channel 512² crops, SGD(5e-8, 0.9, 5e-4), batch 16, val every epoch with
threshold-max Jaccard gating best saves.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..data import DataLoader, make_fake_voc
from ..data.governor import GOVERNOR_MODES, FeedActuators, FeedGovernor
from ..chaos import sites as chaos_sites
from ..models import build_from_config, model_tasks
from ..parallel import (
    DATA_AXIS,
    WIRE_KEY,
    create_train_state,
    make_eval_step,
    make_train_step,
    pack_wire,
    prefetch_to_device,
)
from ..parallel import plan as plan_lib
from ..telemetry import TraceCapture, get_accountant, mfu_estimate, scopes
from ..telemetry import events as events_lib
from ..telemetry import feed as feed_lib
from ..telemetry import set_enabled as telemetry_set_enabled
from ..utils.helpers import generate_param_report
from ..utils.profiling import device_memory_stats
from ..chaos.policies import CircuitBreaker, CircuitOpenError
from . import config as config_lib
from . import tasks
from .checkpoint import (
    CheckpointManager,
    atomic_write_json,
    latest_checkpoint_dir,
    next_run_dir,
)
from .logging import (
    MetricWriter,
    MultiWriter,
    make_val_panels,
    make_writer,
)
from .optim import make_optimizer
from .precision import precision_policy
from .preemption import PreemptionGuard
from .sentinel import StepSentinel


#: the dispatch's step annotation while no capture records
_NO_STEP_MARK = contextlib.nullcontext()


class _RollbackBudgetTick(Exception):
    """Internal: one rollback counted against the CircuitBreaker budget
    (raised inside the breaker so the rollback books as a failure, caught
    immediately by the handler)."""


class _DivergenceDetected(RuntimeError):
    """Internal control flow: the sentinel returned ``diverged`` inside
    ``train_epoch``; ``fit`` catches this and runs rollback-and-replay.
    Escapes only when no sentinel rollback is possible (budget spent /
    no checkpoint), converted to a loud ``FloatingPointError``."""

    def __init__(self, epoch: int, step_start: int, step_end: int,
                 batch_indices: list[int], losses: list, report):
        self.epoch = epoch
        self.step_start = step_start      # global steps, inclusive window
        self.step_end = step_end
        self.batch_indices = batch_indices
        self.losses = losses              # observed losses in the window
        self.report = report              # the SentinelReport that tripped
        super().__init__(
            f"sentinel verdict 'diverged' at step {report.step} "
            f"({report.reason}: {report.value}) — window "
            f"[{step_start}, {step_end}] of epoch {epoch}, "
            f"{len(batch_indices)} batch(es) to quarantine")


class _TrainerFeedActuators(FeedActuators):
    """The feed governor's knobs, bound to a live trainer (see
    data/governor.py): prefetch depths resize hot (both prefetchers read
    their bound live), the device-path flip and echo factor apply at
    epoch boundaries only — the governor owns that discipline."""

    def __init__(self, trainer: "Trainer"):
        self._t = trainer

    def get_prefetch(self) -> tuple[int, int]:
        return self._t._host_prefetch, self._t._device_prefetch

    def set_prefetch(self, host: int, device: int) -> None:
        t = self._t
        t._host_prefetch = int(host)
        t._device_prefetch = int(device)
        if hasattr(t.train_loader, "prefetch"):  # grain has no live bound
            t.train_loader.prefetch = int(host)

    def flip_available(self) -> tuple[bool, str]:
        return self._t._feed_flip_available()

    def flip_device_path(self) -> None:
        self._t._flip_device_path()

    def get_echo(self) -> int:
        return self._t._echo

    def base_echo(self) -> int:
        return self._t.cfg.data.echo

    def can_set_echo(self) -> tuple[bool, str]:
        if self._t.cfg.data.steps_per_dispatch > 1:
            return False, ("data.steps_per_dispatch > 1 packs distinct "
                           "batches per dispatch — mutually exclusive "
                           "with echo")
        return True, ""

    def set_echo(self, factor: int) -> None:
        # takes effect at the next epoch (train_epoch reads it at entry);
        # schedules were sized for the BASE echo, so a governor-armed
        # factor shortens the poly/cosine horizon rather than extending
        # it — constant LR (the default) is unaffected
        self._t._echo = max(1, int(factor))

    def pack_status(self) -> tuple[bool, str | None]:
        return self._t._pack_status()


class Trainer:
    """Build once, ``fit()`` to train, ``validate()`` to eval.

    All construction is lazy-free and explicit so tests can reach into any
    piece (``trainer.state``, ``trainer.mesh``, ``trainer.train_step`` …).
    """

    def __init__(self, cfg: config_lib.Config,
                 writers: MetricWriter | None = None):
        self.cfg = cfg
        self.is_main = jax.process_index() == 0

        # --- run dir (reference run_<N> scheme, train_pascal.py:73-82)
        self.run_dir = next_run_dir(cfg.work_dir)
        # --- flight recorder (telemetry/events.py): every host opens its
        # own run_dir/events/<host>.<pid>.jsonl; the run_<N> index is the
        # process generation the timeline merger stitches on.  cfg.telemetry
        # off = never configured = every emit() is one list check.
        self._events = (events_lib.configure(self.run_dir)
                        if cfg.telemetry else None)
        if writers is not None:
            self.writer = writers
        elif self.is_main:
            self.writer = MultiWriter(*[
                make_writer(name, self.run_dir,
                            experiment_name=cfg.experiment_name,
                            comet_project=cfg.comet_project or None,
                            comet_workspace=cfg.comet_workspace or None)
                for name in cfg.log_writers])
        else:
            self.writer = MetricWriter()  # no-op on non-main hosts

        if cfg.task not in model_tasks(cfg.model.name):
            raise ValueError(
                f"task={cfg.task!r} with model.name={cfg.model.name!r}: "
                f"that model trains under task="
                f"{' | '.join(model_tasks(cfg.model.name))}")
        #: what the loop is told of the task (train/tasks.py): it asks this
        #: object, never the task's name
        self.task = tasks.get(cfg.task, cfg)
        self.task.check(cfg)
        if cfg.data.echo < 1:
            raise ValueError(f"data.echo must be >= 1, got {cfg.data.echo}")
        if cfg.data.source not in ("fs", "packed"):
            raise ValueError(
                f"data.source must be 'fs' or 'packed', got "
                f"{cfg.data.source!r}")
        if cfg.data.source == "packed" and not cfg.data.pack_path:
            raise ValueError(
                "data.source=packed needs data.pack_path — the pack root "
                "dptpu-pack --out wrote (pack once, mmap forever; see "
                "docs/QUICKSTART.md 'Packing a dataset')")
        if cfg.data.pack_quarantine and cfg.data.source != "packed":
            raise ValueError(
                "data.pack_quarantine names records of a pack — it needs "
                "data.source=packed")
        if cfg.data.prepared_cache and cfg.data.source != "packed" \
                and self.is_main:
            # migration pointer (loud, once): the packed data plane is
            # the ONE prepared format going forward — it pre-decodes the
            # whole source, shards reads by host and gives the governor/
            # sentinel O(1) seek; the prepared crop cache still works
            # but is legacy.  prepared OVER a packed source is the
            # blessed composition — no note for runs already packed.
            from ..data.packed import pack_commands_for_config
            print(
                "note: data.prepared_cache is the LEGACY prepared format "
                "— the packed data plane (data/packed.py) supersedes it: "
                "pack once with `"
                + " && ".join(pack_commands_for_config(cfg))
                + "` and set data.source=packed data.pack_path=<out>",
                file=sys.stderr, flush=True)
        if cfg.data.governor not in GOVERNOR_MODES:
            raise ValueError(
                f"data.governor must be one of {GOVERNOR_MODES}, got "
                f"{cfg.data.governor!r}")
        if cfg.data.max_echo < 1:
            raise ValueError(
                f"data.max_echo must be >= 1, got {cfg.data.max_echo}")
        if cfg.data.governor == "auto" and not cfg.telemetry:
            # auto is multi-host safe since the consensus primitive
            # (parallel/consensus.py): every ladder input routes through
            # replicated_decision, so hosts can never disagree about the
            # echo factor — the old single-process-only restriction is
            # lifted
            raise ValueError(
                "data.governor=auto needs telemetry=true: the goodput "
                "accountant's input_wait attribution IS the stall "
                "signal the governor acts on")
        if cfg.data.steps_per_dispatch < 1:
            raise ValueError(f"data.steps_per_dispatch must be >= 1, got "
                             f"{cfg.data.steps_per_dispatch}")
        if cfg.data.steps_per_dispatch > 1 and cfg.data.echo > 1:
            raise ValueError(
                "data.steps_per_dispatch and data.echo both repeat steps "
                "per host batch in incompatible ways — pick one (echo "
                "re-steps the SAME batch; steps_per_dispatch packs "
                "DISTINCT batches into one dispatch)")

        # --- parallel plan (parallel/plan.py): the declarative strategy
        # -> validated mesh + composed sharding layout.  With
        # parallel.strategy unset the legacy mesh.* knobs still derive a
        # plan, so EVERY run carries one — recorded in fit_summary.json,
        # every checkpoint's meta (the cross-plan restore discriminator)
        # and the bench record's plan block.  strategy=auto walks the
        # mesh-shape ladder with the memory model; the resolution is
        # printed so the run's layout is never a mystery.
        self.plan = plan_lib.plan_from_config(
            cfg, memory_inputs=(self._plan_memory_inputs
                                if cfg.parallel.strategy == "auto"
                                else None))
        if self.is_main and cfg.parallel.strategy == "auto":
            print(f"parallel.strategy=auto resolved to "
                  f"{self.plan.describe()}", flush=True)
        self.mesh = self.plan.make_mesh()

        # --- live feed knobs (data/governor.py): the governor's
        # actuation surface.  Config values seed them; the governor (auto
        # mode) may move them — prefetch depths hot (both prefetchers
        # read their bound live), echo at epoch boundaries only.
        self._host_prefetch = cfg.data.prefetch
        self._device_prefetch = cfg.data.device_prefetch
        self._echo = cfg.data.echo
        #: set when the governor's epoch-boundary flip moved augmentation
        #: (+ guidance) on device mid-run
        self._feed_flipped = False

        # --- data
        root = cfg.data.root
        if cfg.data.fake:
            root = root or os.path.join(self.run_dir, "fake_voc")
            if not os.path.exists(os.path.join(root, "VOCdevkit")):
                make_fake_voc(root, n_images=8, size=(96, 128), n_val=3,
                              seed=cfg.seed)
        elif cfg.data.download:
            # Fetch once, on process 0 only — N processes racing a 2 GB
            # urlretrieve/extract into a shared root corrupts the tree.
            # Process 0's failure is caught and broadcast (the broadcast IS
            # the barrier), so the other processes fail fast instead of
            # hanging on a barrier process 0 never reaches.
            from ..data.voc import ensure_voc
            err = ""
            if self.is_main:
                try:
                    ensure_voc(root, download=True)
                except Exception as e:  # re-raised below, on every process
                    err = f"{type(e).__name__}: {e}"
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                failed = int(multihost_utils.broadcast_one_to_all(
                    jnp.int32(bool(err))))
                if failed and not err:
                    err = "see process 0 logs"
            if err:
                raise RuntimeError(f"VOC download failed on process 0 "
                                   f"({err})")
        #: the resolved dataset root (fake fixtures land under the run
        #: dir) — the governor's pack_recommendation names it
        self._data_root = root
        if cfg.data.coalesce_wire and not cfg.data.uint8_transfer:
            raise ValueError(
                "data.coalesce_wire concatenates the batch's uint8 leaves "
                "into one wire buffer — it requires data.uint8_transfer "
                "(float leaves would need a bitcast wire this deliberately "
                "avoids); enable uint8_transfer + prepared_cache")
        if cfg.data.uint8_transfer and not cfg.data.prepared_cache:
            raise ValueError(
                "data.uint8_transfer needs data.prepared_cache: only the "
                "prepared pipeline is uint8-exact end-to-end (the plain "
                "pipeline's cubic resize leaves fractional float values "
                "that quantization would silently alter)")
        if cfg.val_overlap and jax.process_count() > 1:
            raise ValueError(
                "val_overlap is single-process only: the val thread and "
                "the train loop would issue cross-host collectives in "
                "unsynchronized order (a distributed deadlock), so "
                "multi-host runs must validate serially")
        #: in-flight overlapped validation (val_overlap): set by
        #: _launch_overlapped_val, consumed by _join_overlapped_val
        self._pending_val = None
        self.train_set, self.val_set, val_wire = self.task.datasets(
            cfg, tasks.DataContext(root, self._open_pack))
        #: what the prepared val wire ships that the eval step undoes
        #: (tasks.ValWire): 3-channel batches, the packed 1-bit crop_gt
        self._val_device_guidance, self._val_packbits = val_wire
        # Batch sizes are GLOBAL (the reference's trainBatch=16 spans its 4
        # GPUs; BASELINE speaks of global batches); each host's loader feeds
        # its 1/process_count share, which shard_batch assembles into the
        # global array.  The global batch must divide cleanly over BOTH the
        # process count and the mesh data axis (and accum micro-batches) —
        # catching it here beats an opaque uneven-sharding error at step 1.
        n_proc = jax.process_count()
        data_axis = self.mesh.devices.shape[0]
        tb = cfg.data.train_batch
        if tb % n_proc:
            raise ValueError(f"global train batch {tb} not divisible by "
                             f"{n_proc} processes")
        if tb % (data_axis * cfg.optim.accum_steps):
            raise ValueError(
                f"global train batch {tb} not divisible by data axis "
                f"{data_axis} x accum_steps {cfg.optim.accum_steps}")
        vb_host = max(1, -(-cfg.data.val_batch // n_proc))  # ceil, >= 1
        if self.is_main and vb_host * n_proc != cfg.data.val_batch:
            print(f"note: global val batch rounded "
                  f"{cfg.data.val_batch} -> {vb_host * n_proc} "
                  f"({vb_host}/host x {n_proc} hosts)", flush=True)
        if cfg.data.loader == "grain":
            # Grain train loader (process workers, checkpointable iterators);
            # eval stays on the thread loader, which wrap-pads the final
            # batch so every sample is scored (grain's multi-host sharding
            # drops remainders instead — fine for training, wrong for eval).
            from ..data import GrainDataLoader
            self.train_loader = GrainDataLoader(
                self.train_set, tb // n_proc, shuffle=True, drop_last=True,
                seed=cfg.seed, num_workers=cfg.data.num_workers,
                num_shards=n_proc, shard_index=jax.process_index())
        elif cfg.data.loader == "threads":
            self.train_loader = DataLoader(
                self.train_set, tb // n_proc, shuffle=True,
                drop_last=True, seed=cfg.seed,
                num_workers=cfg.data.num_workers,
                prefetch=cfg.data.prefetch,
                num_shards=n_proc, shard_index=jax.process_index())
        else:
            raise ValueError(f"unknown data.loader: {cfg.data.loader!r} "
                             "(threads | grain)")
        self.val_loader = DataLoader(
            self.val_set, vb_host, shuffle=False, drop_last=False,
            seed=cfg.seed, num_workers=cfg.data.num_workers,
            prefetch=cfg.data.prefetch,
            num_shards=n_proc, shard_index=jax.process_index())
        # drop_last swallows a sub-batch-size dataset whole; training
        # would silently run zero steps per epoch (NaN epoch loss).  The
        # emptiness decision is laundered through the consensus
        # primitive: shards round unevenly, and one host raising here
        # alone would leave the rest hanging at the first collective —
        # if ANY host's shard is empty, every host raises in lockstep.
        from ..parallel.consensus import replicated_decision
        min_batches = int(replicated_decision(
            len(self.train_loader), reduce="min",
            label="trainer/train_loader_len"))
        if min_batches == 0:
            raise ValueError(
                f"train loader is empty: dataset has {len(self.train_set)} "
                f"samples globally (~{len(self.train_set) // n_proc} on "
                f"this host's shard) but the per-host batch is "
                f"{tb // n_proc} with drop_last — lower data.train_batch or "
                "enlarge the dataset")

        # --- model / optimizer / state
        # train.precision (train/precision.py): the bf16 policy owns the
        # model's compute dtype (master params stay f32 via flax's
        # param_dtype default); train.reduce_buckets runs the step's
        # fwd/bwd per-device inside shard_map, so BN batch stats must
        # reduce explicitly — the model is built cross-replica.
        self.precision = precision_policy(cfg.train.precision)
        if cfg.train.reduce_buckets:
            # the planner owns compatibility: buckets compose with the
            # dp family incl. ZeRO-1 (plan.BUCKET_COMPATIBLE — the
            # sharded optimizer update lives outside the shard_map
            # region), never with TP or a live model axis
            if self.plan.strategy not in plan_lib.BUCKET_COMPATIBLE:
                raise plan_lib.reduce_buckets_conflict(self.plan.strategy)
            if self.plan.model > 1 or cfg.model.pam_impl == "ring":
                raise plan_lib.PlanError(
                    "train.reduce_buckets needs a data-only mesh "
                    "(model axis 1) and a non-ring PAM — its shard_map "
                    "region owns the data axis; nearest supported: "
                    "parallel.strategy=dp (or dp_zero1)")
        self.model = build_from_config(
            cfg.model,
            dtype=(self.precision.compute_dtype if self.precision
                   else cfg.model.dtype),
            bn_cross_replica_axis=(DATA_AXIS if cfg.train.reduce_buckets
                                   else None),
            # ring PAM shards the spatial tokens over this mesh's model axis
            pam_sp_mesh=(self.mesh if cfg.model.pam_impl == "ring" else None))
        steps_per_epoch = len(self.train_loader)  # > 0: guarded above
        # Each loaded batch is stepped data.echo times, so schedules (poly
        # decay, warmup fractions) must span echo x the loader length or
        # they exhaust early and clamp the LR.
        total_steps = steps_per_epoch * cfg.epochs * cfg.data.echo
        self.tx, self.schedule = make_optimizer(cfg.optim, total_steps)
        with self.mesh:
            self.state = create_train_state(
                jax.random.PRNGKey(cfg.seed), self.model, self.tx,
                **self.task.init_input(cfg), mesh=self.mesh,
                shard_params=self.plan.shard_params,
                shard_opt_state=self.plan.shard_opt_state)
        loss_weights = cfg.model.loss_weights
        if loss_weights is None:
            # a model may state its own (a token model: its first head,
            # then its prediction module's lambda)
            loss_weights = getattr(self.model, "loss_weights", None)
        # The plan's TP / ZeRO-1 layouts flow from the created state
        # into the compiled steps (live shardings — exactly what
        # create_train_state placed); the plan owns the threading rule.
        st_sh = self.plan.state_shardings(self.state, self.mesh)
        augment = self.task.device_stage and self.task.device_stage(
            cfg, cfg.data.device_augment, cfg.data.device_guidance)
        eval_stage = None
        if augment is not None and self.task.train_transform is None:
            # no augmentation moved off the host: a stage the task's loss
            # cannot do without (a token model's noise), which evaluation
            # runs too, from a fixed key
            eval_stage = functools.partial(augment,
                                           rng=jax.random.PRNGKey(0))
        # --- self-healing sentinel (train/sentinel.py; see fit()): built
        # before the steps because monitor_grads changes their outputs
        sc = cfg.sentinel
        self._sentinel = StepSentinel(
            ema_beta=sc.ema_beta, suspect_factor=sc.suspect_factor,
            diverged_factor=sc.diverged_factor,
            warmup_steps=sc.warmup_steps, grad_factor=sc.grad_factor,
            update_ratio_max=sc.update_ratio_max,
            telemetry=cfg.telemetry) if sc.enabled else None
        #: rollback budget — THE CircuitBreaker (chaos/policies.py):
        #: each rollback books a failure, each cleanly completed epoch a
        #: success, so only max_rollbacks CONSECUTIVE rollbacks open it
        #: (and the run then fails loudly instead of looping)
        self._rollback_breaker = CircuitBreaker(
            failure_threshold=max(1, sc.max_rollbacks)) \
            if sc.enabled else None
        #: epoch -> loader batch indices quarantined by past rollbacks
        #: (skipped on replay); the JSONL ledger under the run dir is the
        #: durable record, this index is the live skip set
        self._quarantine: dict[int, set[int]] = {}
        #: loader batch index actually dispatched for each epoch-step of
        #: the CURRENT epoch (quarantine skips make `start + i` wrong)
        self._epoch_batch_order: list[int] = []
        self.sentinel_rollbacks = 0
        self.sentinel_quarantined_steps = 0
        self._rollback_seconds: list[float] = []
        step_kwargs = dict(
            loss_weights=loss_weights,
            accum_steps=cfg.optim.accum_steps, mesh=self.mesh,
            loss_type=self.task.loss_type, state_shardings=st_sh,
            augment=augment,
            # the image models' capacity MoE takes its weight from the
            # configuration; any other model states its own beside its
            # ``loss_weights`` (a token model's alignment loss)
            aux_loss_weight=(cfg.model.moe_aux_weight
                             if cfg.model.moe_experts else
                             getattr(self.model, "aux_loss_weight", 0.0)),
            loss_scale=cfg.optim.loss_scale,
            packbits_masks=cfg.data.packbits_masks,
            sentinel_metrics=sc.enabled and sc.monitor_grads,
            precision=self.precision,
            reduce_buckets=cfg.train.reduce_buckets)
        self._step_kwargs = step_kwargs
        self.train_step, self.multi_train_step = self._build_steps()
        #: data.coalesce_wire: the wire-consuming twins of the two programs
        #: above, built lazily at the first train batch — the wire layout
        #: (per-key byte extents) is data-shaped, and deriving it from the
        #: real batch instead of re-deriving shape math from config keeps
        #: one source of truth.  ``_step_kwargs`` is kept for that build.
        self._wire_spec: tuple | None = None
        self._wire_step = None
        self._wire_multi_step = None
        # --- telemetry: goodput program-identity + MFU inputs + on-demand
        # trace.  _programs_seen keys the compile-vs-step goodput split
        # (the FIRST dispatch of each compiled program pays trace+XLA and
        # is attributed to 'compile'); the trace trigger arms from SIGUSR2
        # during fit() and writes bounded XPlane captures under the run dir.
        self._programs_seen: set[str] = set()
        self._prod_steps = 0
        self._flops_per_step: float | None = None
        self._trace = TraceCapture(
            os.path.join(self.run_dir, "trace_on_demand")) \
            if ((cfg.telemetry or cfg.profile_epoch is not None)
                and self.is_main) else None
        # --- input-feed governor (data/governor.py): closes the loop
        # from the measured input_wait fraction to the pipeline knobs.
        # `observe` builds on the main process only (secondary hosts
        # would just write nothing); multi-host `auto` builds on EVERY
        # process — its actuations (the echo factor above all) must land
        # identically everywhere, which is exactly what routing the
        # ladder inputs through replicated_decision (consensus=True)
        # guarantees.  The JSONL ledger stays main-only either way.
        # Needs telemetry: the goodput snapshot deltas ARE its signal.
        # _feed_last holds the previous tick's snapshot.
        from ..telemetry.goodput import FeedWindow
        gov_auto = cfg.data.governor == "auto"
        gov_multi = gov_auto and jax.process_count() > 1
        self._governor = FeedGovernor(
            cfg.data.governor, cfg.data.governor_target,
            _TrainerFeedActuators(self), max_echo=cfg.data.max_echo,
            window=FeedWindow(cfg.data.governor_window),
            jsonl_path=(os.path.join(self.run_dir, "governor.jsonl")
                        if self.is_main else None),
            # auto ALWAYS routes through the consensus primitive —
            # single-process the gather is [value] and the reduce is an
            # identity (no communication), so the multi-host semantics
            # are the only semantics and never rot untested
            consensus=gov_auto,
            telemetry=True) \
            if (cfg.data.governor != "off" and cfg.telemetry
                and (self.is_main or gov_multi)) else None
        self._feed_last: dict | None = None
        #: what the last dispatch's model counted, if it sows counters
        self._last_counters: dict | None = None
        self.eval_step = make_eval_step(
            self.model, loss_weights=loss_weights, mesh=self.mesh,
            loss_type=self.task.loss_type, state_shardings=st_sh,
            preprocess=eval_stage or val_wire.preprocess(cfg),
            packbits_masks=val_wire.packbits)

        # --- checkpointing
        self.ckpt = CheckpointManager(
            os.path.join(self.run_dir, "checkpoints"),
            keep_latest=cfg.checkpoint.keep_latest,
            best_metric_init=(cfg.checkpoint.best_metric_init
                              if self.task.best_init is None
                              else self.task.best_init),
            async_save=cfg.checkpoint.async_save,
            digest=cfg.checkpoint.digest,
            # every save's meta names the plan that laid the state out —
            # the cross-plan restore discriminator (chaos
            # plan_mismatch_restore asserts it)
            static_meta={"plan": self.plan.block()})
        self.start_epoch = 0
        self._resume_start_batch = 0  # exact mid-epoch resume offset
        #: steps the resume restore SKIPPED as unreadable (torn files) on
        #: the way to the one it used — surfaced for ops/chaos assertions
        self.resume_fallback_steps: list[int] = []
        #: the restored checkpoint's meta dict (empty when not resumed) —
        #: the chaos runner's digest-continuity invariants read it
        self.resume_meta: dict = {}
        #: True when the resume restored ACROSS a plan (or topology)
        #: crossing — the elastic chaos scenario's "every restore
        #: announced the crossing" evidence bit
        self.resume_plan_crossing = False
        if cfg.checkpoint.warm_start:
            self._warm_start(cfg.checkpoint.warm_start,
                             cfg.checkpoint.warm_start_partial)
        if cfg.resume == "auto":
            # Continue from the newest prior run with checkpoints (the
            # reference's pinned-run_0 resume, without knowing the index).
            src = latest_checkpoint_dir(cfg.work_dir,
                                        exclude_run=self.run_dir)
            if src is None:
                if self.is_main:
                    print("resume=auto: no prior checkpoints under "
                          f"{cfg.work_dir}; starting fresh", flush=True)
            else:
                self._resume(src)
        elif cfg.resume:
            self._resume(cfg.resume)

        # --- param report (reference generate_param_report, :169)
        if self.is_main:
            flat = config_lib.flatten(cfg)
            flat["n_params"] = self.n_params
            flat["n_devices"] = self.mesh.devices.size
            # the RESOLVED plan (config.json only records the request —
            # under strategy=auto the two differ)
            flat["resolved_plan"] = self.plan.describe()
            flat["train_set"] = str(self.train_set)
            flat["val_set"] = str(self.val_set)
            generate_param_report(
                os.path.join(self.run_dir, f"{cfg.experiment_name}.txt"), flat)
            config_lib.to_json(cfg, os.path.join(self.run_dir, "config.json"))
            self.writer.hparams(flat)

    @property
    def n_params(self) -> int:
        """Trainable parameter count (the reference printed this at startup,
        train_pascal.py:105)."""
        return sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(self.state.params))

    # ---------------------------------------------------- packed source
    def _open_pack(self, dataset_name: str, splits, transform,
                   quarantine=()):
        """Open one ``dptpu-pack`` directory under ``data.pack_path`` as
        this run's source for (dataset, task, splits).  A missing or
        mismatched pack fails LOUDLY with the exact ``dptpu-pack``
        invocation that builds it — the operator's move, named."""
        from ..data.packed import (
            PackedDataset,
            PackFormatError,
            pack_command,
            pack_dir_path,
        )

        cfg = self.cfg
        kind = self.task.pack_kind
        area_thres = self.task.pack_area_thres(cfg)
        path = pack_dir_path(cfg.data.pack_path, dataset_name, kind, splits)
        root = (cfg.data.sbd_root if dataset_name == "sbd"
                else self._data_root)
        cmd = pack_command(root, cfg.data.pack_path, dataset_name, kind,
                           splits, area_thres)
        try:
            ds = PackedDataset(path, transform=transform,
                               quarantine=quarantine, expect_kind=kind)
        except (OSError, PackFormatError) as e:
            raise ValueError(
                f"data.source=packed but no readable "
                f"{dataset_name}/{kind} pack at {path} "
                f"({type(e).__name__}: {e}) — build it once: `{cmd}`"
            ) from e
        if area_thres is not None \
                and ds.meta.get("area_thres") != area_thres:
            raise ValueError(
                f"pack {path} was built with area_thres="
                f"{ds.meta.get('area_thres')} but this run wants "
                f"data.area_thres={cfg.data.area_thres} — its instance "
                f"list differs; re-pack: `{cmd}`")
        return ds

    def _pack_status(self) -> tuple[bool, str | None]:
        """The governor's rung-0 input (data/governor.py): is this run
        already feeding from a pack, and if not, the exact CLI that
        removes the stall at its source."""
        cfg = self.cfg
        if cfg.data.source == "packed":
            return True, None
        from ..data.packed import pack_commands_for_config
        cmds = pack_commands_for_config(cfg, root=self._data_root)
        return False, (
            "rung 0 — cheaper than tuning around the stall is deleting "
            "it: pre-decode the dataset once and train from the mmap "
            "(data.source=packed data.pack_path=<out>): `"
            + " && ".join(cmds) + "`")

    def _plan_memory_inputs(self) -> tuple:
        """``strategy=auto``'s memory-model inputs: a shape-only
        ``TrainState`` template of THIS config's model/optimizer (via
        ``jax.eval_shape`` — no weights initialized, no mesh needed:
        state shapes are layout-independent) and the global train
        batch's byte count.  Built from the config alone, before the
        mesh exists — the plan decides the mesh."""
        cfg = self.cfg
        policy = precision_policy(cfg.train.precision)
        model = build_from_config(
            cfg.model,
            dtype=policy.compute_dtype if policy else cfg.model.dtype)
        tx, _ = make_optimizer(cfg.optim, 100)  # shapes don't see steps
        state_struct = jax.eval_shape(
            lambda: create_train_state(
                jax.random.PRNGKey(0), model, tx,
                **self.task.init_input(cfg)))
        return self.task.memory_inputs(cfg, model, state_struct)

    def _warm_start(self, path: str, partial: bool) -> None:
        """Import model weights from a torch ``.pth`` state_dict — the
        reference's unconditional warm start (train_pascal.py:103) as a
        config knob.  Only params/batch-stats are imported (the reference
        never persisted optimizer state, SURVEY.md §3.5); step/opt-state/RNG
        stay fresh.  Use ``resume`` for full-state Orbax restarts."""
        from ..utils.torch_interop import (
            inflate_stem_channels,
            is_torchvision_resnet,
            load_torch_file,
            torch_state_dict_to_params,
            torchvision_resnet_depth,
            torchvision_resnet_rename,
        )

        sd = load_torch_file(path)
        rename = None
        if is_torchvision_resnet(sd):
            # An ImageNet-pretrained torchvision backbone (the reference's
            # model lineage): bridge the naming, widen the RGB stem to this
            # model's input channels, and import partially (the seg head
            # isn't in a classification checkpoint).
            bb = self.cfg.model.backbone
            if not bb.startswith("resnet"):
                raise ValueError(
                    f"{path} looks like a torchvision ResNet checkpoint "
                    f"but model.backbone={bb!r}")
            depth = torchvision_resnet_depth(sd)
            if depth != int(bb[len("resnet"):]):
                # a partial import would silently leave most of the deeper
                # net at fresh init — refuse instead
                raise ValueError(
                    f"{path} is a torchvision resnet{depth} checkpoint "
                    f"but model.backbone={bb!r}")
            sd = inflate_stem_channels(sd, self.cfg.model.in_channels)
            rename = torchvision_resnet_rename(depth)
            partial = True
            if self.is_main:
                print(f"warm start: torchvision ResNet naming detected in "
                      f"{path}; importing as pretrained backbone",
                      flush=True)
        # Shape/dtype-only templates: the live state may be sharded across
        # processes, and describing shapes must not gather it to host.
        as_struct = lambda t: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
        params, stats = torch_state_dict_to_params(
            sd, as_struct(self.state.params), as_struct(self.state.batch_stats),
            rename=rename, allow_missing=partial, allow_unused=partial)
        if rename is not None:
            # Torchvision mode forces partial (the seg head isn't in a
            # classification checkpoint), but the BACKBONE must import
            # completely — width variants (wide_resnet, resnext) share a
            # plain resnet's layer counts and would otherwise fall through
            # the shape-mismatch path leaf by leaf, leaving a silently
            # half-pretrained backbone.
            from flax.traverse_util import flatten_dict
            missing = [
                ".".join(p)
                for tree in (params.get("backbone", {}),
                             stats.get("backbone", {}))
                for p, v in flatten_dict(tree).items()
                if isinstance(v, jax.ShapeDtypeStruct)
            ]
            if missing:
                raise ValueError(
                    f"torchvision import left {len(missing)} backbone "
                    f"leaves at fresh init (e.g. backbone.{missing[0]}): "
                    f"tensor shapes in {path} do not match a plain "
                    f"resnet{torchvision_resnet_depth(sd)} (wide_resnet / "
                    "resnext variants are not supported)")

        imported = [0, 0]  # [loaded from checkpoint, kept template]

        def place(new, old):
            if isinstance(new, jax.ShapeDtypeStruct):
                imported[1] += 1
                return old  # leaf absent from the checkpoint (partial)
            imported[0] += 1
            # numpy -> sharded device array in one hop, preserving the
            # leaf's existing mesh placement (replicated or TP-sharded).
            # DONATION SAFETY (the checkpoint.restore lesson): on CPU,
            # device_put of a host numpy array can be ZERO-COPY — the
            # jax.Array aliases the numpy buffer — and the first train
            # step DONATES these leaves, handing XLA memory that the
            # import pipeline still references.  That intermittently
            # surfaced as a non-finite first loss from a clean batch and
            # correct imported weights (timing-dependent: whether the
            # put aliases depends on allocator state).  jnp.copy
            # re-buffers into XLA-owned memory, donation-safe on every
            # backend — one extra copy, paid once at warm start.
            return jnp.copy(jax.device_put(np.asarray(new), old.sharding))

        self.state = self.state.replace(
            params=jax.tree.map(place, params, self.state.params),
            batch_stats=jax.tree.map(place, stats, self.state.batch_stats))
        if imported[0] == 0:
            # Every leaf fell through allow_missing: a key-naming mismatch,
            # not a warm start.  Silently training from fresh init is the
            # masking torch_interop's two separate flags exist to prevent.
            raise ValueError(
                f"warm start from {path} imported 0 of "
                f"{imported[1]} leaves — checkpoint keys do not match this "
                "model; check the architecture/naming")
        if self.is_main:
            print(f"warm-started {imported[0]} leaves from {path} "
                  f"({imported[1]} kept from fresh init)", flush=True)

    def _resume(self, source: str) -> None:
        mgr = CheckpointManager(source) if os.path.abspath(source) != \
            os.path.abspath(os.path.join(self.run_dir, "checkpoints")) \
            else self.ckpt
        self.state, meta = mgr.restore(self.state)
        self.resume_meta = dict(meta)
        saved_plan = meta.get("plan")
        n_dev = self.mesh.devices.size
        if plan_lib.plans_differ(saved_plan, self.plan.block(), n_dev):
            # Cross-plan restore: StandardRestore adopts the TARGET
            # state's shardings, so the arrays land resharded into this
            # plan's layout (and restore's re-buffer pass keeps them
            # donation-safe) — announce it loudly; a silent layout
            # change under a resumed run is how garbage gets loaded.
            # plans_differ also sees TOPOLOGY crossings the layout
            # can't (a data=None dp plan normalizes equal on any
            # device count) — the elastic shrink/grow path.
            self.resume_plan_crossing = True
            if self.is_main:
                saved_topo = (saved_plan or {}).get("topology")
                topo = (f" across a topology change ({saved_topo} -> "
                        f"{self.plan.topology})"
                        if saved_topo and saved_topo != self.plan.topology
                        else "")
                print("cross-plan restore: checkpoint was saved under "
                      f"plan {saved_plan} and is resharding into "
                      f"{self.plan.block()} (strategy "
                      f"{saved_plan.get('strategy')} -> "
                      f"{self.plan.strategy}){topo}", flush=True)
        self.resume_fallback_steps = list(mgr.last_restore_fallback)
        self.start_epoch = int(meta.get("epoch", 0)) + 1
        self.ckpt.best_metric = float(
            meta.get("best_metric", self.ckpt.best_metric))
        interrupted = meta.get("interrupted_epoch")
        if interrupted is not None and self.cfg.checkpoint.exact_resume:
            # Exact mid-epoch resume: the preempt save recorded how many
            # steps of the interrupted epoch already trained; the epoch's
            # batch order is deterministic given (seed, epoch), so continue
            # at that batch instead of replaying the epoch.  A batch
            # interrupted mid-echo replays its echoes (rounded down).
            # The recorded offset indexes THE batch order it was written
            # under; anything that changes that order (host count, batch
            # size, seed) or the steps-per-batch accounting (echo) makes it
            # meaningless.  Replaying the epoch is the layout-safe fallback
            # (batches repeat, none skipped).
            now = {"num_shards": jax.process_count(),
                   "echo": self.cfg.data.echo,
                   "train_batch": self.cfg.data.train_batch,
                   "seed": self.cfg.seed}
            stale = {k: (meta.get(k, v), v) for k, v in now.items()
                     if int(meta.get(k, v)) != v}
            if stale:
                if self.is_main:
                    diffs = ", ".join(f"{k}: {a} -> {b}"
                                      for k, (a, b) in stale.items())
                    print(f"exact_resume: data-order config changed "
                          f"({diffs}) — replaying the interrupted epoch "
                          "instead", flush=True)
            else:
                done = int(meta.get("epoch_steps_done", 0)) \
                    // max(1, self.cfg.data.echo)
                # A stop landing exactly on the epoch's last step still
                # needs the epoch-end bookkeeping (validation, best gate,
                # checkpoint) the preempt skipped — replay the final batch
                # so the epoch completes through the normal path.
                done = min(done, len(self.train_loader) - 1)
                self.start_epoch = int(interrupted)
                self._resume_start_batch = done
        if self.is_main:
            at = f"epoch {self.start_epoch}"
            if self._resume_start_batch:
                at += f" batch {self._resume_start_batch}"
            print(f"resumed from {source} at {at} "
                  f"(best={self.ckpt.best_metric:.4f})", flush=True)

    # ------------------------------------------------------------------ train
    def _build_steps(self, wire_spec: tuple | None = None):
        """The (single-step, K-step-or-None) compiled train programs from
        the one stored ``_step_kwargs`` — the only constructor for both the
        plain and the wire-consuming (data.coalesce_wire) twins, so the two
        families cannot drift as kwargs grow.  The K-step program exists
        iff data.steps_per_dispatch > 1; epoch-tail remainders always run
        through the single-step one."""
        k = self.cfg.data.steps_per_dispatch
        single = make_train_step(self.model, self.tx, wire_spec=wire_spec,
                                 **self._step_kwargs)
        multi = (make_train_step(self.model, self.tx, steps_per_call=k,
                                 wire_spec=wire_spec, **self._step_kwargs)
                 if k > 1 else None)
        return single, multi

    def _pack_wire_transform(self, batch: dict) -> dict:
        """data.coalesce_wire stage for the prefetcher's placement thread:
        pack the batch into the one-buffer wire, and on the FIRST batch
        derive the spec + build the wire-consuming step programs.  Runs on
        the worker so the full-batch memcpy stays off the dispatch thread;
        the attribute writes are published to the dispatch loop by the
        placement future's ``result()`` (completion happens-before the
        first wire batch is yielded)."""
        batch, spec = pack_wire(batch, self.task.device_keys)
        if self._wire_spec is None:
            self._wire_spec = spec
            self._wire_step, self._wire_multi_step = self._build_steps(spec)
        elif spec != self._wire_spec:
            raise RuntimeError(
                f"data.coalesce_wire: batch layout changed mid-training "
                f"({spec} vs {self._wire_spec}) — the train loader must "
                "produce fixed-shape batches (drop_last + fixed crop)")
        return batch

    def _note_step_cost(self, fn, args, steps_per_call: int) -> None:
        """One-shot model-FLOPs/step count for MFU — XLA's own
        ``cost_analysis`` of the exact compiled program (with the
        persistent compile cache on, the executable is shared with the
        running step: this re-traces but does not re-compile).  Where the
        cost model gives nothing there is no count and no MFU."""
        if self._flops_per_step is not None or not self.cfg.telemetry:
            return
        from ..telemetry.goodput import xla_step_cost
        flops = xla_step_cost(fn, *args)["flops"]
        if flops and flops > 0:  # guard negative cost-model sentinels
            self._flops_per_step = flops / max(1, steps_per_call)

    def _report_goodput(self, history: dict | None = None) -> None:
        """Fit-end goodput breakdown + MFU estimate: into the writer stack
        (=> metrics.jsonl / console / comet), the registry gauges (=> the
        serve front's /metrics when co-hosted) and ``history``."""
        if not self.cfg.telemetry:
            return
        rep = get_accountant().report()
        fed = feed_lib.publish()
        if history is not None:
            history["goodput"] = rep
        scalars = {f"goodput/{b}_s": round(v, 4)
                   for b, v in rep["buckets"].items()}
        scalars["goodput/total_s"] = round(rep["total_s"], 4)
        scalars["goodput/productive_frac"] = round(rep["goodput"], 4)
        # beside input_wait, on whom: the share of batches the loader had
        # built, and of placements that had finished, when the loop came
        for stage in ("batch", "fetch"):
            if fed[f"input_{stage}_total"]:
                scalars[f"input/{stage}_ready_frac"] = round(
                    fed[f"input_{stage}_ready_total"]
                    / fed[f"input_{stage}_total"], 4)
        # MFU is a device metric: off-TPU there is no peak to divide by
        if self._flops_per_step and self._prod_steps \
                and jax.devices()[0].platform == "tpu":
            step_time = rep["buckets"]["step"] / self._prod_steps
            if step_time > 0:
                # cost_analysis of a partitioned program counts ONE
                # device's share: the b8 x 1-chip and b32 x 4-chip steps
                # both report 1.314e13 (chip runs, PR 21) — no division
                # by the device count
                est = mfu_estimate(self._flops_per_step, step_time,
                                   device_kind=None)
                est["flops_source"] = "xla_cost_analysis"
                if history is not None:
                    history["mfu"] = est
                scalars["mfu"] = round(est["mfu"], 6)
                scalars["mfu/flops_per_step"] = self._flops_per_step
                scalars["mfu/peak_flops_per_device"] = \
                    est["peak_flops_per_device"]
        if self.is_main:
            self.writer.scalars(scalars, int(self.state.step))

    # ------------------------------------------------------- feed governor
    def _feed_tick(self, epoch: int, step: int) -> None:
        """Log-cadence governor observation: difference the goodput
        snapshot against the previous tick's and push the delta into the
        stall window.  Only step/compile/input_wait move between ticks of
        the train loop (eval/checkpoint book their own buckets), so the
        fraction is a pure feed signal.  Pure perf_counter bookkeeping —
        no host sync enters the loop."""
        snap = get_accountant().snapshot()
        last = self._feed_last
        self._feed_last = snap
        if last is None:
            return
        busy = (snap["step"] - last["step"]) \
            + (snap["compile"] - last["compile"])
        wait = snap["input_wait"] - last["input_wait"]
        if busy + wait <= 0 and not self._governor.consensus:
            # zero-delta local tick: nothing to learn — but under
            # consensus the tick still runs (its allgather is a
            # collective every host must join at this cadence; the
            # governor drops the empty sample itself, and FeedWindow
            # still drops negative deltas from accountant resets)
            return
        self._governor.tick(busy, wait, step=step, epoch=epoch)

    def _feed_flip_available(self) -> tuple[bool, str]:
        """Eligibility of the governor's rung-2 flip: move augmentation
        (and, instance task, guidance synthesis — the expensive host
        stage) on device at an epoch boundary.  Ineligible configs get
        the reason as a RECOMMENDATION naming the config keys — the
        governor logs it instead of acting."""
        cfg = self.cfg
        if self.task.device_stage is None \
                or self.task.train_transform is None:
            return False, (f"task={self.task.name} has no host "
                           "augmentation to move")
        moves_guidance = self.task.guidance_on_device(cfg)
        already = cfg.data.device_augment and (
            cfg.data.device_guidance or not moves_guidance)
        if already or self._feed_flipped:
            return False, "on-device augmentation + guidance already active"
        if cfg.data.coalesce_wire:
            # unreachable today (coalesce_wire validation requires the
            # prepared cache below) but load-bearing if that chain ever
            # loosens: the dispatch loop runs the wire-built steps, and
            # a flip-changed batch layout is refused mid-training
            return False, (
                "coalesce_wire packed the wire layout from the current "
                "host pipeline — set data.device_augment/"
                "data.device_guidance in the config instead")
        if cfg.data.prepared_cache:
            return False, (
                "prepared cache owns the pipeline front — set "
                "data.device_augment/data.device_guidance (and consider "
                "data.uint8_transfer) in the config instead")
        if cfg.data.loader != "threads":
            return False, (
                "grain loader builds its pipeline up front — set "
                "data.device_augment/data.device_guidance in the config")
        if moves_guidance and not cfg.data.device_guidance:
            from ..ops.guidance_device import FAMILIES as _DEV_FAM
            if cfg.data.guidance not in _DEV_FAM:
                return False, (
                    f"guidance family {cfg.data.guidance!r} has no device "
                    f"implementation (supported: {_DEV_FAM}) — "
                    "data.prepared_cache is the remaining lever")
        what = "flip augmentation"
        if moves_guidance:
            what += " + guidance synthesis"
        return True, (f"move {what} on device "
                      "(data.device_augment=true"
                      + (", data.device_guidance=true"
                         if moves_guidance else "") + ")")

    def _flip_device_path(self) -> None:
        """Apply the rung-2 flip (epoch boundary — the recompile-safe
        seam): rebuild the host transform stacks with the flip/guidance
        stages dropped, install the fused on-device stage, and rebuild
        the compiled steps.  The next dispatch re-traces and books under
        'compile' (the program keys are cleared below).  Val is
        untouched: it keeps the deterministic host path it was built
        with."""
        ok, reason = self._feed_flip_available()
        if not ok:
            raise RuntimeError(f"device-path flip not available: {reason}")
        cfg = self.cfg
        dev_guidance = self.task.guidance_on_device(cfg)
        new_tf = self.task.train_transform(
            cfg, flip=False, geom=not cfg.data.device_augment_geom,
            guidance="none" if dev_guidance else cfg.data.guidance)

        def set_transform(ds):
            subs = getattr(ds, "datasets", None)
            if subs is not None:  # CombinedDataset: per-constituent
                for s in subs:
                    set_transform(s)
            elif hasattr(ds, "transform"):
                ds.transform = new_tf

        set_transform(self.train_set)
        self._step_kwargs["augment"] = self.task.device_stage(
            cfg, True, dev_guidance)
        self.train_step, self.multi_train_step = self._build_steps()
        # the rebuilt programs' first dispatch is a fresh trace+XLA —
        # re-book it as 'compile', not a mysteriously slow 'step'
        self._programs_seen.discard("plain1")
        self._programs_seen.discard("plainK")
        self._feed_flipped = True
        if self.is_main:
            print(f"governor: flipped augmentation"
                  f"{' + guidance' if dev_guidance else ''} on device "
                  "(host stages dropped; steps rebuilt)", flush=True)

    # ------------------------------------------------------------ IR audit
    def audit_programs(self, train_batch=None, val_batch=None) -> dict:
        """``{name: (fn, example_args)}`` for the EXACT jitted programs
        this trainer dispatches — the hook jaxaudit (analysis.ir) traces.
        Args are ShapeDtypeStruct templates: tracing never executes, and
        a struct can never be consumed by the step's donation.

        ``train_batch`` / ``val_batch``: one host batch from the real
        loaders, for configs whose wire format (uint8_transfer,
        packbits_masks, coalesce_wire, device_guidance) a config-derived
        synthesis cannot reproduce; the plain f32 wire synthesizes
        itself.  Under data.coalesce_wire the WIRE-consuming twins are
        audited (they are what the loop dispatches): the caller's real
        batch is packed through the prefetcher's own transform, which
        derives/validates the wire spec and builds the twins if no batch
        has yet.  The K-step program (data.steps_per_dispatch) is
        included when configured."""
        from ..analysis.ir import struct_of

        cfg = self.cfg
        h, w = cfg.data.crop_size
        sds = jax.ShapeDtypeStruct
        if train_batch is None:
            if cfg.data.uint8_transfer or cfg.data.packbits_masks \
                    or cfg.data.coalesce_wire:
                raise ValueError(
                    "this config ships a non-f32 wire "
                    "(uint8_transfer/packbits/coalesce) — pass one real "
                    "host batch from the train loader as train_batch")
            train_batch = {
                "concat": sds((cfg.data.train_batch, h, w,
                               cfg.model.in_channels), jnp.float32),
                "crop_gt": sds((cfg.data.train_batch, h, w),
                               jnp.float32),
            }
        if val_batch is None and not (self._val_device_guidance
                                      or self._val_packbits):
            # the shape the eval loop actually dispatches: the per-host
            # val share, padded to the device multiple exactly as
            # evaluate() does (pad_to_multiple + shard_batch) — NOT the
            # train batch, which eval never sees
            n_proc = jax.process_count()
            n_dev = self.mesh.devices.size
            vb_host = max(1, -(-cfg.data.val_batch // n_proc))
            vb = -(-vb_host // n_dev) * n_dev * n_proc
            val_batch = {
                "concat": sds((vb, h, w, cfg.model.in_channels),
                              jnp.float32),
                "crop_gt": sds((vb, h, w), jnp.float32),
            }
        state_s = struct_of(self.state)
        if cfg.data.coalesce_wire:
            # the dispatched programs are the wire-consuming twins —
            # packing the caller's real host batch through the same
            # transform the prefetcher uses derives (or validates) the
            # wire spec and builds the twins if the first batch hasn't
            batch_s = struct_of(self._pack_wire_transform(
                dict(train_batch)))
            train_fn, multi_fn = self._wire_step, self._wire_multi_step
        else:
            train_fn, multi_fn = self.train_step, self.multi_train_step
            batch_s = struct_of(dict(train_batch))
        programs = {"train_step": (train_fn, (state_s, batch_s))}
        if multi_fn is not None:
            k = cfg.data.steps_per_dispatch
            programs["multi_train_step"] = (
                multi_fn, (state_s,) + (batch_s,) * k)
        if val_batch is not None:
            programs["eval_step"] = (self.eval_step,
                                     (state_s, struct_of(dict(val_batch))))
        return programs

    def audit(self, check: bool = False, contracts_dir: str | None = None,
              **batches) -> dict:
        """Run jaxaudit over :meth:`audit_programs`; returns
        ``{name: report}``.  With ``check``, each report additionally
        carries ``contract_drift`` (the drift lines against the
        checked-in contracts — empty means clean).

        Under ``train.precision`` the JA002 pass audits against the
        policy's declared accumulation points (``ja002_allow``) — the
        strict default would flag the policy's own f32 islands (master-
        grad accumulation, BN stats, the loss) on every report."""
        from ..analysis import contracts as contracts_lib
        from ..analysis import ir as ir_lib

        audit_kwargs = {}
        if getattr(self, "precision", None) is not None:
            audit_kwargs["f32_allow"] = self.precision.ja002_allow()
        if self.cfg.train.reduce_buckets:
            audit_kwargs["overlap_expected"] = True
        with self.mesh:
            reports = ir_lib.audit_many(self.audit_programs(**batches),
                                        **audit_kwargs)
        if check:
            for rep in reports.values():
                rep["contract_drift"] = contracts_lib.check_report(
                    rep, contracts_dir)
        return reports

    def train_epoch(self, epoch: int,
                    guard: PreemptionGuard | None = None,
                    start_batch: int = 0,
                    abort_check=None) -> float:
        """One epoch; returns mean train loss (the reference printed the
        running loss once per epoch, train_pascal.py:207-212).

        ``guard``: stop-consensus checked every ``preempt_check_every``
        steps, so all hosts leave the loop at the same step.
        ``start_batch``: skip the first batches of the epoch's deterministic
        order — the exact-resume continuation of a preempted epoch (the
        returned mean covers only the batches actually trained)."""
        cfg = self.cfg
        self.train_loader.set_epoch(epoch, start_batch=start_batch)
        losses = []
        #: per-dispatch (grad_norm, update_ratio) outputs, aligned with
        #: ``losses`` (sentinel.monitor_grads only; else stays empty)
        aux_outs = []
        monitor = bool(self._step_kwargs.get("sentinel_metrics"))
        self._epoch_batch_order = []
        t0 = time.perf_counter()
        acct = get_accountant()
        # Track the step as a python int (start + i): reading
        # ``self.state.step`` every iteration would block on the device and
        # serialize host data-prep against device compute.
        step0 = int(self.state.step)

        def host_batches():
            # quarantine (sentinel rollback-and-replay): loader indices a
            # past rollback blamed for divergence are skipped on replay;
            # the order list maps each dispatched step back to its loader
            # index so a LATER divergence in this epoch quarantines the
            # right batches even after skips.
            qset = self._quarantine.get(epoch)
            for i, batch in enumerate(self.train_loader):
                idx = start_batch + i
                if qset and idx in qset:
                    continue
                if cfg.debug_asserts:
                    self.task.batch_asserts(batch, cfg)
                self._epoch_batch_order.append(idx)
                yield batch

        # the echo factor in effect for THIS epoch: the config's base, or
        # the governor's armed factor (changed at epoch boundaries only,
        # so it is stable across the epoch's accounting below)
        echo = self._echo

        def echoed(it):
            # Data echoing (config.py: data.echo): repeat each already-placed
            # device batch — zero extra host decode or H2D traffic per echo;
            # the step's advancing RNG gives each echo fresh on-device
            # augmentation when enabled.
            for b in it:
                for _ in range(echo):
                    yield b

        def waited(it):
            # input-wait measured at the batch-fetch boundary: host time
            # blocked on the prefetcher IS the data-pipeline stall signal
            # (the silently-dominant cost FFCV / arxiv 2005.02130 document)
            # — a first-class goodput bucket instead of invisible idle.
            # Pure perf_counter bookkeeping: no host sync enters the loop.
            it = iter(it)
            while True:
                with acct.account("input_wait"):
                    try:
                        b = next(it)
                    except StopIteration:
                        return
                    # chaos seam: injected latency here IS input stall
                    # (books under input_wait); payload poisoning tears
                    # the batch the step is about to consume
                    b = chaos_sites.fire("trainer/batch_fetch", payload=b)
                yield b

        def dispatches(placed):
            """(n_steps, losses) per compiled call: K-step chunks through
            the multi-step program (data.steps_per_dispatch), the epoch
            tail (and the k=1 config) through the single-step one.  The
            wire-consuming twins substitute under data.coalesce_wire —
            read per call, not hoisted: they are built lazily by
            ``host_batches`` while the prefetcher pulls ahead."""
            def dispatch(fn, key, n, args):
                """One compiled call, goodput-attributed: the first
                dispatch of each program pays trace+XLA and books under
                'compile'; repeats are productive 'step' time.  The trace
                trigger ticks BEFORE the call so an armed capture starts
                on (not after) the step it was requested for."""
                step_mark = _NO_STEP_MARK
                if self._trace is not None:
                    self._trace.tick(n)
                    if self._trace.active:
                        # a capture records: the dispatch is a step on the
                        # profiler's timeline, and the capture learns which
                        # program to put its device ops down to.  Off: the
                        # one attribute read above, no JAX call.
                        step_mark = jax.profiler.StepTraceAnnotation(
                            scopes.STEP_ANNOTATION,
                            step_num=step0 + steps_done)
                        self._trace.note_program(fn, (self.state, *args))
                first = key not in self._programs_seen
                with step_mark, acct.account("compile" if first else "step"):
                    self.state, out = fn(self.state, *args)
                if isinstance(out, tuple) and isinstance(out[-1], dict):
                    # (loss[, aux], counters) of a model that sows counters
                    # (telemetry/counters.py): they go aside (read at the
                    # log cadence), the rest is what every step hands back
                    *out, self._last_counters = out
                    out = out[0] if len(out) == 1 else tuple(out)
                if first:
                    self._programs_seen.add(key)
                    # the cost-analysis re-trace books as compile too —
                    # it is trace time, and idle must stay unexplained
                    # time only
                    with acct.account("compile"):
                        self._note_step_cost(fn, (self.state, *args), n)
                else:
                    self._prod_steps += n
                # chaos seam, between dispatches: sigterm here is a
                # preemption landing mid-epoch (through the real guard),
                # nan poisons the LOSS the loop observes (the divergence-
                # detection driver — the state itself trained on real
                # data and stays finite)
                return chaos_sites.fire("trainer/train_step", payload=out)

            def one_step(b):
                if cfg.data.coalesce_wire:
                    return dispatch(self._wire_step, "wire1", 1, (b,))
                return dispatch(self.train_step, "plain1", 1, (b,))

            if cfg.data.steps_per_dispatch <= 1:
                for b in placed:
                    yield 1, one_step(b)
                return
            import itertools
            k = cfg.data.steps_per_dispatch
            it = iter(placed)
            while True:
                chunk = list(itertools.islice(it, k))
                if not chunk:
                    return
                if len(chunk) == k:
                    if cfg.data.coalesce_wire:
                        lv = dispatch(self._wire_multi_step, "wireK", k,
                                      chunk)
                    else:
                        lv = dispatch(self.multi_train_step, "plainK", k,
                                      chunk)
                    yield k, lv
                else:
                    for b in chunk:
                        yield 1, one_step(b)

        steps_done = 0
        interrupted = False
        with self.mesh:
            # Async H2D overlap: up to device_prefetch batches are already
            # placed (sharded) while the current step computes.
            batches = prefetch_to_device(
                host_batches(), self.mesh,
                # a multi-step dispatch consumes K placed batches at once;
                # a window smaller than K would stall the chip on placement
                # at every chunk boundary.  Read live (callable) so the
                # governor's hot resize applies mid-epoch.
                size=lambda: max(self._device_prefetch,
                                 cfg.data.steps_per_dispatch),
                keys=(WIRE_KEY,) if cfg.data.coalesce_wire
                else self.task.device_keys,
                transform=(self._pack_wire_transform
                           if cfg.data.coalesce_wire else None),
                start=start_batch)
            if echo > 1:
                batches = echoed(batches)
            batches = waited(batches)
            # cadence comes from the guard itself (a caller-provided guard
            # may carry its own check_every)
            check = guard.check_every if guard is not None else 1
            for n_steps, out in dispatches(batches):
                if monitor:  # step emits (loss, (grad_norm, ratio))
                    loss, aux = out
                    aux_outs.append(aux)
                else:
                    loss = out
                losses.append(loss)  # device scalar or (K,); sync deferred
                steps_done += n_steps
                step = step0 + steps_done
                # Boundary-crossing test, not a bare modulo: with K-step
                # dispatches the step sequence is K-strided and could skip
                # every `step % check == 0` point for a whole epoch.  All
                # processes see identical (step, n_steps), so the consensus
                # cadence stays synchronized.
                if guard is not None and \
                        (step // check) != ((step - n_steps) // check) and \
                        guard.should_stop():
                    interrupted = True
                    break
                crossed = (step // cfg.log_every_steps) \
                    != ((step - n_steps) // cfg.log_every_steps)
                if crossed and abort_check is not None:
                    # val_overlap: a failure on the val thread (e.g. the
                    # non-finite watchdog) must abort training NOW, not a
                    # full epoch later at the join
                    abort_check()
                if crossed:
                    # The log-cadence sync runs on EVERY process, not just
                    # main: the watchdog below must raise on all hosts
                    # together (loss is replicated, so they all see the
                    # same value) — a main-only raise would leave the other
                    # processes blocked forever at their next collective.
                    # Goodput: this sync pays the deferred device compute
                    # of the steps dispatched since the last crossing —
                    # productive step time (the epoch-end bulk-readback
                    # convention), not idle.  The feed window's busy
                    # delta depends on it: unbooked, a fully-overlapped
                    # feed would read as a ~1.0 stall fraction.
                    with acct.account("step"):
                        loss_vec = np.atleast_1d(jax.device_get(loss))
                    if self._sentinel is not None:
                        # sentinel absorbs the isfinite watchdog: judge
                        # the latest dispatch against the current EMA
                        # (update=False — the epoch-end sweep owns EMA
                        # advancement, in strict step order) and hand a
                        # diverged verdict to fit's rollback path
                        g_vec = r_vec = None
                        if monitor:
                            a = np.atleast_2d(
                                np.asarray(jax.device_get(aux_outs[-1])))
                            g_vec, r_vec = a[:, 0], a[:, 1]
                        rep = self._sentinel.observe(
                            step - n_steps + 1, loss_vec, grad_norms=g_vec,
                            update_ratios=r_vec, update=False)
                        if rep.diverged:
                            raise self._divergence(
                                epoch, step0, rep, step, loss_vec)
                    if self._governor is not None:
                        # feed-governor tick (data/governor.py): one
                        # goodput-snapshot delta into the stall window,
                        # rung-1 prefetch resize may hot-apply.  Rides
                        # the cadence the loop already pays — no extra
                        # host sync.
                        self._feed_tick(epoch, step)
                    if self._sentinel is None and cfg.debug_asserts and \
                            not np.all(np.isfinite(loss_vec)):
                        # bf16 watchdog: surface divergence at the log
                        # cadence instead of training garbage for the rest
                        # of the epoch (see also the epoch-end sweep below).
                        # The whole (K,) dispatch vector is checked, not
                        # just one element — a mid-dispatch blowup must not
                        # slip past the cadence check.
                        off = int(np.flatnonzero(
                            ~np.isfinite(loss_vec))[0])
                        raise FloatingPointError(
                            f"non-finite train loss {loss_vec[off]} at "
                            f"step {step - n_steps + 1 + off} (epoch "
                            f"{epoch}) — divergence; lower optim.lr, "
                            "enable optim.grad_clip_norm, or set "
                            "optim.loss_scale for bf16 underflow")
                    if cfg.telemetry:
                        # the feed's four counters reach the registry here
                        # and at the fit's end, never per step
                        feed_lib.publish()
                    if self.is_main:
                        # Attribute each logged loss to the step that
                        # crossed a cadence boundary, indexing that step's
                        # own element of the (K,) dispatch vector —
                        # loss_vec[-1] at `step` would skew the train/loss
                        # curve by up to K-1 steps.  A single dispatch can
                        # cross SEVERAL boundaries (K > log_every_steps):
                        # every multiple of the cadence inside
                        # (step - n_steps, step] gets its own point.  For
                        # K=1 this is exactly one (loss_vec[0], step).
                        self._log_counters(step)
                        L = cfg.log_every_steps
                        bstep = ((step - n_steps) // L + 1) * L
                        while bstep <= step:
                            loss_now = float(
                                loss_vec[bstep - (step - n_steps) - 1])
                            self.writer.scalars(
                                {"train/loss": loss_now,
                                 "train/lr": float(self.schedule(bstep)),
                                 "train/epoch": epoch}, bstep)
                            bstep += L
        # One bulk readback, not one float() per step: each scalar fetch is a
        # host sync that drains the dispatch pipeline.  Entries are scalars
        # (one per step) or (K,) vectors (one per multi-step dispatch).
        # Goodput: this wait IS the deferred device compute of the epoch's
        # steps landing — productive time, not idle.
        if losses:
            with acct.account("step"):
                fetched, fetched_aux = jax.device_get((losses, aux_outs))
            loss_arr = np.concatenate([np.atleast_1d(x) for x in fetched])
        else:
            loss_arr = np.array([np.nan])
        if self._sentinel is not None and losses:
            # THE EMA-updating sentinel pass: the full epoch's losses in
            # strict step order (free — the bulk readback above already
            # landed them).  Mid-epoch cadence checks judged against a
            # per-epoch-stale EMA; this is where it advances.
            g_arr = r_arr = None
            if monitor and fetched_aux:
                aux_arr = np.concatenate(
                    [np.atleast_2d(np.asarray(x)) for x in fetched_aux])
                g_arr, r_arr = aux_arr[:, 0], aux_arr[:, 1]
            rep = self._sentinel.observe(
                step0 + 1, loss_arr, grad_norms=g_arr,
                update_ratios=r_arr, update=True)
            if rep.diverged:
                raise self._divergence(epoch, step0, rep,
                                       step0 + loss_arr.size, loss_arr)
        bad = np.flatnonzero(~np.isfinite(loss_arr))
        if bad.size and losses and self._sentinel is None:
            # Epoch-end non-finite sweep (free: the losses are already on
            # host).  Always logged; fatal under debug_asserts.  With the
            # sentinel enabled this legacy response is absorbed: a
            # non-finite loss is a 'diverged' verdict handled above.
            msg = (f"{bad.size}/{loss_arr.size} non-finite train losses this "
                   f"epoch (first at epoch step {int(bad[0])}) — divergence "
                   "or bf16 underflow; lower optim.lr, enable "
                   "optim.grad_clip_norm, or set optim.loss_scale")
            if cfg.debug_asserts:
                raise FloatingPointError(msg)
            if self.is_main:
                print(f"warning: {msg}", flush=True)
                self.writer.scalars(
                    {"train/nonfinite_steps": int(bad.size)},
                    int(self.state.step))
        mean_loss = float(np.mean(loss_arr)) if losses else float("nan")
        dt = time.perf_counter() - t0
        if not losses and self._quarantine.get(epoch):
            # every batch of the epoch is quarantined: nothing trained,
            # nothing to log — the caller's loop moves on
            return float("nan")
        # Distinct images ingested — echoed repeats of a batch are not fresh
        # data; reporting them would make any echo setting look like a win.
        # `echo` is this epoch's LIVE factor (governor-armed included).
        n_imgs = steps_done * cfg.data.train_batch / echo
        # An interrupted epoch logs no completed-epoch summary: its partial
        # mean would skew per-epoch curves, and the replayed epoch will log
        # the real one.
        if self.is_main and not interrupted:
            scalars = {"train/epoch_loss": mean_loss,
                       "train/imgs_per_sec": n_imgs / dt if dt > 0 else 0.0,
                       "train/epoch_seconds": dt, "train/epoch": epoch}
            if start_batch:
                scalars["train/resumed_at_batch"] = start_batch
            peak = device_memory_stats()["peak_bytes_in_use"]
            if peak:  # backends without stats (CPU) report zero
                scalars["train/peak_hbm_gb"] = round(peak / 2**30, 3)
            self.writer.scalars(scalars, int(self.state.step))
        return mean_loss

    def _log_counters(self, step: int) -> None:
        """The last dispatch's counters (a model that sows them,
        telemetry/counters.py) into the writer stack (=> metrics.jsonl) and
        the registry (=> /metrics).  Rides the log cadence's existing sync;
        a multi-step dispatch hands back (K,) vectors, combined as each
        counter's declaration says."""
        if not self._last_counters:
            return
        from ..telemetry import counters as counters_lib

        got = {k: float(counters_lib.combine(k, np.atleast_1d(v)))
               for k, v in jax.device_get(self._last_counters).items()}
        self.writer.scalars({f"train/{k}": v for k, v in got.items()}, step)
        if self.cfg.telemetry:
            from ..telemetry import get_registry
            from ..telemetry.registry import is_enabled

            if is_enabled():
                for k, v in got.items():
                    get_registry().gauge(
                        f"train_{k}", "Model counter of the last logged "
                        "train step (telemetry/counters.py)").set(v)

    # ------------------------------------------------- sentinel rollback
    def _divergence(self, epoch: int, step0: int, report, end_step: int,
                    observed) -> _DivergenceDetected:
        """Build the rollback request for a ``diverged`` verdict: the
        quarantine window runs from the verdict's step through the end of
        the observed vector (later steps in the same dispatch trained on
        a state the bad step already poisoned), mapped back to loader
        batch indices via this epoch's dispatch order."""
        first = end_step - len(observed) + 1
        w0 = int(report.step)
        window = [float(x) for x in observed[w0 - first:]]
        # the LIVE echo factor (governor-armed included): each loader
        # batch produced that many steps this epoch, so the step->batch
        # index mapping must divide by it — and the quarantine skip then
        # drops ALL echoes of a poisoned batch on replay (host_batches
        # skips the index before the echo stage re-expands it)
        echo = max(1, self._echo)
        order = self._epoch_batch_order
        idxs = sorted({
            order[j] for s in range(w0, end_step + 1)
            if 0 <= (j := (s - step0 - 1) // echo) < len(order)})
        return _DivergenceDetected(epoch, w0, end_step, idxs, window,
                                   report)

    def _budget_tick(self) -> None:
        raise _RollbackBudgetTick()

    def _last_committed_step(self) -> int | None:
        """Newest checkpoint step the commit ledger vouches for (rollback
        must never target a possibly-torn write; a torn restore target
        would turn one bad batch into a dead run).  With no ledger yet
        (a pre-ledger directory) the manager's newest step is trusted."""
        committed = self.ckpt.committed_steps()
        for s in sorted((int(s) for s in self.ckpt.all_steps()),
                        reverse=True):
            if not committed or s in committed:
                return s
        return None

    def _handle_divergence(self, d: _DivergenceDetected,
                           history: dict) -> int:
        """Rollback-and-replay: budget-check, quarantine the bad window,
        restore the last COMMITTED checkpoint in-process, and return the
        epoch to resume from.  Runs identically on every host (all inputs
        are replicated values or collective ops), so multi-host rollback
        needs no extra consensus."""
        cfg = self.cfg
        # budget FIRST: a run that diverges after every rollback must
        # fail loudly, not loop.  Each rollback books one failure on the
        # breaker; a cleanly completed epoch (fit loop) books a success.
        try:
            self._rollback_breaker.call(self._budget_tick)
        except CircuitOpenError:
            raise FloatingPointError(
                f"sentinel: rollback budget exhausted "
                f"({cfg.sentinel.max_rollbacks} consecutive rollbacks "
                f"without a cleanly completed epoch) — still diverging: "
                f"{d}") from d
        except _RollbackBudgetTick:
            pass
        self._discard_overlapped_val()
        t0 = time.perf_counter()
        self.ckpt.wait()  # land in-flight async saves + refresh the ledger
        target = self._last_committed_step()
        if target is None:
            # fit() saves a step-0 checkpoint when the sentinel is armed,
            # so this means checkpointing itself is broken — surface it
            raise FloatingPointError(
                f"sentinel: diverged with NO committed checkpoint to roll "
                f"back to ({d})") from d
        self.state, meta = self.ckpt.restore(self.state, step=target)
        dt = time.perf_counter() - t0
        self._rollback_seconds.append(dt)
        self.sentinel_rollbacks += 1
        self.sentinel_quarantined_steps += len(d.batch_indices)
        self._quarantine.setdefault(d.epoch, set()).update(d.batch_indices)
        self._sentinel.reset()  # spike verdicts re-warm on the replay
        self._book_rollback(d, target, dt)
        resume_epoch = int(meta.get("epoch", -1)) + 1
        # flight recorder: the replay anchor closing the
        # divergence -> rollback -> replay episode
        events_lib.emit("sentinel", "replay", step=int(self.state.step),
                        epoch=resume_epoch,
                        payload={"rolled_back_to_step": int(target)})
        # completed-epoch history about to be replayed is dropped — the
        # replay logs the real entries (same rule as preempt resume).
        # val entries carry their epoch stamp, so a rollback past a
        # validated epoch (e.g. its best-save was the torn write) cannot
        # leave duplicate val records after the replay re-validates.
        del history["train_loss"][max(0, resume_epoch - self.start_epoch):]
        history["val"] = [m for m in history["val"]
                          if m.get("epoch", -1) < resume_epoch]
        self._resume_start_batch = 0
        if self.is_main:
            print(f"sentinel: diverged at step {d.report.step} "
                  f"({d.report.reason}) — rolled back to committed step "
                  f"{target} in {dt:.2f}s, quarantined batches "
                  f"{d.batch_indices} of epoch {d.epoch}, resuming at "
                  f"epoch {resume_epoch} (rollback "
                  f"{self.sentinel_rollbacks}/"
                  f"{cfg.sentinel.max_rollbacks})", flush=True)
        return resume_epoch

    def _quarantine_records(self, d: _DivergenceDetected) -> list | None:
        """Resolve the quarantined loader batch indices to the exact
        packed records through ``PackedDataset.seek`` — O(1) per sample
        off the pack's index rows.  The batch -> sample mapping is the
        epoch's deterministic order (``DataLoader.batch_sample_indices``);
        None when the train source is not packed (or the loader can't
        map), in which case batch indices remain the ledger's only
        name."""
        from ..data.packed import resolve_packed

        mapper = getattr(self.train_loader, "batch_sample_indices", None)
        if mapper is None or resolve_packed(self.train_set, 0) is None:
            return None
        out = []
        for bi in sorted(d.batch_indices):
            entries = []
            for si in mapper(int(bi), epoch=d.epoch):
                hit = resolve_packed(self.train_set, int(si))
                if hit is None:  # mixed sources: stay honest, omit all
                    return None
                ds, local = hit
                m = ds.seek(local)
                entries.append({"record": m["record"],
                                "image": m["image_id"],
                                "object": m["object"]})
            out.append({"batch_index": int(bi), "records": entries})
        return out

    def _book_rollback(self, d: _DivergenceDetected, target: int,
                       seconds: float) -> None:
        """Durable + telemetry record of one rollback: a quarantine.jsonl
        line (the ledger ops reads back), registry counters, and writer
        scalars."""
        if self.is_main:
            rec = {"epoch": d.epoch, "step_start": d.step_start,
                   "step_end": d.step_end,
                   "batch_indices": list(d.batch_indices),
                   # packed source: the quarantined batches resolved to
                   # the EXACT records via PackedDataset.seek (O(1) off
                   # the index rows — no re-iteration, no decode); null
                   # on fs sources, where batch indices are the only
                   # stable name
                   "records": self._quarantine_records(d),
                   # JSON has no NaN/Inf: non-finite observed losses are
                   # null (the same rule JsonlWriter applies)
                   "losses": [x if np.isfinite(x) else None
                              for x in d.losses],
                   "reason": d.report.reason,
                   "rollback_to_step": int(target),
                   "restore_seconds": round(seconds, 3)}
            with open(os.path.join(self.run_dir, "quarantine.jsonl"),
                      "a") as f:
                f.write(json.dumps(rec) + "\n")
            self.writer.scalars(
                {"train/sentinel_rollbacks": self.sentinel_rollbacks,
                 "train/sentinel_quarantined_steps":
                     self.sentinel_quarantined_steps,
                 "train/sentinel_rollback_to_step": int(target)},
                d.step_end)
        # flight recorder: the rollback itself (every host; quarantine.jsonl
        # above stays the main-only authoritative ledger)
        events_lib.emit(
            "sentinel", "rollback", step=d.step_end, epoch=d.epoch,
            payload={"reason": d.report.reason,
                     "rollback_to_step": int(target),
                     "restore_seconds": round(seconds, 3),
                     "batch_indices": sorted(int(b)
                                             for b in d.batch_indices)})
        if self.cfg.telemetry:
            from ..telemetry import get_registry
            from ..telemetry.registry import is_enabled

            if is_enabled():
                reg = get_registry()
                reg.counter(
                    "train_sentinel_rollbacks_total",
                    "Sentinel-triggered in-process rollbacks").inc()
                reg.counter(
                    "train_sentinel_quarantined_steps_total",
                    "Steps quarantined by sentinel rollbacks"
                ).inc(len(d.batch_indices))
                reg.histogram(
                    "train_sentinel_recovery_seconds",
                    "Rollback restore time (divergence -> resumed state)"
                ).observe(seconds)

    # ------------------------------------------------------------------- eval
    def _eval_metrics(self, state, epoch: int | None = None
                      ) -> tuple[dict, dict | None]:
        """The device/host evaluation half of :meth:`validate` — no writer
        or checkpoint side effects, so it is safe to run on the val-overlap
        thread against a snapshot ``state``."""
        self.val_loader.set_epoch(0)
        # goodput: validation wall-clock books under 'eval' (per-thread
        # stacks keep the val-overlap thread's books separate)
        with get_accountant().account("eval"), self.mesh:
            metrics = self.task.evaluate(
                self.eval_step, state, self.val_loader, self.cfg, self.mesh,
                tasks.ValWire(self._val_device_guidance, self._val_packbits))
        first = metrics.pop("_first_batch", None)
        if self.cfg.debug_asserts and not np.isfinite(metrics["loss"]):
            # Watchdog, val side: a 1-step epoch's train loss is computed
            # BEFORE the diverging update, so the val loss can be the first
            # place non-finite values surface.
            raise FloatingPointError(
                f"non-finite val loss {metrics['loss']} at epoch {epoch} — "
                "divergence; lower optim.lr, enable optim.grad_clip_norm, "
                "or set optim.loss_scale for bf16 underflow")
        return metrics, first

    def validate(self, epoch: int | None = None, log_panels: bool = True,
                 state=None) -> dict:
        state = self.state if state is None else state
        metrics, first = self._eval_metrics(state, epoch)
        self._log_val(metrics, first, epoch, int(state.step),
                      log_panels=log_panels)
        return metrics

    def _log_val(self, metrics: dict, first: dict | None,
                 epoch: int | None, step: int,
                 log_panels: bool = True) -> None:
        """Writer half of validation — main thread only."""
        if self.is_main:
            flat = {"val/loss": metrics["loss"]}
            for key in ("jaccard", "perplexity"):
                if key in metrics:
                    flat[f"val/{key}"] = metrics[key]
            if "best_threshold" in metrics:
                flat["val/best_threshold"] = metrics["best_threshold"]
            for th, v in metrics.get("jaccard_per_threshold", {}).items():
                flat[f"val/jaccard@{th}"] = v
            if "miou" in metrics:
                flat["val/miou"] = metrics["miou"]
                flat["val/pixel_acc"] = metrics["pixel_acc"]
            if epoch is not None:
                flat["val/epoch"] = epoch
            self.writer.scalars(flat, step)
            if log_panels and first is not None:
                try:
                    fig = make_val_panels(first)
                    self.writer.figure("val_panels", fig, step)
                    import matplotlib.pyplot as plt
                    plt.close(fig)
                except Exception:
                    pass  # visualization must never kill training

    # ----------------------------------------------------- val overlap
    def _launch_overlapped_val(self, epoch: int, step: int) -> None:
        """Start validation of the CURRENT state on a thread (val_overlap):
        the next train epoch proceeds while eval forwards interleave on the
        device and the paste-back runs beside the loader.

        The snapshot must be a device-side COPY, not a reference: the
        train step donates its state argument, so the next epoch's first
        step would delete the original buffers while the val thread (and
        the deferred best-save) still read them.  One extra full state in
        HBM until the join; the copy itself is a single pass of HBM
        bandwidth (~ms).  All writer/checkpoint side effects happen at
        :meth:`_join_overlapped_val` on the main thread."""
        import threading

        with self.mesh:
            state = jax.tree.map(
                lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x,
                self.state)
        box: dict = {}

        def run() -> None:
            try:
                box["result"] = self._eval_metrics(state, epoch)
            except BaseException as e:  # re-raised at join
                box["error"] = e

        t = threading.Thread(target=run, name=f"val-overlap-{epoch}",
                             daemon=True)
        t.start()
        self._pending_val = (epoch, step, state, t, box)

    def _poll_overlapped_val_error(self) -> None:
        """Fail fast if the in-flight overlapped validation already died
        (called at the train loop's log cadence): without this, a val-side
        divergence watchdog would only surface at the join, a full train
        epoch after the fact."""
        pending = self._pending_val
        if pending is not None and "error" in pending[4]:
            self._join_overlapped_val(None)  # immediate join; raises

    def _join_overlapped_val(self, history: dict | None,
                             finish: bool = True) -> None:
        """Wait for the in-flight overlapped validation (if any) and apply
        its deferred epoch-end bookkeeping via :meth:`_finish_val`.
        ``finish=False`` waits only (benchmarks timing the schedule must
        not fold checkpoint/panel costs into the measurement)."""
        pending = self._pending_val
        if pending is None:
            return
        self._pending_val = None
        epoch, step, state, thread, box = pending
        thread.join()
        if "error" in box:
            raise box["error"]
        if finish:
            metrics, first = box["result"]
            self._finish_val(metrics, first, epoch, step, state, history)

    def _discard_overlapped_val(self) -> None:
        """Abandon the in-flight overlapped validation: join the thread
        (it reads a valid snapshot; letting it run unsupervised would race
        a later validate() on the shared val loader and pin the extra HBM
        state) and drop its result.  For unwind paths only — a primary
        exception is already propagating, so the box's own error (if any)
        is intentionally swallowed."""
        pending = self._pending_val
        if pending is None:
            return
        self._pending_val = None
        pending[3].join()

    def _finish_val(self, metrics: dict, first: dict | None, epoch: int,
                    step: int, state, history: dict | None) -> None:
        """THE epoch-end validation bookkeeping — one owner for both the
        serial and overlapped schedules (logging, history, best-gated
        checkpoint of ``state`` at ``step``)."""
        self._log_val(metrics, first, epoch, step)
        if history is not None:
            # epoch-stamped: a sentinel rollback must be able to drop the
            # entries of epochs it is about to replay (see
            # _handle_divergence) without positional guesswork
            history["val"].append(dict(metrics, epoch=epoch))
        # best-gating metric, higher is better: the task names it
        name, best = self.task.best(metrics)
        is_best = self.ckpt.save(step, state, metric=best,
                                 extra={"epoch": epoch})
        if is_best and self.is_main:
            self.writer.scalars(
                {f"val/new_best_{name}": best, "val/epoch": epoch}, step)

    # -------------------------------------------------------------------- fit
    def fit(self, guard: PreemptionGuard | None = None,
            epochs: int | None = None) -> dict:
        """The full loop (reference train_pascal.py:180-308): train each
        epoch; validate every ``eval_every``; snapshot every
        ``snapshot_every``; save best on threshold-max Jaccard improvement.

        Trains epochs ``self.start_epoch`` up to ``epochs`` (``cfg.epochs``
        when None) and leaves ``start_epoch`` at the next epoch to train, so
        a further ``fit`` on this trainer continues where this one ended
        (compiled programs, loaders and the run dir stay): ``fit(epochs=1)``
        then ``fit()`` trains every epoch once.  Schedules span
        ``cfg.epochs`` whatever ``epochs`` says.

        Preemption: unless disabled (``checkpoint.save_on_preempt=false``),
        SIGTERM/SIGINT triggers a consensus stop, one final full-state
        checkpoint, and a clean return — ``history["preempted"]`` marks it.
        The save records the epoch position (``epoch_steps_done``); with
        ``checkpoint.exact_resume`` (default) the resumed run continues the
        interrupted epoch at exactly that batch — no batch trains twice and
        none are skipped (the epoch's order is deterministic given
        (seed, epoch)).  Exactness is at batch granularity: a stop landing
        mid-echo (``data.echo > 1``) replays that batch's echoes, a stop on
        the epoch's last step replays the final batch (so epoch-end
        validation/best-gating still run), and a resume whose data-order
        config changed (process count, train batch, seed, echo) replays the
        whole epoch — the recorded offset indexes an order that no longer
        exists.  ``exact_resume=false`` replays the epoch from its start
        unconditionally (batches repeat, none skipped).  Pass your own
        entered ``guard`` to drive stops programmatically (e.g. a
        wall-clock watchdog calling ``trip()``)."""
        cfg = self.cfg
        epochs = cfg.epochs if epochs is None else int(epochs)
        first_epoch = self.start_epoch
        history = {"train_loss": [], "val": []}
        if cfg.profile_epoch is not None and self.is_main and not \
                (self.start_epoch <= cfg.profile_epoch < cfg.epochs):
            print(f"warning: profile_epoch={cfg.profile_epoch} outside the "
                  f"epoch range [{self.start_epoch}, {cfg.epochs}) — no "
                  "trace will be written", flush=True)
        # goodput books cover exactly this fit; the on-demand trace trigger
        # (SIGUSR2 -> bounded XPlane capture under run_dir/trace_on_demand)
        # is armed for its duration.  set_enabled gates EVERY optional
        # instrumentation path (spans, preemption publishing) process-wide,
        # so telemetry=false is the true zero-instrumentation baseline.
        telemetry_set_enabled(cfg.telemetry)
        get_accountant().reset(enabled=cfg.telemetry)
        # flight recorder: the generation's opening anchor — the timeline
        # merger bounds every generation by this fit_start/fit_end pair
        # (an unpaired fit_start IS the crash evidence)
        events_lib.emit(
            "trainer", "fit_start", step=int(self.state.step),
            epoch=self.start_epoch,
            payload={"epochs": epochs,
                     "resumed": bool(self.resume_meta),
                     "plan_crossing": bool(self.resume_plan_crossing)})
        # chaos: arm an env-named fault plan (DPTPU_CHAOS_PLAN) for this
        # fit; with the env unset and nothing armed this is one getenv.
        chaos_sites.maybe_arm_from_env()
        self._prod_steps = 0
        # the accountant's books were just zeroed: a snapshot from a
        # previous fit would difference negative (FeedWindow drops
        # negatives, but a fresh fit starts a fresh window)
        self._feed_last = None
        with contextlib.ExitStack() as stack:
            if self._trace is not None:
                stack.callback(self._trace.close)
                stack.callback(self._trace.install_signal())
            if guard is None and cfg.checkpoint.save_on_preempt:
                guard = stack.enter_context(PreemptionGuard(
                    check_every=cfg.checkpoint.preempt_check_every))
            # an exception unwinding past the loop (train-side watchdog,
            # Ctrl-C without a guard) must not strand the val-overlap
            # thread: it would race a later validate() on the shared val
            # loader and pin the snapshot's HBM.  Normal completion joins
            # with full bookkeeping below, making this a no-op.
            stack.callback(self._discard_overlapped_val)
            if self._sentinel is not None and \
                    self._last_committed_step() is None:
                # the sentinel's rollback target must EXIST before the
                # first divergence can strike: a fresh run commits its
                # initial state (step 0, or the resumed step) up front, so
                # an epoch-0 divergence rolls back to init instead of
                # failing with nothing to restore
                self.ckpt.save(int(self.state.step), self.state,
                               extra={"epoch": self.start_epoch - 1})
                self.ckpt.wait()
            #: profile_epoch's capture while it is open: the epoch's train
            #: steps, its validation and its save (for the last epoch the
            #: final wait too) land in ONE trace, on the device's clock
            profiling = stack.enter_context(contextlib.ExitStack())
            epoch = self.start_epoch
            while epoch < epochs:
                t0 = time.perf_counter()
                sb = self._resume_start_batch  # only the run's first epoch
                self._resume_start_batch = 0
                estep0 = int(self.state.step)
                profiling.close()  # the previous epoch's, if it was traced
                if cfg.profile_epoch == epoch and self._trace is not None:
                    # Op-level device trace of one epoch (SURVEY §5.1: the
                    # reference had only wall-clock prints), through the
                    # one capture path: XPlane files for tensorboard/xprof
                    # plus scope_table.json / scope_summary.json under the
                    # run dir.
                    profiling.enter_context(self._trace.region(
                        os.path.join(self.run_dir, "profile")))
                try:
                    epoch_loss = self.train_epoch(
                        epoch, guard=guard, start_batch=sb,
                        abort_check=(self._poll_overlapped_val_error
                                     if cfg.val_overlap else None))
                except _DivergenceDetected as d:
                    # rollback-and-replay: restore the last committed
                    # checkpoint, quarantine the bad window, re-enter the
                    # loop at the restored epoch (budget-bounded — the
                    # handler raises when the CircuitBreaker is open)
                    epoch = self._handle_divergence(d, history)
                    continue
                # the previous epoch's overlapped validation ran during
                # this train epoch; land its bookkeeping (best save, logs)
                # before this epoch's own epoch-end work
                self._join_overlapped_val(history)
                step = int(self.state.step)
                if guard is not None and guard.should_stop():
                    # The partial epoch is not appended to history; the
                    # resumed run continues it at the recorded batch
                    # (checkpoint.exact_resume) or replays it in full.
                    history["preempted"] = True
                    # shield(): signals delivered during the final save and
                    # flush are absorbed (no escalation), so a scheduler's
                    # follow-up SIGTERM cannot kill the very checkpoint this
                    # stop exists to land.
                    with guard.shield():
                        if self.ckpt.latest_step() != step:
                            self.ckpt.save(
                                step, self.state,
                                extra={"epoch": epoch - 1,
                                       "interrupted_epoch": epoch,
                                       # epoch position in steps, counting
                                       # what an earlier partial run of this
                                       # same epoch already consumed
                                       "epoch_steps_done":
                                           sb * self._echo
                                           + (step - estep0),
                                       # the batch order's identity; a
                                       # change in any of these makes the
                                       # offset stale -> _resume falls back
                                       # to replay.  The LIVE echo: a
                                       # governor-armed factor differs from
                                       # the resumed config's base, so the
                                       # resume safely replays the epoch.
                                       "num_shards": jax.process_count(),
                                       "echo": self._echo,
                                       "train_batch": cfg.data.train_batch,
                                       "seed": cfg.seed,
                                       "preempted": True})
                        self.ckpt.wait()
                    if self.is_main:
                        self.writer.scalars(
                            {"preempted_at_epoch": epoch}, step)
                    break
                history["train_loss"].append(epoch_loss)
                if self._governor is not None:
                    # the recompile-safe seam: device-path flip / echo
                    # arm / hysteresis disarm land BETWEEN epochs, before
                    # validation (val books its own goodput bucket, so it
                    # never pollutes the stall window either way)
                    self._governor.epoch_boundary(epoch=epoch, step=step)
                if self._rollback_breaker is not None:
                    # a cleanly completed epoch closes the rollback
                    # breaker: the budget bounds CONSECUTIVE rollbacks,
                    # not lifetime ones (config.sentinel.max_rollbacks)
                    self._rollback_breaker.call(lambda: None)
                extra = {"epoch": epoch}
                if cfg.eval_every and (epoch + 1) % cfg.eval_every == 0:
                    if cfg.val_overlap:
                        # validate concurrently with the NEXT train epoch
                        # (joined after it); the last epoch's launch is
                        # joined right after the loop
                        self._launch_overlapped_val(epoch, step)
                    else:
                        metrics, first = self._eval_metrics(self.state,
                                                            epoch)
                        self._finish_val(metrics, first, epoch, step,
                                         self.state, history)
                elif cfg.checkpoint.snapshot_every and \
                        (epoch + 1) % cfg.checkpoint.snapshot_every == 0:
                    self.ckpt.save(step, self.state, extra=extra)
                if self.is_main:
                    self.writer.scalars(
                        {"epoch": epoch,
                         "epoch_total_seconds": time.perf_counter() - t0},
                        step)
                epoch += 1
            # Flush inside the stack (and shielded): the graceful-stop
            # handlers must stay installed, and escalation deferred, until
            # the last async save has committed.
            with guard.shield() if guard is not None else contextlib.nullcontext():
                # the final epoch's overlapped validation has no train
                # epoch to hide behind; land it before the last save wait
                self._join_overlapped_val(history)
                self.ckpt.wait()
            profiling.close()  # a traced last epoch holds that wait
            # a further fit continues here (a preempted epoch is replayed)
            self.start_epoch = epoch
            # after the last save has landed, so its wait is in the books
            self._report_goodput(history)
            # recovery block (the bench/report schema, train/sentinel.py):
            # populated when the sentinel ran, None when it was off — the
            # key itself is always present
            if self._sentinel is not None:
                from ..utils.profiling import percentile
                from .sentinel import make_recovery_block
                history["recovery"] = make_recovery_block(
                    rollbacks=self.sentinel_rollbacks,
                    quarantined_steps=self.sentinel_quarantined_steps,
                    # supervisor_restarts stays None here — a supervisor
                    # concept; dptpu-supervise folds its own count into
                    # the summaries it aggregates
                    recovery_p50_s=(
                        round(percentile(self._rollback_seconds, 50), 3)
                        if self._rollback_seconds else None))
            else:
                history["recovery"] = None
            # feed block (data/governor.py): the governor's summary —
            # windowed stall fraction, effective echo, the action tally.
            # Key always present; None when the governor is off (the
            # recovery-block convention).
            history["feed"] = (self._governor.summary_block()
                               if self._governor is not None else None)
            if self.is_main:
                # fit_summary.json: the one file a SUPERVISOR (or operator)
                # can classify an exited run by without Orbax — written
                # atomically so a crash mid-write reads as "no summary"
                # (= crashed), never as a torn verdict
                atomic_write_json(
                    os.path.join(self.run_dir, "fit_summary.json"),
                    {"preempted": bool(history.get("preempted")),
                     "completed": not history.get("preempted"),
                     "final_step": int(self.state.step),
                     "start_epoch": first_epoch,
                     "epochs": cfg.epochs,
                     "epochs_recorded": len(history["train_loss"]),
                     "recovery": history["recovery"],
                     "feed": history["feed"],
                     # {mfu, peak_source, flops_source, ...} on a TPU,
                     # null elsewhere
                     "mfu": history.get("mfu"),
                     # the resolved plan this run actually trained under
                     # (under strategy=auto, the ladder's pick)
                     "plan": self.plan.block()})
            gp = history.get("goodput") or {}
            events_lib.emit(
                "trainer", "fit_end", step=int(self.state.step),
                payload={"preempted": bool(history.get("preempted")),
                         "epochs_recorded": len(history["train_loss"]),
                         "rollbacks": self.sentinel_rollbacks,
                         # the goodput breakdown rides the closing anchor
                         # so the doctor's wall-clock sinks need no
                         # writer-specific metrics file
                         "goodput": {
                             "total_s": gp.get("total_s"),
                             "buckets": gp.get("buckets"),
                             "productive_frac": gp.get("goodput")}})
            self.writer.flush()
        return history

    def close(self) -> None:
        if self._trace is not None:
            self._trace.close()
        self.ckpt.close()
        self.writer.close()
        # restores any outer event log (a flywheel's, when the fit ran
        # in-process) as the current sink
        events_lib.release(self._events)
        self._events = None
