"""Benchmark: steady-state training throughput of the flagship model.

Prints ONE JSON line:
    {"metric": "...", "value": N, "unit": "imgs/sec/chip", "vs_baseline": N,
     "flops_per_step": N, "tflops_per_sec_per_chip": N, "mfu_vs_peak": N}

Measures the full jitted train step (forward + multi-output loss + backward +
SGD update) for DANet-ResNet101 on 512x512 4-channel inputs — the reference's
exact training configuration (train_pascal.py:65,86,118,127) — on the TPU
chip(s) JAX finds.  With no TPU it exits non-zero and prints nothing, unless
``JAX_PLATFORMS=cpu`` asks by name for the downsized CPU smoke (record labelled
``platform: cpu``, ``mfu: null``).  On TPU the step runs the PR-8 fast path
by default: bf16 mixed precision (f32 master params,
`precision` block in the record), the fused Pallas dual-attention kernels
(model.attention_impl=auto), and the bucketed overlapped gradient all-reduce
(`reduce_buckets`); ``--check-regression`` gates the number against the
newest committed same-config BENCH record (>10% drop exits non-zero).

``vs_baseline``: the reference published no numbers (BASELINE.json.published
== {}; its epoch timer printed to a console nobody recorded), so there is no
honest throughput ratio to print.  The defensible, falsifiable ratio is
**MFU**: XLA's own ``cost_analysis()`` FLOP count for the exact compiled
step, times measured steps/sec, over the chip's published peak —
``vs_baseline`` IS ``mfu_vs_peak``.  (Earlier rounds ratioed against an
invented 5.0 imgs/s/chip GPU estimate; that fiction is retired.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_parser = argparse.ArgumentParser(
    description=((__doc__ or "").splitlines() or [None])[0])
_parser.add_argument(
    "--serve", action="store_true",
    help="bench the serve/ inference service instead of the train step: "
         "synthetic client load against the micro-batcher, reporting "
         "requests/sec + p50/p99 latency in the standard record schema")
_parser.add_argument(
    "--sessions", action="store_true",
    help="with --serve: bench the interactive click loop through "
         "serve/sessions — 1 cold click + N warm clicks per session "
         "against a split (guidance_inject='head') predictor, reporting "
         "warm/cold latency and the cache counters in a `sessions` "
         "record block")
_parser.add_argument(
    "--fleet", type=int, default=None, metavar="N",
    help="with --serve: put N in-process replica services behind the "
         "serve/fleet consistent-hash router (attach mode) and bench "
         "the ROUTED click loop — aggregate clicks/sec plus the "
         "proxy-vs-direct p50 overhead in a `fleet` record block "
         "(null on every off-fleet record)")
_parser.add_argument(
    "--check-regression", action="store_true",
    help="after the record prints, compare it against the NEWEST "
         "same-config committed BENCH_*.json and exit non-zero on a "
         ">10%% throughput regression — the bench trajectory as a gate, "
         "not a single data point")
# this module is also imported (by tests): only read argv when bench.py IS
# the program, so a host process keeps its own -h/--help and flags
_CLI_ARGS, _ = _parser.parse_known_args(
    sys.argv[1:] if __name__ == "__main__" else [])

import jax  # noqa: E402

from distributedpytorch_tpu.backend_health import (  # noqa: E402
    enable_compile_cache,
    require_accelerator,
)

# One device policy (backend_health): a TPU, or the CPU when JAX_PLATFORMS=cpu
# asked for it by name — the downsized smoke whose record says platform: cpu
# and carries no MFU.  No chip and no such request: exit before any record.
ON_TPU = require_accelerator("bench.py") == "tpu"
enable_compile_cache()

import numpy as np  # noqa: E402
import optax  # noqa: E402

# Peak dense-matmul throughput and HBM bandwidth per chip, keyed by
# device_kind substring.  The tables moved to telemetry/goodput.py (the
# trainer's MFU estimator shares them); these module attributes remain the
# bench-side names.
from distributedpytorch_tpu.telemetry.goodput import (  # noqa: E402
    PEAK_FLOPS_BY_KIND,
    PEAK_HBM_BY_KIND,
    mfu_estimate,
    xla_step_cost,
)
from distributedpytorch_tpu.chaos import sites as chaos_sites  # noqa: E402
from distributedpytorch_tpu.data.governor import feed_block  # noqa: E402
from distributedpytorch_tpu.telemetry import get_accountant  # noqa: E402
from distributedpytorch_tpu.telemetry.events import events_block  # noqa: E402
from distributedpytorch_tpu.train.precision import (  # noqa: E402
    precision_block,
    precision_policy,
)
from distributedpytorch_tpu.train.elastic import (  # noqa: E402
    elastic_block,
)
from distributedpytorch_tpu.train.continuous import (  # noqa: E402
    flywheel_block,
)
from distributedpytorch_tpu.train.sentinel import (  # noqa: E402
    recovery_block,
)


def ir_audit_fields(fn, args, program: str, **audit_kw) -> dict:
    """The record's IR-audit fields (jaxaudit, analysis/ir.py): the
    compiled program's collective inventory, its compile-contract
    status ('pass' | 'drift' | 'no_contract' | 'skipped'),
    and the audit's own wall-clock attribution (audit_ms:
    lower/compile/walk millis, null when skipped).  All three keys are
    ALWAYS present so record consumers can rely on the schema;
    DPTPU_BENCH_AUDIT=0 skips the audit; an audit that raises fails the
    run (a record whose program could not be audited is not a record).
    The trace
    is cache-shared with the MFU estimator's lowering (telemetry
    .lowering), so the inventory costs no extra lower on the hot path.

    Bench programs are named by their bench config (model/backbone/
    size/batch vary by env knobs and platform) so they can NEVER collide
    with the canonical contract set — a 512px TPU forward pinned under
    the canonical 64px name would poison `jaxaudit check` everywhere.
    A fresh setup therefore starts at 'no_contract':
    DPTPU_BENCH_AUDIT_UPDATE=1 pins the current program as that
    config's contract, after which every later record reports
    pass/drift against it.

    ``audit_kw`` passes through to the auditor: the bf16 bench step
    audits against the precision policy's declared accumulation points
    (f32_allow), and the bucketed step stamps overlap_expected so a
    TPU-pinned bench contract requires async -start collectives."""
    fields = {"collectives": None, "ir_contract": "skipped",
              "audit_ms": None}
    if os.environ.get("DPTPU_BENCH_AUDIT", "1") == "0":
        return fields
    from distributedpytorch_tpu.analysis import contracts as _contracts
    from distributedpytorch_tpu.analysis import ir as _ir

    rep = _ir.audit(fn, _ir.struct_of(tuple(args)), name=program,
                    **audit_kw)
    fields["collectives"] = rep["collectives"]
    fields["audit_ms"] = rep.get("timing_ms")
    if os.environ.get("DPTPU_BENCH_AUDIT_UPDATE") == "1":
        _contracts.save_contract(
            _contracts.contract_from_report(rep),
            _contracts.default_contracts_dir())
    fields["ir_contract"] = _contracts.check_report_status(rep)
    return fields


def _kind_lookup(table: dict) -> float | None:
    kind = jax.devices()[0].device_kind.lower()
    for sub, val in table.items():
        if sub in kind:
            return val
    return None


def peak_flops_per_chip() -> float | None:
    return _kind_lookup(PEAK_FLOPS_BY_KIND)


def peak_hbm_bw_per_chip() -> float | None:
    return _kind_lookup(PEAK_HBM_BY_KIND)


def step_cost(step, state, batch) -> dict:
    """XLA's cost model for the exact compiled train step (whole global
    batch): FLOPs and HBM bytes accessed — the two roofline inputs.  One
    lower+compile; the executable is cache-shared with the timed run.
    (Thin wrapper over the shared telemetry helper, kept for the
    bench-side name.)"""
    return xla_step_cost(step, state, batch)

# The real config on TPU; a downsized one for the CPU smoke.
BATCH = 8 if ON_TPU else 2
#: batch override for the b16 A/Bs with the same
#: cost-model/roofline fields as the official record
if os.environ.get("DPTPU_BENCH_BATCH"):
    BATCH = int(os.environ["DPTPU_BENCH_BATCH"])
SIZE = 512 if ON_TPU else 64
BACKBONE = "resnet101" if ON_TPU else "resnet18"
DTYPE = "bfloat16" if ON_TPU else "float32"
STEPS = 20 if ON_TPU else 3
WARMUP = 3 if ON_TPU else 1
#: A/B hook for the roofline lever without editing the bench: set
#: DPTPU_BENCH_SCORE_DTYPE=bfloat16 to materialize the PAM's N^2 scores
#: half-width (model.pam_score_dtype; softmax math stays f32).  Default
#: keeps the reference-like f32 scores until the accuracy side
#: (convergence run d) justifies flipping it.
SCORE_DTYPE = os.environ.get("DPTPU_BENCH_SCORE_DTYPE") or None
#: DPTPU_BENCH_BN_STATS=compute drops flax's f32 promotion of BN batch
#: statistics (model.bn_fp32_stats=false) — the measured-mechanism A/B for
#: the convert_reduce_fusion chains (46% of b8 device time, the largest
#: b16 regression term).
BN_FP32_STATS = os.environ.get("DPTPU_BENCH_BN_STATS") != "compute"
#: DPTPU_BENCH_REMAT=1 [+ DPTPU_BENCH_REMAT_POLICY=dots_saveable]: the
#: explicit-remat-policy A/B against XLA's auto-remat at b16.
REMAT = os.environ.get("DPTPU_BENCH_REMAT") == "1"
REMAT_POLICY = os.environ.get("DPTPU_BENCH_REMAT_POLICY") or None
#: DPTPU_BENCH_MODEL=deeplabv3 benches BASELINE config 4 (DeepLabV3-R101
#: os=16, 513², 21-class softmax CE, 3-channel input) with the same
#: MFU/roofline fields as the flagship.  Default: the flagship DANet.
BENCH_MODEL = os.environ.get("DPTPU_BENCH_MODEL", "danet")
#: train.precision for the bench step: the mixed-precision policy (bf16
#: compute, f32 master params — train/precision.py) rides the existing
#: DTYPE split (bf16 on TPU, f32 on CPU smoke); DPTPU_BENCH_PRECISION
#: overrides for A/Bs.  The record's `precision` block carries it
#: (null when f32 — keys always present).
PRECISION = os.environ.get("DPTPU_BENCH_PRECISION") or DTYPE
#: parallel plan for the bench step (parallel/plan.py):
#: DPTPU_BENCH_STRATEGY names a ladder rung (dp | dp_tp | dp_zero1 |
#: dp_tp_zero1) and the planner resolves mesh + composed shardings —
#: the dp_tp A/B measures the TP boundary collectives' cost on real
#: hardware.  Default: plain dp (the committed trajectory).  The
#: record's `plan` block carries it (null for the trivial dp default,
#: the precision-block convention, so pre-planner history stays
#: comparable).
BENCH_STRATEGY = os.environ.get("DPTPU_BENCH_STRATEGY", "") or "dp"
#: train.reduce_buckets for the bench step: reverse-topo bucketed
#: gradient all-reduce (comm/compute overlap) — default 8 on TPU where
#: the async scheduler exploits it, 0 on the CPU smoke (keeps the
#: downsized program aligned with the cpu8 canonical contract shapes)
#: and 0 under model-axis plans (buckets compose with dp/dp_zero1 only
#: — plan.BUCKET_COMPATIBLE; an explicit env override of both knobs
#: fails loudly through the step's planner-routed guard).
#: DPTPU_BENCH_REDUCE_BUCKETS overrides for the overlap A/B.
REDUCE_BUCKETS = int(os.environ.get(
    "DPTPU_BENCH_REDUCE_BUCKETS",
    "8" if ON_TPU and BENCH_STRATEGY in ("dp", "dp_zero1") else "0"))
#: DPTPU_BENCH_GOVERNOR=observe|auto stamps the train record's `feed`
#: block as GOVERNED and arms the --check-regression feed gate: the
#: record's measured input_wait fraction must sit at or below the
#: governor target (DPTPU_BENCH_GOVERNOR_TARGET, default the config's
#: data.governor_target) — ROADMAP item 2's "input_wait ≈ 0 on the
#: bench config" acceptance, made mechanical.  Unset = ungoverned
#: (feed.governor null): the fraction is still measured and recorded,
#: nothing gates.  Observation-only either way: the bench's timed loop
#: is never actuated.
BENCH_GOVERNOR = os.environ.get("DPTPU_BENCH_GOVERNOR") or None
#: DPTPU_BENCH_SOURCE=packed stamps the record's feed.source (fs =
#: per-sample decode off the tree, packed = dptpu-pack mmap records,
#: data/packed.py).  The bench's timed loop steps PRE-PLACED synthetic
#: batches — it exercises no input plane, so the stamp is a LABEL for
#: history hygiene, not a measured difference: it keys
#: --check-regression's same-config filter (a packed-labeled record
#: never baselines an fs one — the contract any future feed-bound bench
#: mode and trainer-derived records rely on) and counts as a non-default
#: A/B in _is_default_config.  The behavioral acceptance lives in the
#: FEED gate: a governed source=packed record must measure stall <=
#: data.governor_target.  Default: fs.
BENCH_SOURCE = os.environ.get("DPTPU_BENCH_SOURCE") or "fs"
#: DPTPU_BENCH_QUANTIZE=int8 serves the --serve benches through the
#: int8-quantized forward (serve/quantize; per-channel symmetric
#: weights, dequant-at-use).  The record's `quantization` block carries
#: the regime (null when unquantized — the `precision` convention) and
#: keys --check-regression's same-config filter: an int8 record never
#: baselines the f32 serving trajectory.
BENCH_QUANTIZE = os.environ.get("DPTPU_BENCH_QUANTIZE") or None
#: DPTPU_BENCH_AOT_CACHE=DIR threads the --serve benches' warmup
#: through the AOT executable cache (serve/aot): a warm cache boots
#: with zero XLA compiles and the record's `cold_start` block shows the
#: measured warmup-seconds win (aot_cache=hit) vs the cold-compile
#: baseline (off/miss).  A cold dir is BUILT after the bench so the
#: next run measures the warm boot — the A/B is two consecutive runs.
#: The cold_start.aot_cache value keys the same-config filter: an
#: AOT-warm record never baselines a cold-compile one.
BENCH_AOT_CACHE = os.environ.get("DPTPU_BENCH_AOT_CACHE") or None


def _governor_target() -> float:
    env = os.environ.get("DPTPU_BENCH_GOVERNOR_TARGET")
    if env:
        return float(env)
    from distributedpytorch_tpu.train.config import DataConfig

    return DataConfig().governor_target

def _is_default_config() -> bool:
    return (BENCH_MODEL == "danet" and not SCORE_DTYPE
            and BN_FP32_STATS and not REMAT
            and not os.environ.get("DPTPU_BENCH_BATCH")
            and not os.environ.get("DPTPU_BENCH_PRECISION")
            and not os.environ.get("DPTPU_BENCH_REDUCE_BUCKETS")
            and not os.environ.get("DPTPU_BENCH_STRATEGY")
            and not os.environ.get("DPTPU_BENCH_SOURCE")
            and not os.environ.get("DPTPU_BENCH_QUANTIZE")
            and not os.environ.get("DPTPU_BENCH_AOT_CACHE"))


# -------------------------------------------------- regression gate
#: --check-regression failure threshold: a >10% throughput drop against
#: the newest committed same-config record fails the run
REGRESSION_THRESHOLD = 0.10


def load_bench_history(history_dir: str | None = None) -> list:
    """``[(path, record), ...]`` from the committed ``BENCH_*.json``
    round records, oldest-first (lexicographic — the driver names them
    ``BENCH_r<NN>.json``).  Each file is either a bare record or the
    driver's ``{"cmd": ..., "parsed": {record}}`` wrapper; unreadable
    files are skipped (history must never crash a record run)."""
    import glob

    history_dir = history_dir or os.path.dirname(os.path.abspath(__file__))
    out = []
    for path in sorted(glob.glob(os.path.join(history_dir,
                                              "BENCH_*.json"))):
        try:
            with open(path) as f:
                data = json.load(f)
        except Exception:
            continue
        rec = data.get("parsed") if isinstance(data, dict) else None
        if not isinstance(rec, dict):
            rec = data if isinstance(data, dict) else None
        if isinstance(rec, dict) and "metric" in rec and "value" in rec:
            out.append((path, rec))
    return out


def _feed_source(record: dict) -> str:
    """The record's feed.source, normalized: records predating the
    packed data plane (and serve records, whose ``feed`` is null) read
    as the ``fs`` default."""
    feed = record.get("feed") or {}
    return feed.get("source") or "fs"


def _cold_start_aot(record: dict) -> str:
    """The record's cold_start.aot_cache, normalized: records predating
    the AOT cache (and train records, whose ``cold_start`` is null)
    read as the ``off`` default."""
    cold = record.get("cold_start") or {}
    return cold.get("aot_cache") or "off"


def _events_enabled(record: dict) -> bool:
    """Whether the measured window ran with the flight recorder armed:
    records predating the events block (and telemetry-off runs, whose
    ``events`` block is all-null) read as off — the default."""
    ev = record.get("events") or {}
    return ev.get("path") is not None


def _fleet_replicas(record: dict):
    """The record's fleet.replicas, normalized: records predating the
    fleet front (and direct serve/train records, whose ``fleet`` block
    is null) read as None — off-fleet, the default."""
    fleet = record.get("fleet") or {}
    return fleet.get("replicas")


def check_regression(record: dict, history: list | None = None,
                     threshold: float = REGRESSION_THRESHOLD
                     ) -> tuple[bool, str]:
    """Compare ``record`` against the NEWEST committed record of the
    SAME config: same ``metric`` string (the metric name carries
    model/backbone/size/batch), same ``platform`` (a CPU smoke
    number must never gate against a TPU record), and same
    ``precision`` block + ``reduce_buckets`` + ``plan`` block (a
    bf16+bucketed fast-path number, an f32 serialized-reduce number and
    a dp_tp sharded-plan number are all different trajectories —
    none may baseline another, even if a variant record was committed
    into history).  Returns
    ``(ok, message)``; ``ok=False`` means the throughput dropped more
    than ``threshold``.  No prior record -> ok (a fresh config starts
    its own trajectory)."""
    history = load_bench_history() if history is None else history
    prior = [(p, r) for p, r in history
             if r.get("metric") == record.get("metric")
             and r.get("platform") == record.get("platform")
             and r.get("precision") == record.get("precision")
             and r.get("reduce_buckets") == record.get("reduce_buckets")
             # the feed source joins the config key: a packed-plane
             # record and an fs one measure different input regimes —
             # neither may baseline the other.  Missing key == fs (the
             # default), so pre-pack committed history still compares.
             and _feed_source(r) == _feed_source(record)
             # the quantization block joins the config key: an int8
             # serve record and an f32 one run different programs —
             # neither may baseline the other.  Null == unquantized
             # (the default), so pre-quantization history compares.
             and r.get("quantization") == record.get("quantization")
             # ...and so does the cold-start AOT mode: an AOT-warm
             # record's warmup rode pre-compiled executables — its
             # number never baselines a cold-compile boot (or vice
             # versa).  Missing key == "off", the pre-AOT default.
             and _cold_start_aot(r) == _cold_start_aot(record)
             # the plan block joins the config key: a dp_tp (or any
             # sharded-plan) record and a pure-dp record are different
             # trajectories — neither may baseline the other.  Null ==
             # the trivial dp default, so pre-planner history compares.
             and r.get("plan") == record.get("plan")
             # ...and so does the elastic block: a record whose measured
             # window absorbed supervisor re-plans (topology changes,
             # plan-crossing restores) is a different regime than a
             # static run — never a baseline for one.  Null == static
             # (the default), so pre-elastic history still compares.
             and r.get("elastic") == record.get("elastic")
             # ...and the flywheel block: a record measured while
             # continuous mode was fitting/swapping in-process is a
             # different regime than a static serve/train run.  Null ==
             # flywheel off (the default), so prior history compares.
             and r.get("flywheel") == record.get("flywheel")
             # ...and whether the flight recorder was armed: event
             # emission is pinned <=2% of step, but pinned is not zero —
             # a recorder-armed record and a recorder-off one are
             # different regimes.  Null block == off (the default), so
             # pre-recorder committed history still compares.
             and _events_enabled(r) == _events_enabled(record)
             # ...and the fleet SHAPE: a routed N-replica record and a
             # direct single-service one measure different paths (the
             # proxy hop is real work), and fleet sizes are their own
             # families.  Only the replica count joins the key — the
             # block's measured values (rps, overhead) are the NUMBER,
             # not the config.  Null == off-fleet (the default), so
             # pre-fleet committed history still compares.
             and _fleet_replicas(r) == _fleet_replicas(record)]
    if not prior:
        return True, (f"no prior {record.get('metric')} record on "
                      f"{record.get('platform')}; nothing to compare")
    path, ref = prior[-1]
    old, new = float(ref["value"]), float(record["value"])
    if old <= 0:
        return True, f"prior record in {os.path.basename(path)} is <= 0"
    delta = new / old - 1.0
    msg = (f"{record.get('metric')}: {new:.3f} vs {old:.3f} "
           f"{ref.get('unit', '')} in {os.path.basename(path)} "
           f"({delta:+.1%})")
    if -delta > threshold:
        return False, f"throughput regression past {threshold:.0%}: {msg}"
    return True, msg


def check_feed(record: dict, target: float | None = None
               ) -> tuple[bool, str]:
    """The feed gate of ``--check-regression``: a GOVERNED record's
    measured ``feed.input_wait_fraction`` must sit at or below the
    governor target — the mechanical form of ROADMAP item 2's
    "input_wait ≈ 0 on the bench config" acceptance.  Ungoverned
    records (``feed`` null or ``feed.governor`` null) pass trivially
    with an explanatory message; a governed record missing the measured
    fraction FAILS (an unmeasured gate is no gate)."""
    feed = record.get("feed")
    if not feed or not feed.get("governor"):
        return True, "ungoverned record; feed gate not armed"
    target = _governor_target() if target is None else float(target)
    frac = feed.get("input_wait_fraction")
    if frac is None:
        return False, ("governed record carries no measured "
                       "input_wait fraction — nothing to gate")
    if frac > target:
        return False, (f"input_wait fraction {frac:.4f} above the "
                       f"governor target {target} (feed-bound, not "
                       "chip-bound)")
    return True, (f"input_wait fraction {frac:.4f} <= target {target}")


def _maybe_check_regression(record: dict) -> None:
    """The --check-regression tail of every bench mode: report to
    stderr (stdout is the record), exit 1 on a gated regression."""
    if not _CLI_ARGS.check_regression:
        return
    # the feed gate runs for every fresh record — including A/B
    # variants: a governed variant's stall measurement is exactly what
    # the gate exists to judge, independent of the throughput baseline
    ok, msg = check_feed(record)
    print(f"check-regression (feed): {msg}", file=sys.stderr)
    if not ok:
        raise SystemExit(1)
    if not _is_default_config():
        # A/B variants (DPTPU_BENCH_PRECISION=float32, REDUCE_BUCKETS=0,
        # batch/score-dtype overrides, ...) are exploratory measurements,
        # not trajectory records: a slower-by-design variant must never
        # fail the gate, and committed history only holds default runs
        print("check-regression: skipped (non-default A/B config — the "
              "gate protects the default-config trajectory)",
              file=sys.stderr)
        return
    ok, msg = check_regression(record)
    print(f"check-regression: {msg}", file=sys.stderr)
    if not ok:
        raise SystemExit(1)


#: --serve load shape: enough concurrent closed-loop clients to keep the
#: top bucket fillable, enough requests for a stable p99
SERVE_CLIENTS = 8
SERVE_REQUESTS = 128 if ON_TPU else 64
SERVE_MAX_BATCH = 8

#: --serve --sessions click-loop shape: concurrent interactive sessions,
#: each 1 cold click (encode+decode) + N warm refinement clicks (decode
#: only) — the DEXTR refinement workload, measured
SESSIONS_N = 16 if ON_TPU else 8
SESSION_WARM_CLICKS = 8 if ON_TPU else 6


def _serve_env_extras(predictor):
    """Apply the serve-side A/B env knobs to a freshly built predictor:
    DPTPU_BENCH_QUANTIZE swaps in the int8-quantized forward.  Returns
    ``(predictor, quant_policy)`` (policy None when unquantized)."""
    qpolicy = None
    if BENCH_QUANTIZE:
        from distributedpytorch_tpu.serve.quantize import (
            quant_policy,
            quantize_predictor,
        )

        qpolicy = quant_policy(BENCH_QUANTIZE)
        if qpolicy is not None:
            predictor = quantize_predictor(predictor, qpolicy)
    return predictor, qpolicy


def _cold_start_block(warm: dict | None) -> dict | None:
    """The record's ``cold_start`` block from a service's last warmup —
    keys ALWAYS present on serve records (warmup_seconds,
    programs_compiled, aot_cache), the whole block null on train
    records (the sessions-block convention)."""
    if warm is None:
        return None
    return {"warmup_seconds": warm["warmup_seconds"],
            "programs_compiled": warm["programs_compiled"],
            "aot_cache": warm["aot_cache"]}


def _stamp_serve_fast_path(record: dict, svc, qpolicy):
    """One owner for the serve-record fast-path stamping shared by
    serve_bench and serve_sessions_bench: the ``cold_start`` +
    ``quantization`` blocks, and the quantized audit options — returns
    ``(audit_kw, program_suffix)`` so a quantized record audits against
    the QuantPolicy's declared dequant points under its own ``_int8``
    config name (the config-naming rule)."""
    from distributedpytorch_tpu.serve.quantize import quantization_block

    record["cold_start"] = _cold_start_block(svc.last_warmup)
    record["quantization"] = quantization_block(qpolicy)
    if qpolicy is None:
        return {}, ""
    return {"f32_allow": qpolicy.ja002_allow()}, "_int8"


def _maybe_build_aot_cache(svc, predictor) -> None:
    """DPTPU_BENCH_AOT_CACHE tail: a bench that booted cold against a
    configured cache dir BUILDS the cache afterward, so the NEXT run
    measures the warm boot — the cold-vs-warm A/B is two consecutive
    runs of the same command."""
    if not BENCH_AOT_CACHE:
        return
    if svc.last_warmup and svc.last_warmup["aot_cache"] == "hit":
        return
    from distributedpytorch_tpu.serve.aot import AotCache

    try:
        AotCache(BENCH_AOT_CACHE).build(predictor, svc.buckets)
        print(f"bench: built AOT cache at {BENCH_AOT_CACHE} — re-run "
              "to measure the warm boot", file=sys.stderr)
    except Exception as e:  # a failed build must never kill the record
        print(f"bench: AOT cache build failed "
              f"({type(e).__name__}: {e})", file=sys.stderr)


def _sessions_block(store_snapshot: dict | None,
                    swaps: dict | None,
                    warm_ms: list | None = None,
                    cold_ms: list | None = None) -> dict | None:
    """The record's `sessions` block — keys ALWAYS present (the PR 4/5
    schema-stability convention), the whole block null outside session
    mode."""
    if store_snapshot is None:
        return None
    from distributedpytorch_tpu.utils.profiling import percentile

    warm_p50 = (round(percentile(warm_ms, 50.0), 3) if warm_ms else None)
    cold_p50 = (round(percentile(cold_ms, 50.0), 3) if cold_ms else None)
    return {
        "warm_p50_ms": warm_p50,
        "cold_p50_ms": cold_p50,
        "warm_cold_ratio": (round(warm_p50 / cold_p50, 4)
                            if warm_p50 and cold_p50 else None),
        "evictions": sum((store_snapshot.get("evictions") or {}).values()),
        "swaps": sum((swaps or {}).values()),
    }


def serve_bench():
    """Synthetic client load against serve.InferenceService.

    Fresh-init weights (throughput does not depend on the checkpoint),
    the same model/resolution ladder as the train bench, every bucket
    warmed before the clock starts (compiles are a cold-start cost the
    steady-state number must not include).  SERVE_CLIENTS threads each
    submit their share of SERVE_REQUESTS as a burst and wait — the
    64-request acceptance scenario, measured.
    """
    import threading

    from distributedpytorch_tpu.models import build_model
    from distributedpytorch_tpu.parallel import create_train_state
    from distributedpytorch_tpu.predict import Predictor
    from distributedpytorch_tpu.serve import InferenceService

    model = build_model("danet", nclass=1, backbone=BACKBONE,
                        output_stride=8, dtype=DTYPE)
    state = create_train_state(jax.random.PRNGKey(0), model,
                               optax.sgd(1e-3), (1, SIZE, SIZE, 4))
    predictor = Predictor(model, state.params, state.batch_stats,
                          resolution=(SIZE, SIZE), relax=50)
    predictor, qpolicy = _serve_env_extras(predictor)
    r = np.random.RandomState(0)
    image = r.randint(0, 256, (SIZE, SIZE, 3)).astype(np.uint8)
    quarter, mid = SIZE // 4, SIZE // 2
    jobs = [np.array([[quarter, mid], [SIZE - quarter, mid],
                      [mid, quarter], [mid, SIZE - quarter]], np.float64)
            + float(i % 16) for i in range(SERVE_REQUESTS)]

    svc = InferenceService(predictor, max_batch=SERVE_MAX_BATCH,
                           queue_depth=2 * SERVE_REQUESTS,
                           max_wait_s=0.002, aot_cache=BENCH_AOT_CACHE)
    acct = get_accountant()
    acct.reset()
    with acct.account("compile"):
        svc.warmup()   # compiles off the clock, tripwire stays exact
    with svc:
        errors: list[Exception] = []

        def client(chunk) -> None:
            # submit failures (shed, unhealthy trip) must land in
            # `errors` too — an escaping exception would kill the thread
            # and leave its chunk uncounted but reported as served
            futures = []
            for pts in chunk:
                try:
                    futures.append(svc.submit(image, pts))
                except Exception as e:  # noqa: BLE001 — recorded, reported
                    errors.append(e)
            for f in futures:
                try:
                    f.result(timeout=600)
                except Exception as e:  # noqa: BLE001 — recorded, reported
                    errors.append(e)

        threads = [
            threading.Thread(target=client,
                             args=(jobs[k::SERVE_CLIENTS],))
            for k in range(SERVE_CLIENTS)]
        t0 = time.perf_counter()
        with acct.account("step"):  # the measured burst is the payload
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        dt = time.perf_counter() - t0
        stats = svc.metrics.snapshot()
    goodput_rep = acct.report()

    completed = SERVE_REQUESTS - len(errors)
    record = {
        "metric": (f"danet_{BACKBONE}_{SIZE}px_serve_b{SERVE_MAX_BATCH}"
                   "_throughput"),
        # successes only: an errored request is not served throughput
        "value": round(completed / dt, 3),
        "unit": "requests/sec",
        # no published serving baseline exists; neutral ratio, same rule
        # as the train bench's unknown-hardware branch
        "vs_baseline": 1.0,
        "platform": jax.devices()[0].platform,
        "requests": SERVE_REQUESTS,
        "clients": SERVE_CLIENTS,
        "errors": len(errors),
        "batches": stats["batches"],
        "batch_buckets": stats["batch_buckets"],
        "shed_queue_full": stats["shed_queue_full"],
        "shed_deadline": stats["shed_deadline"],
        "retrace_failures": stats["retrace_failures"],
    }
    if "latency_ms" in stats:
        record["p50_ms"] = stats["latency_ms"]["p50"]
        record["p99_ms"] = stats["latency_ms"]["p99"]
    if "pad_fraction" in stats:
        record["pad_fraction"] = stats["pad_fraction"]
    # standard telemetry fields, same schema as the train record: serving
    # has no per-request FLOPs count, so mfu is explicitly null rather
    # than absent (consumers can rely on the key)
    record["goodput"] = round(goodput_rep["goodput"], 4)
    record["goodput_breakdown"] = {
        k: round(v, 3) for k, v in goodput_rep["buckets"].items() if v}
    record["mfu"] = None
    # feed block: a train-side concept (serving has no input pipeline to
    # govern), null on serve records — key always present
    record["feed"] = None
    # chaos field: the armed fault-injection scenario's name, null when
    # none is armed — key ALWAYS present (schema stability), so record
    # consumers can tell a clean number from a chaos-conditioned one
    record["chaos"] = chaos_sites.active_scenario()
    # sessions block: null outside --sessions mode, key always present
    record["sessions"] = _sessions_block(None, None)
    # recovery block (self-healing, train/sentinel.py): keys always
    # present, all null — the bench's burst loop never runs Trainer.fit,
    # so there is no sentinel to roll anything back
    record["recovery"] = recovery_block()
    # flywheel block (train/continuous.py): continuous-mode tallies —
    # null here (the burst bench serves without a session sink), keys
    # always present; --check-regression's same-config filter keys on
    # it, so a flywheel-exercised record never baselines a static one
    record["flywheel"] = flywheel_block()
    # elastic block: a train-supervision concept, null on serve records
    # — key always present (schema stability)
    record["elastic"] = elastic_block()
    # precision block (train/precision.py): the compute regime the
    # served model actually runs (bf16 on TPU); null when f32 — key
    # always present (schema stability)
    record["precision"] = precision_block(precision_policy(DTYPE))
    # plan block: a TRAIN-side concept (serve replicates the predictor),
    # null on serve records — key always present (schema stability)
    record["plan"] = None
    # fleet block: this burst hits ONE service directly (no router hop)
    # — null off-fleet, key always present (see serve_fleet_bench)
    record["fleet"] = None
    # events block (telemetry/events.py): flight-recorder tallies for
    # the measured window — keys ALWAYS present, all null when the
    # recorder is off (the bench default).  --check-regression keys its
    # same-config filter on it (recorder-armed vs off are regimes).
    record["events"] = events_block()
    # cold_start block (serve/aot): the measured boot tax — warmup
    # seconds, programs compiled (0 on an AOT-warm boot) and the cache
    # outcome; keys always present on serve records, block null on
    # train ones.  quantization block (serve/quantize): the weight
    # regime the burst served; null when unquantized — the precision
    # convention.  Both key --check-regression's same-config filter.
    audit_kw, suffix = _stamp_serve_fast_path(record, svc, qpolicy)
    # IR-audit fields: the top bucket's forward (the program serving the
    # measured burst), same schema as the train record.  Config-named —
    # never the canonical serve_forward_b<N> names, whose contracts pin
    # the 64px audit config, not this bench's resolution.
    record.update(ir_audit_fields(
        predictor.forward_jitted,
        (jax.ShapeDtypeStruct((SERVE_MAX_BATCH, SIZE, SIZE, 4),
                              np.float32),),
        f"bench_serve_{BACKBONE}_{SIZE}px_b{SERVE_MAX_BATCH}{suffix}",
        **audit_kw))
    from distributedpytorch_tpu.utils.profiling import device_memory_stats

    record["peak_bytes_in_use"] = \
        device_memory_stats()["peak_bytes_in_use"]
    # AFTER the memory read: the build's full-ladder recompile must not
    # inflate the record's high-water mark
    _maybe_build_aot_cache(svc, predictor)
    if not ON_TPU:
        record["note"] = ("JAX_PLATFORMS=cpu smoke (downsized config), "
                          "not a device number")
    print(json.dumps(record))
    return record


def serve_sessions_bench():
    """The interactive click loop through serve/sessions, measured.

    SESSIONS_N concurrent sessions each place 1 cold click (encode +
    decode + feature-cache install) and SESSION_WARM_CLICKS refinement
    clicks (decode against the cached on-device features).  The headline
    is the warm/cold latency ratio — the fraction of a full forward an
    interactive refinement actually costs (acceptance: <= 0.5 on the
    CPU smoke, tracking the decode/(encode+decode) contract FLOPs
    split).  Buckets are warmed off the clock, as in the burst bench.
    """
    import threading

    from distributedpytorch_tpu.models import build_model
    from distributedpytorch_tpu.parallel import create_train_state
    from distributedpytorch_tpu.predict import Predictor
    from distributedpytorch_tpu.serve import InferenceService

    model = build_model("danet", nclass=1, backbone=BACKBONE,
                        output_stride=8, dtype=DTYPE,
                        guidance_inject="head")
    state = create_train_state(jax.random.PRNGKey(0), model,
                               optax.sgd(1e-3), (1, SIZE, SIZE, 4))
    predictor = Predictor(model, state.params, state.batch_stats,
                          resolution=(SIZE, SIZE), relax=50)
    predictor, qpolicy = _serve_env_extras(predictor)
    r = np.random.RandomState(0)
    image = r.randint(0, 256, (SIZE, SIZE, 3)).astype(np.uint8)
    quarter, mid = SIZE // 4, SIZE // 2
    base_pts = np.array([[quarter, mid], [SIZE - quarter, mid],
                         [mid, quarter], [mid, SIZE - quarter]],
                        np.float64)

    svc = InferenceService(predictor, max_batch=SERVE_MAX_BATCH,
                           queue_depth=4 * SESSIONS_N, max_wait_s=0.002,
                           aot_cache=BENCH_AOT_CACHE)
    acct = get_accountant()
    acct.reset()
    with acct.account("compile"):
        svc.warmup()
    cold_ms: list[float] = []
    warm_ms: list[float] = []
    lock = threading.Lock()
    errors: list[Exception] = []
    served = [0]   # clicks actually answered with a mask — an errored
    #                cold click aborts its session's whole loop, so the
    #                headline must count answers, not scheduled clicks

    def session_loop(k: int) -> None:
        sid = f"bench-{k}"
        try:
            t0 = time.perf_counter()
            svc.predict(image, base_pts + (k % 8), timeout=600,
                        session_id=sid)
            cold = (time.perf_counter() - t0) * 1e3
            with lock:
                served[0] += 1
            warms = []
            for c in range(SESSION_WARM_CLICKS):
                t0 = time.perf_counter()
                svc.predict(image, base_pts + (k % 8) + (c % 3),
                            timeout=600, session_id=sid)
                warms.append((time.perf_counter() - t0) * 1e3)
                with lock:
                    served[0] += 1
            with lock:
                cold_ms.append(cold)
                warm_ms.extend(warms)
        except Exception as e:  # noqa: BLE001 — recorded, reported
            with lock:
                errors.append(e)

    with svc:
        threads = [threading.Thread(target=session_loop, args=(k,))
                   for k in range(SESSIONS_N)]
        t0 = time.perf_counter()
        with acct.account("step"):
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        dt = time.perf_counter() - t0
        stats = svc.metrics.snapshot()
        store_snap = svc.health()["sessions"]
        swaps = svc.health()["swap"]["swaps"]
    goodput_rep = acct.report()

    clicks = served[0]
    record = {
        "metric": (f"danet_{BACKBONE}_{SIZE}px_sessions"
                   f"_s{SESSIONS_N}x{SESSION_WARM_CLICKS}_click_loop"),
        "value": round(clicks / dt, 3),
        "unit": "clicks/sec",
        "vs_baseline": 1.0,     # no published interactive baseline
        "platform": jax.devices()[0].platform,
        "sessions_n": SESSIONS_N,
        "warm_clicks_per_session": SESSION_WARM_CLICKS,
        "errors": len(errors),
        "batches": stats["batches"],
        "batch_buckets": stats["batch_buckets"],
        "shed_queue_full": stats["shed_queue_full"],
        "shed_session_lane": stats["shed_session_lane"],
        "shed_deadline": stats["shed_deadline"],
        "retrace_failures": stats["retrace_failures"],
        "session_hits": store_snap["hits"],
        "session_misses": store_snap["misses"],
        "session_live_bytes": store_snap["live_bytes"],
        "sessions": _sessions_block(store_snap, swaps, warm_ms, cold_ms),
    }
    record["goodput"] = round(goodput_rep["goodput"], 4)
    record["goodput_breakdown"] = {
        k: round(v, 3) for k, v in goodput_rep["buckets"].items() if v}
    record["mfu"] = None
    record["feed"] = None  # train-side concept, null on serve records
    record["chaos"] = chaos_sites.active_scenario()
    record["recovery"] = recovery_block()  # null block; key stability
    record["flywheel"] = flywheel_block()  # no sink in this loop; key
    #                                        always present (see serve_bench)
    record["elastic"] = elastic_block()  # train-side concept; key present
    # precision block: the served model's compute regime; null when f32
    record["precision"] = precision_block(precision_policy(DTYPE))
    # plan block: train-side concept, null on serve records; key present
    record["plan"] = None
    # fleet block: direct in-process clicks, no router hop — null
    # off-fleet, key always present (see serve_fleet_bench)
    record["fleet"] = None
    # events block: flight-recorder tallies, all null when the recorder
    # is off (see serve_bench); keys always present
    record["events"] = events_block()
    # cold_start + quantization blocks — the serve-record pair (see
    # serve_bench); keys always present
    audit_kw, suffix = _stamp_serve_fast_path(record, svc, qpolicy)
    # IR audit of the warm hot path (the decode program at the top
    # bucket) — config-named, same convention as the burst bench
    feats = predictor.feature_struct(1)
    record.update(ir_audit_fields(
        predictor.decode_jitted,
        (jax.ShapeDtypeStruct((SERVE_MAX_BATCH, *feats.shape[1:]),
                              feats.dtype),
         jax.ShapeDtypeStruct((SERVE_MAX_BATCH, SIZE, SIZE, 1),
                              np.float32)),
        f"bench_serve_decode_{BACKBONE}_{SIZE}px_b{SERVE_MAX_BATCH}"
        f"{suffix}", **audit_kw))
    from distributedpytorch_tpu.utils.profiling import device_memory_stats

    record["peak_bytes_in_use"] = \
        device_memory_stats()["peak_bytes_in_use"]
    # AFTER the memory read (see serve_bench)
    _maybe_build_aot_cache(svc, predictor)
    if not ON_TPU:
        record["note"] = ("JAX_PLATFORMS=cpu smoke (downsized config), "
                          "not a device number")
    print(json.dumps(record))
    return record


def serve_fleet_bench():
    """The click loop ROUTED: N replica services behind the fleet front.

    The same interactive load as ``--sessions`` (SESSIONS_N sessions,
    1 cold + N warm clicks each) — but through serve/fleet's
    consistent-hash router over ``--fleet N`` in-process replicas, each
    a real :class:`InferenceService` behind its own HTTP server (attach
    mode: the router's own path, none of local mode's process
    supervision noise in the number).  All replicas share one compiled
    predictor — the bench isolates the ROUTING tax, not N compiles.

    Two measurements ride in the ``fleet`` block: aggregate routed
    clicks/sec (the headline), and the proxy-vs-direct warm-click p50 —
    the same session's warm clicks alternately through the front and
    straight at the replica that owns it, so both paths hit the same
    session cache and the difference IS the hop (the <=5% routing-
    overhead acceptance reads off ``proxy_overhead_pct``)."""
    import threading
    from http.server import ThreadingHTTPServer

    from distributedpytorch_tpu.models import build_model
    from distributedpytorch_tpu.parallel import create_train_state
    from distributedpytorch_tpu.predict import Predictor
    from distributedpytorch_tpu.serve import FleetFront, InferenceService
    from distributedpytorch_tpu.serve.__main__ import (
        _HealthCache,
        make_handler,
    )
    from distributedpytorch_tpu.serve.client import ServeClient

    n_replicas = max(1, int(_CLI_ARGS.fleet))
    model = build_model("danet", nclass=1, backbone=BACKBONE,
                        output_stride=8, dtype=DTYPE,
                        guidance_inject="head")
    state = create_train_state(jax.random.PRNGKey(0), model,
                               optax.sgd(1e-3), (1, SIZE, SIZE, 4))
    predictor = Predictor(model, state.params, state.batch_stats,
                          resolution=(SIZE, SIZE), relax=50)
    predictor, qpolicy = _serve_env_extras(predictor)
    r = np.random.RandomState(0)
    image = r.randint(0, 256, (SIZE, SIZE, 3)).astype(np.uint8)
    quarter, mid = SIZE // 4, SIZE // 2
    base_pts = np.array([[quarter, mid], [SIZE - quarter, mid],
                         [mid, quarter], [mid, SIZE - quarter]],
                        np.float64)

    services = [InferenceService(predictor, max_batch=SERVE_MAX_BATCH,
                                 queue_depth=4 * SESSIONS_N,
                                 max_wait_s=0.002,
                                 aot_cache=BENCH_AOT_CACHE)
                for _ in range(n_replicas)]
    acct = get_accountant()
    acct.reset()
    with acct.account("compile"):
        # one compile, N registrations: replica 0's warmup compiles the
        # ladder, the rest hit the in-process jit cache
        for svc in services:
            svc.warmup()
    httpds, urls = [], []
    lock = threading.Lock()
    errors: list[Exception] = []
    served = [0]
    latencies_ms: list[float] = []

    def session_loop(client: ServeClient, k: int) -> None:
        sid = f"bench-fleet-{k}"
        try:
            for c in range(1 + SESSION_WARM_CLICKS):
                t0 = time.perf_counter()
                client.predict(image, base_pts + (k % 8) + (c % 3),
                               session_id=sid)
                ms = (time.perf_counter() - t0) * 1e3
                with lock:
                    served[0] += 1
                    latencies_ms.append(ms)
        except Exception as e:  # noqa: BLE001 — recorded, reported
            with lock:
                errors.append(e)

    front = None
    try:
        for svc in services:
            svc.start()
            httpd = ThreadingHTTPServer(
                ("127.0.0.1", 0), make_handler(svc, _HealthCache()))
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
            httpds.append(httpd)
            urls.append(f"http://127.0.0.1:{httpd.server_address[1]}")
        front = FleetFront(attach=urls, poll_interval_s=0.2)
        front.start()
        fleet_url = front.serve_http("127.0.0.1", 0)
        assert front.wait_live(n_replicas, timeout_s=60.0), \
            "fleet never saw its attached replicas healthy"
        # routed burst — the headline number
        clients = [ServeClient(fleet_url, timeout_s=600.0)
                   for _ in range(SESSIONS_N)]
        threads = [threading.Thread(target=session_loop,
                                    args=(clients[k], k))
                   for k in range(SESSIONS_N)]
        t0 = time.perf_counter()
        with acct.account("step"):
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        dt = time.perf_counter() - t0
        # overhead probe: one session's warm clicks, alternating routed
        # vs direct-at-its-owner — same replica, same session cache,
        # the p50 difference is the hop
        probe = ServeClient(fleet_url, timeout_s=600.0)
        probe.predict(image, base_pts, session_id="fleet-probe")
        owner_rid = probe.last_fleet["replica"]
        owner_url = front.registry.url(owner_rid)
        direct = ServeClient(owner_url, timeout_s=600.0)
        routed_ms, direct_ms = [], []
        for i in range(40):
            # paired design: same session, same replica, same points
            # within a pair, order alternating — the per-click model
            # variance (~±1ms) cancels in the pairwise delta, which a
            # difference of independent p50s would inherit whole
            pair = ((probe, routed_ms), (direct, direct_ms))
            for client, sink in (pair if i % 2 == 0 else pair[::-1]):
                t0 = time.perf_counter()
                client.predict(image, base_pts + (i % 3),
                               session_id="fleet-probe")
                sink.append((time.perf_counter() - t0) * 1e3)
        loads = front.registry.live_loads()
        p99s = [s["p99_ms"] for s in loads.values()
                if s.get("p99_ms") is not None]
        front_health = front.health()
    finally:
        if front is not None:
            front.stop()
        for httpd in httpds:
            httpd.shutdown()
            httpd.server_close()
        for svc in services:
            svc.stop()
    goodput_rep = acct.report()

    def p50(xs):
        return float(np.percentile(xs, 50)) if xs else None

    proxy_p50 = p50(routed_ms)
    direct_p50 = p50(direct_ms)
    # hop cost = median of PAIRED deltas (matched clicks), not the
    # difference of two independent p50s: the per-click model variance
    # is several times the hop itself and cancels only pairwise
    hop_ms = p50([r - d for r, d in zip(routed_ms, direct_ms)])
    clicks = served[0]
    record = {
        "metric": (f"danet_{BACKBONE}_{SIZE}px_fleet{n_replicas}"
                   f"_s{SESSIONS_N}x{SESSION_WARM_CLICKS}_click_loop"),
        "value": round(clicks / dt, 3),
        "unit": "clicks/sec",
        "vs_baseline": 1.0,     # no published fleet baseline
        "platform": jax.devices()[0].platform,
        "sessions_n": SESSIONS_N,
        "warm_clicks_per_session": SESSION_WARM_CLICKS,
        "errors": len(errors),
        "p50_ms": p50(latencies_ms),
        "p99_ms": (float(np.percentile(latencies_ms, 99))
                   if latencies_ms else None),
        # the fleet block — keys ALWAYS present on fleet records, the
        # whole block null on every off-fleet record.
        # --check-regression keys its same-config filter on
        # fleet.replicas only (the sizes are separate families; the
        # measured values are the number, not the config).
        "fleet": {
            "replicas": n_replicas,
            "mode": front_health["mode"],
            "live": front_health["live"],
            "aggregate_rps": round(clicks / dt, 3),
            "proxy_p50_ms": (None if proxy_p50 is None
                             else round(proxy_p50, 3)),
            "direct_p50_ms": (None if direct_p50 is None
                              else round(direct_p50, 3)),
            "proxy_overhead_pct": (
                None if hop_ms is None or not direct_p50 else
                round(hop_ms / direct_p50 * 100.0, 2)),
            "p99_spread_ms": (round(max(p99s) - min(p99s), 3)
                              if len(p99s) >= 2 else None),
        },
    }
    record["goodput"] = round(goodput_rep["goodput"], 4)
    record["goodput_breakdown"] = {
        k: round(v, 3) for k, v in goodput_rep["buckets"].items() if v}
    record["mfu"] = None
    record["feed"] = None  # train-side concept, null on serve records
    record["chaos"] = chaos_sites.active_scenario()
    record["recovery"] = recovery_block()  # null block; key stability
    record["flywheel"] = flywheel_block()  # key always present
    record["elastic"] = elastic_block()  # train-side concept
    record["precision"] = precision_block(precision_policy(DTYPE))
    record["plan"] = None  # train-side concept, null on serve records
    record["events"] = events_block()
    # cold_start + quantization blocks — the serve-record pair (see
    # serve_bench); replica 0's warmup is the boot that compiled
    audit_kw, suffix = _stamp_serve_fast_path(record, services[0],
                                              qpolicy)
    feats = predictor.feature_struct(1)
    record.update(ir_audit_fields(
        predictor.decode_jitted,
        (jax.ShapeDtypeStruct((SERVE_MAX_BATCH, *feats.shape[1:]),
                              feats.dtype),
         jax.ShapeDtypeStruct((SERVE_MAX_BATCH, SIZE, SIZE, 1),
                              np.float32)),
        f"bench_fleet_decode_{BACKBONE}_{SIZE}px_b{SERVE_MAX_BATCH}"
        f"{suffix}", **audit_kw))
    from distributedpytorch_tpu.utils.profiling import device_memory_stats

    record["peak_bytes_in_use"] = \
        device_memory_stats()["peak_bytes_in_use"]
    if not ON_TPU:
        record["note"] = ("JAX_PLATFORMS=cpu smoke (downsized config), "
                          "not a device number")
    print(json.dumps(record))
    return record


def main() -> None:
    # chaos: a DPTPU_CHAOS_PLAN env plan arms for the bench too, so the
    # record's `chaos` field names the scenario that conditioned the
    # number.  Inside main(), not at module scope — importers (tests)
    # must never arm a fault plan as an import side
    # effect (the same rule as the __main__-gated argv read above).
    chaos_sites.maybe_arm_from_env()
    if BENCH_SOURCE not in ("fs", "packed"):
        raise SystemExit(
            f"DPTPU_BENCH_SOURCE must be fs|packed, got {BENCH_SOURCE!r}")
    if BENCH_QUANTIZE not in (None, "int8"):
        raise SystemExit(
            f"DPTPU_BENCH_QUANTIZE must be int8, got {BENCH_QUANTIZE!r}")
    if _CLI_ARGS.serve:
        if _CLI_ARGS.fleet is not None:
            record = serve_fleet_bench()
        elif _CLI_ARGS.sessions:
            record = serve_sessions_bench()
        else:
            record = serve_bench()
        _maybe_check_regression(record)
        return
    if _CLI_ARGS.sessions:
        raise SystemExit("--sessions is a serve mode; pass --serve too")
    if _CLI_ARGS.fleet is not None:
        raise SystemExit("--fleet is a serve mode; pass --serve too")
    from distributedpytorch_tpu.models import build_model
    from distributedpytorch_tpu.parallel import (
        create_train_state,
        shard_batch,
    )
    from distributedpytorch_tpu.parallel import plan as plan_lib

    # parallel plan: the bench step is built THROUGH the planner, so a
    # DPTPU_BENCH_STRATEGY=dp_tp A/B measures exactly the program the
    # trainer would run under that strategy (composed shardings and all)
    plan = plan_lib.resolve_plan(BENCH_STRATEGY,
                                 n_devices=len(jax.devices()))
    mesh = plan.make_mesh()
    n_chips = mesh.devices.size
    semantic = BENCH_MODEL != "danet"
    size = (SIZE + 1) if semantic and ON_TPU else SIZE  # 513² protocol
    in_ch, nclass = (3, 21) if semantic else (4, 1)
    # train.precision + train.reduce_buckets — the PR-8 fast path: bf16
    # compute under the policy (f32 master params), bucketed overlapped
    # gradient reduce (cross-replica BN rides the shard_map region).
    policy = precision_policy(PRECISION)
    # no policy -> the model dtype IS the resolved PRECISION (i.e. f32):
    # DPTPU_BENCH_PRECISION=float32 must measure a genuinely-f32 model,
    # and the record's null `precision` block must mean what it says —
    # falling back to the platform DTYPE here would silently rebuild the
    # legacy bf16-model-dtype config while labeling the record f32
    common = dict(nclass=nclass, backbone=BACKBONE,
                  dtype=(policy.compute_dtype if policy else PRECISION),
                  bn_fp32_stats=BN_FP32_STATS, remat=REMAT,
                  remat_policy=REMAT_POLICY,
                  bn_cross_replica_axis=("data" if REDUCE_BUCKETS
                                         else None))
    if semantic:
        # aux_head=True: BASELINE config 4 was measured multi-output
        # (primary + 0.4-weighted aux CE) — benching without it would be
        # a different model than the committed 122.6 imgs/s row
        model = build_model(BENCH_MODEL, output_stride=16, aux_head=True,
                            **common)
    else:
        model = build_model("danet", output_stride=8,
                            pam_score_dtype=SCORE_DTYPE, **common)
    tx = optax.sgd(1e-3, momentum=0.9)
    r = np.random.RandomState(0)
    host_batch = {
        "concat": r.uniform(0, 255, (BATCH * n_chips, size, size, in_ch)
                            ).astype(np.float32),
        "crop_gt": (
            r.randint(0, nclass, (BATCH * n_chips, size, size)
                      ).astype(np.float32) if semantic else
            (r.uniform(size=(BATCH * n_chips, size, size)) > 0.7
             ).astype(np.float32)),
    }
    from distributedpytorch_tpu.utils.profiling import throughput

    with mesh:
        state = create_train_state(jax.random.PRNGKey(0), model, tx,
                                   (1, size, size, in_ch), mesh=mesh,
                                   shard_params=plan.shard_params,
                                   shard_opt_state=plan.shard_opt_state)
        step = plan.make_train_step(
            model, tx, mesh=mesh, state=state,
            loss_type="multi_softmax" if semantic else "multi_sigmoid",
            precision=policy, reduce_buckets=REDUCE_BUCKETS)
        batch = shard_batch(mesh, host_batch)
        cost = step_cost(step, state, batch)
        flops = cost["flops"]

        state_box = [state]

        def one_step():
            state_box[0], loss = step(state_box[0], batch)
            # Return the loss AND a param leaf: throughput() materializes the
            # return value, so timing provably covers the optimizer update
            # (loss alone completes before the update does).
            return loss, jax.tree.leaves(state_box[0].params)[0]

        # Goodput accounting over the bench itself: the first call pays
        # trace+XLA ('compile'); the steady-state loop is 'step'.  The
        # bench's goodput fraction answers "how much of this record's
        # wall-clock was measurement vs compile".
        acct = get_accountant()
        acct.reset()
        with acct.account("compile"):
            jax.device_get(one_step())
        # throughput() dispatches every step and materializes once at the
        # end: dispatch is asynchronous, so a per-step sync would time the
        # host round trip, not the pipelined device rate (see profiling).
        with acct.account("step"):
            stats = throughput(one_step, steps=STEPS, warmup=WARMUP,
                               items_per_step=BATCH * n_chips)
        goodput_rep = acct.report()
        # after the measurement (never before: the audit's trace must not
        # share the timed window); struct args — the real state was
        # donated to the steps above.  The name carries the bench config
        # so each A/B variant pins its own contract.  Under the policy
        # the JA002 pass uses the declared accumulation points, and the
        # bucketed step's contract (pinned on TPU) requires async
        # -start collectives — the overlap gate of ROADMAP item 4.
        audit_kw = {}
        if policy is not None:
            audit_kw["f32_allow"] = policy.ja002_allow()
        if REDUCE_BUCKETS:
            audit_kw["overlap_expected"] = True
        # sharded plans name their own bench program (the config-naming
        # rule): a dp_tp 512px step must never pin/check the dp config's
        # contract.  mesh_axes rides along so a pinned strategy contract
        # carries the per-axis collective inventory.
        suffix = "" if BENCH_STRATEGY == "dp" else f"_{BENCH_STRATEGY}"
        if plan.sharded:
            audit_kw["mesh_axes"] = plan.axis_sizes(n_chips)
        audit_fields = ir_audit_fields(
            step, (state, batch),
            f"bench_{BENCH_MODEL}_{BACKBONE}_{size}px_b{BATCH}{suffix}",
            **audit_kw)

    per_chip = stats["items_per_sec"] / n_chips
    record = {
        "metric": (f"{BENCH_MODEL}_{BACKBONE}_{size}px_b{BATCH}"
                   "_train_step_throughput"),
        "value": round(per_chip, 3),
        "unit": "imgs/sec/chip",
        # a JAX_PLATFORMS=cpu smoke is not a TPU number
        "platform": jax.devices()[0].platform,
    }
    if SCORE_DTYPE and not semantic:
        # stamped only when it reached the model: the semantic build has
        # no PAM and silently ignores DPTPU_BENCH_SCORE_DTYPE
        record["pam_score_dtype"] = SCORE_DTYPE
    if not BN_FP32_STATS:
        record["bn_fp32_stats"] = False
    if REMAT:
        record["remat"] = True
        record["remat_policy"] = REMAT_POLICY
    peak = peak_flops_per_chip() if ON_TPU else None
    if flops is not None:
        # cost_analysis of a partitioned program counts ONE device's
        # share (the b8 x 1-chip and b32 x 4-chip steps both report
        # 1.314e13 — chip runs, PR 21): flops and bytes are per chip
        # already, so nothing below divides by the chip count
        record["flops_per_step"] = flops
        achieved = flops / stats["mean_s"]  # FLOP/s per chip
        record["tflops_per_sec_per_chip"] = round(achieved / 1e12, 2)
        if cost["bytes"]:
            record["bytes_accessed_per_step"] = cost["bytes"]
        if peak:
            record["mfu_vs_peak"] = round(achieved / peak, 4)
            record["vs_baseline"] = record["mfu_vs_peak"]
            # Roofline floor for one step: max(compute at peak MXU, HBM
            # traffic at peak bandwidth) — what a perfectly-overlapped
            # execution could not beat.  Both axes come from the same
            # device-kind tables, so the diagnosis matches the chip.
            bw = peak_hbm_bw_per_chip()
            if cost["bytes"] and bw:
                t_flops = flops / peak
                t_bytes = cost["bytes"] / bw
                record["roofline_ms_per_step"] = round(
                    max(t_flops, t_bytes) * 1e3, 2)
                record["roofline_bound"] = (
                    "compute" if t_flops >= t_bytes else "memory")
    if "vs_baseline" not in record:
        # no XLA cost model / unknown chip: report a neutral ratio rather
        # than an invented one
        record["vs_baseline"] = 1.0
    # Standard telemetry fields (always present, None when unknowable):
    # goodput = productive fraction of this record's wall-clock; mfu =
    # model-FLOPs utilization (a device metric: null off-TPU);
    # peak_bytes_in_use = HBM high-water mark.
    record["goodput"] = round(goodput_rep["goodput"], 4)
    record["goodput_breakdown"] = {
        k: round(v, 3) for k, v in goodput_rep["buckets"].items() if v}
    # feed block (data/governor.py): the measured input-stall fraction
    # of the record's own goodput books (the timed loop steps pre-placed
    # batches, so ≈ 0 by construction — and the gate catches it if a
    # future bench change makes the loop feed-bound), the governing mode
    # (null = ungoverned), the echo factor (null: the bench loop never
    # echoes).  Keys always present; --check-regression gates the
    # fraction against the governor target when governed.
    record["feed"] = feed_block(goodput_rep, governor=BENCH_GOVERNOR,
                                source=BENCH_SOURCE)
    # chaos field: armed fault-plan name or null; key always present
    # (the PR 4 schema-stability convention)
    record["chaos"] = chaos_sites.active_scenario()
    # sessions block: a serve-mode concept, null on train records — key
    # always present (schema stability)
    record["sessions"] = _sessions_block(None, None)
    # recovery block (train/sentinel.py): rollbacks / quarantined_steps /
    # supervisor_restarts / recovery_p50_s — keys always present, null
    # when the sentinel is off (this synthetic step loop never arms it)
    record["recovery"] = recovery_block()
    # flywheel block (train/continuous.py): examples_logged / fits_run /
    # swap tallies when continuous mode drove this process, all-null
    # otherwise (this synthetic loop never does) — key ALWAYS present
    # (the recovery-block convention); --check-regression's same-config
    # filter keys on it
    record["flywheel"] = flywheel_block()
    # elastic block (train/elastic.py): {topology_changes, replans,
    # recovery_p50_s} when an elastic supervisor re-planned the run
    # this record measures, null otherwise — key ALWAYS present (the
    # recovery-block convention).  The bench's synthetic loop is never
    # supervised, so this is null here; --check-regression's
    # same-config filter keys on it, so an elastic-exercised record
    # (its wall-clock carries re-plan recoveries) can never baseline
    # the static trajectory.
    record["elastic"] = elastic_block()
    # precision block (train/precision.py): the mixed-precision regime
    # the measured step ran under; null when f32 — key always present
    record["precision"] = precision_block(policy)
    # plan block (parallel/plan.py): the sharding strategy the measured
    # step was built under — null for the trivial pure-dp default (the
    # precision-block convention: committed pre-planner history stays
    # comparable), the full resolved block for any sharded plan.  Key
    # always present; --check-regression keys its same-config filter on
    # it so a dp_tp record can never baseline the dp trajectory.
    record["plan"] = plan_lib.plan_record_block(plan)
    # cold_start + quantization: serve-side concepts (the train loop
    # has no bucket ladder to warm and trains full-precision), null on
    # train records — keys always present (schema stability)
    record["cold_start"] = None
    record["quantization"] = None
    # fleet block: a serve-side concept (the router hop); null on train
    # records — key always present (schema stability)
    record["fleet"] = None
    # events block (telemetry/events.py): flight-recorder tallies for
    # the measured loop — keys ALWAYS present, all null when the
    # recorder is off (the bench runs un-recorded by default).
    # --check-regression's same-config filter keys on it.
    record["events"] = events_block()
    if REDUCE_BUCKETS:
        record["reduce_buckets"] = REDUCE_BUCKETS
    # IR-audit fields (jaxaudit): collective inventory of the exact
    # compiled step + compile-contract status; keys always present
    record.update(audit_fields)
    # a zero/negative cost-model sentinel, or no TPU: no MFU
    if ON_TPU and flops and flops > 0:
        est = mfu_estimate(flops, stats["mean_s"])
        record["mfu"] = round(est["mfu"], 4)
        record["mfu_peak_source"] = est["peak_source"]
    else:
        record["mfu"] = None
    if not ON_TPU:
        record["note"] = ("JAX_PLATFORMS=cpu smoke (downsized config), "
                          "not a device number")
    from distributedpytorch_tpu.utils.profiling import device_memory_stats

    peak = device_memory_stats()["peak_bytes_in_use"]
    record["peak_bytes_in_use"] = peak  # 0 on backends without stats (CPU)
    if peak:
        record["peak_hbm_gb"] = round(peak / 2**30, 2)
    print(json.dumps(record))
    _maybe_check_regression(record)


if __name__ == "__main__":
    main()
