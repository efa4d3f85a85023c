"""jaxaudit: IR auditing + compile contracts, tier-1.

Three layers, mirroring how the gate is used:

* the CHECKED-IN contracts: the canonical CPU-mesh train/eval/serve
  programs (contracts.build_default_programs — the exact jitted
  callables the trainer and serve front dispatch) re-trace clean against
  ``tests/contracts/*.cpu8.json``;
* INJECTED drift: perturb throwaway jits on purpose (drop
  ``donate_argnums``, add a stray psum, upcast bf16 into non-accum f32,
  return a dead/duplicate output, bake a fat constant) and assert
  jaxaudit reports exactly the injected finding and ``check`` exits
  non-zero;
* the HOOKS: ``Trainer.audit_programs`` / ``InferenceService
  .audit_programs`` expose the live jitted callables, and bench.py's
  record fields degrade to schema-stable placeholders when the audit is
  skipped or broken.

Programs are audited once per module (the compiles are shared with the
persistent compile cache the whole suite uses — no extra fits, no
re-lowering: telemetry.lowering memoizes per process).
"""

import functools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distributedpytorch_tpu.analysis import contracts, ir  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTRACTS_DIR = os.path.join(REPO, "tests", "contracts")

SDS = jax.ShapeDtypeStruct


@pytest.fixture(scope="module")
def canonical_reports():
    """Audit the real canonical programs ONCE for every test below."""
    return ir.audit_many(contracts.build_default_programs())


# ------------------------------------------------------ checked-in contracts

class TestCheckedInContracts:
    def test_contract_files_checked_in(self):
        key = contracts.platform_key()
        for name in contracts.PROGRAM_NAMES:
            path = contracts.contract_path(CONTRACTS_DIR, name, key)
            assert os.path.exists(path), \
                f"missing compile contract {path} — run " \
                "`python -m distributedpytorch_tpu.analysis --ir update`"

    def test_canonical_programs_match_contracts(self, canonical_reports):
        # the acceptance gate: train step, eval step and two serve
        # buckets check clean on the CPU backend
        assert set(canonical_reports) == set(contracts.PROGRAM_NAMES)
        drift = {name: contracts.check_report(rep, CONTRACTS_DIR)
                 for name, rep in canonical_reports.items()}
        assert all(not d for d in drift.values()), \
            "contract drift:\n" + "\n".join(
                f"{n}: {line}" for n, d in drift.items() for line in d)

    def test_train_step_audit_shape(self, canonical_reports):
        rep = canonical_reports["train_step"]
        # donation declared AND committed (the HLO header aliases it)
        assert rep["donation"]["declared_args"] > 0
        assert rep["donation"]["effective"] is True
        assert rep["finding_counts"]["donation"] == 0
        # GSPMD inserted the gradient/BN-stat all-reduces
        assert rep["collectives"]["hlo"].get("all-reduce", 0) > 0
        # XLA's cost model priced the step
        assert rep["flops"] and rep["flops"] > 0
        # no constants baked into the trainer's step
        assert rep["constants"]["count"] == 0

    def test_serve_forward_pins_closure_params(self, canonical_reports):
        # the serve forward closes over the weights BY DESIGN: the
        # constants check sees them, and the contract pins that as the
        # steady state (growth past the band is real drift)
        for name in ("serve_forward_b1", "serve_forward_b8"):
            rep = canonical_reports[name]
            assert rep["constants"]["total_bytes"] > 2**20
            assert rep["finding_counts"]["large_const"] == 1
            assert rep["outputs"] and len(rep["outputs"]) == 1

    def test_eval_step_no_donation_no_findings(self, canonical_reports):
        rep = canonical_reports["eval_step"]
        assert rep["donation"]["declared_args"] == 0
        assert sum(rep["finding_counts"].values()) == 0

    def test_lowering_cache_shared_with_mfu_estimator(
            self, canonical_reports):
        # the satellite contract: auditing and costing the same program
        # must not lower twice — xla_step_cost hits the same cache entry
        from distributedpytorch_tpu.telemetry.goodput import xla_step_cost
        from distributedpytorch_tpu.telemetry.lowering import cache_info

        fn, args = contracts.build_default_programs(("eval_step",)
                                                    )["eval_step"]
        before = cache_info()["entries"]
        cost = xla_step_cost(fn, *args)
        after = cache_info()["entries"]
        assert cost["flops"] and cost["flops"] > 0
        # same fn object + same avals as the module fixture's audit
        # would dedup; a fresh build_default_programs returns NEW jit
        # objects, so at most one new entry — and costing it again adds
        # none
        xla_step_cost(fn, *args)
        assert cache_info()["entries"] == after
        assert after <= before + 1


# --------------------------------------------------------- injected drift

def _toy_programs(donate: bool):
    """A minimal state-updating step, donated or not."""
    def step(state, batch):
        return state + batch.sum(), (state * 2).sum()

    fn = jax.jit(step, donate_argnums=(0,) if donate else ())
    args = (SDS((128,), jnp.float32), SDS((128,), jnp.float32))
    return {"toy_step": (fn, args)}


class TestInjectedDrift:
    def test_dropping_donation_is_exactly_the_reported_drift(
            self, tmp_path):
        good = ir.audit_many(_toy_programs(donate=True))["toy_step"]
        assert good["donation"]["effective"] is True
        contracts.save_contract(contracts.contract_from_report(good),
                                str(tmp_path))
        bad = ir.audit_many(_toy_programs(donate=False))["toy_step"]
        drift = contracts.check_report(bad, str(tmp_path))
        assert drift and all("donation" in line for line in drift), drift

    def test_declared_but_unaliasable_donation_is_a_finding(self):
        # donate a bf16 input into an all-f32-output program: jax warns,
        # XLA aliases nothing, JA006 must say so
        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(x):
            return x.astype(jnp.float32).sum()

        with pytest.warns(UserWarning, match="donated"):
            rep = ir.audit(step, (SDS((64,), jnp.bfloat16),),
                           name="undonatable")
        assert rep["donation"]["declared_args"] == 1
        assert rep["donation"]["effective"] is False
        assert rep["finding_counts"]["donation"] == 1

    def test_stray_psum_is_exactly_the_reported_drift(self, tmp_path):
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("data",))

        def make(extra_psum: bool):
            def body(x):
                y = jax.lax.psum(x, "data")
                if extra_psum:
                    y = y + jax.lax.psum(x * 2, "data")
                return y

            fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                                   out_specs=P()))
            return {"toy_collective": (fn, (SDS((8,), jnp.float32),))}

        base = ir.audit_many(make(False))["toy_collective"]
        assert base["collectives"]["jaxpr"] == {"psum": {"data": 1}}
        contracts.save_contract(contracts.contract_from_report(base),
                                str(tmp_path))
        drifted = ir.audit_many(make(True))["toy_collective"]
        assert drifted["collectives"]["jaxpr"]["psum"]["data"] == 2
        drift = contracts.check_report(drifted, str(tmp_path))
        assert drift and any("psum" in line for line in drift), drift

    def test_bf16_upcast_into_non_accum_f32_is_found(self):
        @jax.jit
        def bad(x):
            return jnp.sin(x.astype(jnp.float32))

        rep = ir.audit(bad, (SDS((32,), jnp.bfloat16),), name="upcast",
                       compile=False)
        assert rep["finding_counts"]["dtype_upcast"] == 1
        assert "sin" in rep["findings"][0]["message"]

    def test_bf16_upcast_into_accumulation_is_allowed(self):
        @jax.jit
        def fine(x):
            return x.astype(jnp.float32).sum()

        rep = ir.audit(fine, (SDS((32,), jnp.bfloat16),), name="accum",
                       compile=False)
        assert rep["finding_counts"]["dtype_upcast"] == 0

    def test_upcast_crossing_a_call_boundary_is_not_a_finding(self):
        # call-like consumers (custom_jvp_call, scan, pjit, ...) are
        # transparent: the value merely crosses a boundary there
        @jax.jit
        def crossing(x):
            y = x.astype(jnp.float32)
            z = jax.nn.log_sigmoid(y)          # custom_jvp_call
            c, _ = jax.lax.scan(lambda c, v: (c + v.sum(), c), 0.0,
                                y.reshape(4, 8))
            return z.sum() + c

        rep = ir.audit(crossing, (SDS((32,), jnp.bfloat16),),
                       name="crossing", compile=False)
        assert rep["finding_counts"]["dtype_upcast"] == 0

    def test_dead_and_duplicate_outputs_are_found(self):
        @jax.jit
        def leaky(x):
            dead = jnp.arange(4, dtype=jnp.float32).sum()
            y = x * 2
            return y, dead, y

        rep = ir.audit(leaky, (SDS((8,), jnp.float32),), name="leaky",
                       compile=False)
        assert rep["finding_counts"]["dead_output"] == 1
        assert rep["finding_counts"]["duplicate_output"] == 1

    def test_const_bloat_is_found_and_drifts(self, tmp_path):
        lean = ir.audit(jax.jit(lambda x: x + 1.0),
                        (SDS((8,), jnp.float32),), name="toy_const",
                        compile=False)
        assert lean["finding_counts"]["large_const"] == 0
        contracts.save_contract(contracts.contract_from_report(lean),
                                str(tmp_path))

        table = np.arange(600_000, dtype=np.float32)  # 2.4 MB closure

        fat_fn = jax.jit(lambda x: x + jnp.asarray(table, jnp.float32)[:8])
        fat = ir.audit(fat_fn, (SDS((8,), jnp.float32),),
                       name="toy_const", compile=False)
        assert fat["finding_counts"]["large_const"] == 1
        drift = contracts.check_report(fat, str(tmp_path))
        assert drift and any("constants" in line or "large_const" in line
                             for line in drift), drift

    def test_check_cli_exits_nonzero_on_drift_and_zero_when_clean(
            self, tmp_path, capsys):
        rc = contracts.run_cli(["update", "--contracts-dir",
                                str(tmp_path)],
                               programs=_toy_programs(donate=True))
        assert rc == 0
        rc = contracts.run_cli(["check", "--contracts-dir", str(tmp_path)],
                               programs=_toy_programs(donate=True))
        assert rc == 0
        rc = contracts.run_cli(["check", "--contracts-dir", str(tmp_path)],
                               programs=_toy_programs(donate=False))
        assert rc == 1
        out = capsys.readouterr().out
        assert "donation" in out

    def test_missing_contract_fails_check(self, tmp_path):
        rc = contracts.run_cli(["check", "--contracts-dir", str(tmp_path)],
                               programs=_toy_programs(donate=True))
        assert rc == 1


# ------------------------------------------------------------------- hooks

class TestHooks:
    def test_trainer_audit_programs_exposes_exact_callables(self):
        # the hook reads only attributes — drive it over a namespace so
        # the test never pays a Trainer construction
        from distributedpytorch_tpu.train import config as config_lib
        from distributedpytorch_tpu.train.trainer import Trainer

        cfg = config_lib.Config()
        train_fn = jax.jit(lambda s, b: (s, b["concat"].sum()))
        eval_fn = jax.jit(lambda s, b: (b["concat"], b["concat"].sum()))
        state = {"w": SDS((4,), jnp.float32)}
        ns = types.SimpleNamespace(
            cfg=cfg, state=state, train_step=train_fn, eval_step=eval_fn,
            multi_train_step=None, _val_device_guidance=False,
            _val_packbits=False,
            mesh=types.SimpleNamespace(devices=np.empty((8, 1))))
        programs = Trainer.audit_programs(ns)
        assert set(programs) == {"train_step", "eval_step"}
        fn, args = programs["train_step"]
        assert fn is train_fn
        state_s, batch_s = args
        h, w = cfg.data.crop_size
        assert batch_s["concat"].shape == \
            (cfg.data.train_batch, h, w, cfg.model.in_channels)
        assert all(isinstance(leaf, jax.ShapeDtypeStruct)
                   for leaf in jax.tree.leaves((state_s, batch_s)))
        # eval audits at the VAL dispatch shape (val batch padded to the
        # device multiple, exactly evaluate()'s pad_to_multiple), never
        # the train batch
        _, (_, val_s) = programs["eval_step"]
        vb = -(-max(1, cfg.data.val_batch) // 8) * 8
        assert val_s["concat"].shape == (vb, h, w, cfg.model.in_channels)

    def test_trainer_hook_refuses_unsynthesizable_wire(self):
        from distributedpytorch_tpu.train import config as config_lib
        from distributedpytorch_tpu.train.trainer import Trainer

        cfg = config_lib.Config()
        cfg.data.uint8_transfer = True
        ns = types.SimpleNamespace(cfg=cfg, state={},
                                   train_step=None, eval_step=None,
                                   multi_train_step=None,
                                   _val_device_guidance=False,
                                   _val_packbits=False)
        with pytest.raises(ValueError, match="wire"):
            Trainer.audit_programs(ns)

    def test_serve_audit_programs_cover_the_bucket_ladder(self):
        from distributedpytorch_tpu.serve import InferenceService

        fwd = jax.jit(lambda x: x.sum(axis=(1, 2, 3)))
        pred = types.SimpleNamespace(resolution=(16, 16), in_channels=4,
                                     forward_jitted=fwd, mesh=None)
        svc = InferenceService(pred, max_batch=4)
        programs = svc.audit_programs()
        assert set(programs) == {"serve_forward_b1", "serve_forward_b2",
                                 "serve_forward_b4"}
        fn, (arg,) = programs["serve_forward_b4"]
        assert fn is fwd and arg.shape == (4, 16, 16, 4)

    def test_bench_fields_schema_stable_when_skipped(self, monkeypatch):
        import bench

        monkeypatch.setenv("DPTPU_BENCH_AUDIT", "0")
        fields = bench.ir_audit_fields(None, (), "x")
        assert fields == {"collectives": None, "ir_contract": "skipped",
                          "audit_ms": None}
        monkeypatch.setenv("DPTPU_BENCH_AUDIT", "1")
        # an unauditable program fails the run: no record is printed with
        # its audit quietly marked broken
        with pytest.raises(Exception):
            bench.ir_audit_fields(None, (), "x")

    def test_bench_fields_check_against_contracts(self, canonical_reports):
        import bench

        fn, args = contracts.build_default_programs(
            ("serve_forward_b1",))["serve_forward_b1"]
        fields = bench.ir_audit_fields(fn, args, "serve_forward_b1")
        assert fields["ir_contract"] == "pass"
        assert fields["collectives"]["jaxpr"] == {}
        # the timing attribution rides along (satellite of jaxguard):
        # always the three keys, all non-negative on a compiled audit
        assert set(fields["audit_ms"]) == {"lower", "compile", "walk"}
        assert all(v is not None and v >= 0
                   for v in fields["audit_ms"].values())

    def test_bench_update_knob_pins_then_passes(self, monkeypatch,
                                                tmp_path):
        # a config-named bench program starts 'no_contract';
        # DPTPU_BENCH_AUDIT_UPDATE=1 pins it, after which it checks
        import bench

        monkeypatch.setattr(contracts, "default_contracts_dir",
                            lambda: str(tmp_path))
        monkeypatch.delenv("DPTPU_BENCH_AUDIT_UPDATE", raising=False)
        fn, args = _toy_programs(donate=True)["toy_step"]
        fields = bench.ir_audit_fields(fn, args, "bench_toy")
        assert fields["ir_contract"] == "no_contract"
        monkeypatch.setenv("DPTPU_BENCH_AUDIT_UPDATE", "1")
        assert bench.ir_audit_fields(fn, args,
                                     "bench_toy")["ir_contract"] == "pass"
        monkeypatch.delenv("DPTPU_BENCH_AUDIT_UPDATE")
        assert bench.ir_audit_fields(fn, args,
                                     "bench_toy")["ir_contract"] == "pass"

    def test_trainer_hook_audits_wire_twins_under_coalesce(self):
        # data.coalesce_wire: the loop dispatches the wire-consuming
        # twins; the hook must return THOSE, with the packed batch struct
        from distributedpytorch_tpu.train import config as config_lib
        from distributedpytorch_tpu.train.trainer import Trainer

        cfg = config_lib.Config()
        cfg.data.coalesce_wire = True
        wire_fn = jax.jit(lambda s, b: (s, b["wire"].sum()))
        eval_fn = jax.jit(lambda s, b: (b["concat"], b["concat"].sum()))
        packed = {"wire": np.zeros((4, 100), np.uint8)}
        ns = types.SimpleNamespace(
            cfg=cfg, state={"w": SDS((4,), jnp.float32)},
            train_step=jax.jit(lambda s, b: (s, 0.0)), eval_step=eval_fn,
            multi_train_step=None, _wire_step=wire_fn,
            _wire_multi_step=None,
            _pack_wire_transform=lambda b: packed,
            _val_device_guidance=False, _val_packbits=False,
            mesh=types.SimpleNamespace(devices=np.empty((8, 1))))
        train_batch = {"concat": np.zeros((4, 8, 8, 4), np.uint8),
                       "crop_gt": np.zeros((4, 8, 8), np.uint8)}
        programs = Trainer.audit_programs(ns, train_batch=train_batch)
        fn, (_, batch_s) = programs["train_step"]
        assert fn is wire_fn
        assert set(batch_s) == {"wire"}
        assert batch_s["wire"].shape == (4, 100)


# --------------------------------------------------------------------- CLI

class TestCLI:
    def test_list_is_static_and_fast(self):
        r = subprocess.run(
            [sys.executable, "-m", "distributedpytorch_tpu.analysis",
             "--ir", "list"],
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, PYTHONPATH=REPO), timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        for name in contracts.PROGRAM_NAMES:
            assert name in r.stdout

    def test_unknown_program_exits_2(self):
        rc = contracts.run_cli(["check", "--programs", "nope"],
                               programs=_toy_programs(donate=True))
        assert rc == 2

    def test_contract_json_round_trips(self, tmp_path,
                                       canonical_reports):
        rep = canonical_reports["eval_step"]
        path = contracts.save_contract(
            contracts.contract_from_report(rep), str(tmp_path))
        with open(path) as f:
            loaded = json.load(f)
        assert contracts.diff_contract(loaded, rep) == []


# ------------------------------------------------------------ contract schema

def _contract_files():
    import glob

    return sorted(glob.glob(os.path.join(CONTRACTS_DIR, "*.json")))


class TestContractSchema:
    """Satellite: every checked-in contract validates against the one
    declared schema — a hand-edited contract fails HERE, loudly, not by
    silently never being compared."""

    @pytest.mark.parametrize(
        "path", _contract_files(),
        ids=[os.path.basename(p) for p in _contract_files()])
    def test_checked_in_contract_is_schema_valid(self, path):
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        errs = contracts.validate_contract_file(path, doc)
        assert not errs, "\n".join(errs)

    def test_schema_catches_hand_edit_hazards(self):
        with open(contracts.contract_path(CONTRACTS_DIR, "eval_step",
                                          "cpu8")) as f:
            good = json.load(f)
        path = os.path.join(CONTRACTS_DIR, "eval_step.cpu8.json")

        # typo'd top-level key: pins nothing, must be loud
        doc = dict(good, finding_cnts=good["finding_counts"])
        del doc["finding_counts"]
        errs = contracts.validate_contract_file(path, doc)
        assert any("finding_cnts" in e for e in errs)
        assert any("missing" in e for e in errs)

        # filename / platform-key naming convention
        errs = contracts.validate_contract_file(
            os.path.join(CONTRACTS_DIR, "eval_step.CPU-8.json"),
            dict(good, platform_key="CPU-8"))
        assert any("platform_key" in e for e in errs)
        errs = contracts.validate_contract_file(
            os.path.join(CONTRACTS_DIR, "other_name.cpu8.json"), good)
        assert any("filename" in e for e in errs)

        # band/count types
        doc = json.loads(json.dumps(good))
        doc["constants"]["total_bytes"] = "lots"
        assert contracts.validate_contract_file(path, doc)
        doc = json.loads(json.dumps(good))
        doc["finding_counts"]["donation"] = -1
        assert contracts.validate_contract_file(path, doc)
        doc = json.loads(json.dumps(good))
        doc["collectives"]["hlo_schedule"] = {"data": ["all-reduce*x"]}
        assert any("hlo_schedule" in e
                   for e in contracts.validate_contract_file(path, doc))

        # schedule_set kind: divergent_pairs shape is policed too
        sched_path = os.path.join(CONTRACTS_DIR,
                                  "guard_schedules.cpu8.json")
        with open(sched_path, encoding="utf-8") as f:
            sched = json.load(f)
        assert contracts.validate_contract_file(sched_path, sched) == []
        bad = json.loads(json.dumps(sched))
        bad["divergent_pairs"] = [["a", "a"]]
        assert any("divergent_pairs" in e
                   for e in contracts.validate_contract_file(sched_path,
                                                             bad))


# ---------------------------------------------------- guard schedule pins

class TestGuardSchedulePin:
    """The cross-program half of jaxguard rides the SAME canonical
    compiles as the contract gate (module fixture) — zero extra
    lowering; test_jaxguard.py covers the rule mechanics on toys."""

    def test_plan_reports_carry_ordered_schedules(self, canonical_reports):
        from distributedpytorch_tpu.analysis.spmd import rle_expand

        for name in contracts.PLAN_PROGRAM_NAMES:
            col = canonical_reports[name]["collectives"]
            sched = col["hlo_schedule"]
            assert sched, f"{name}: no hlo_schedule extracted"
            # the ordered schedule and the aggregate counts are views of
            # one walk: totals must agree per axis label
            for ax, seq in sched.items():
                want = sum(per.get(ax, 0)
                           for per in col["hlo_axes"].values())
                assert len(rle_expand(seq)) == want, (name, ax)

    def test_checked_in_pin_matches_live_schedules(self,
                                                   canonical_reports):
        from distributedpytorch_tpu.analysis import guard

        schedules = {
            name: canonical_reports[name]["collectives"]["hlo_schedule"]
            for name in contracts.PLAN_PROGRAM_NAMES}
        failures = guard.check_schedules(schedules, CONTRACTS_DIR,
                                         contracts.platform_key())
        assert not failures, "\n".join(failures)

    def test_timing_attribution_always_present(self, canonical_reports):
        for name, rep in canonical_reports.items():
            tm = rep["timing_ms"]
            assert set(tm) == {"lower", "compile", "walk"}, name
            assert all(v is None or v >= 0 for v in tm.values()), name
