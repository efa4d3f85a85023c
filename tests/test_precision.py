"""Mixed precision (train.precision) + bucketed overlapped reduce
(train.reduce_buckets) — ROADMAP item 4's step-speed levers, tier-1.

Four layers:

* the POLICY object (train/precision.py): dtype casts, the declared
  JA002 accumulation points, the schema-stable record block;
* the COMPILED STEP: a 3-step bf16 fit whose loss trajectory matches
  f32 within a pinned band (the fast gate for the slow full-Trainer
  fit), and the bucketed reduce's numerics vs the GSPMD-implicit step;
* the AUDIT: the canonical bf16+bucketed program is JA002-clean under
  the policy allowlist and NOT under the strict default (the policy
  declaration is load-bearing), with the async-overlap contract gate
  exercised on synthetic TPU-keyed reports;
* the CONFIG: the new `train` section round-trips and the trainer-side
  validation rejects non-composable layouts.

Step programs reuse the canonical cpu8 audit config (DANet-ResNet18,
64², one lane per device) so the persistent compile cache shares the
executables with tests/test_jaxaudit.py's fixture.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from distributedpytorch_tpu.analysis import contracts, ir  # noqa: E402
from distributedpytorch_tpu.models import build_model  # noqa: E402
from distributedpytorch_tpu.parallel import (  # noqa: E402
    create_train_state,
    make_mesh,
    make_train_step,
    shard_batch,
)
from distributedpytorch_tpu.parallel.step import (  # noqa: E402
    bucket_grad_leaves,
)
from distributedpytorch_tpu.train.precision import (  # noqa: E402
    POLICY_ACCUM_PRIMS,
    Policy,
    precision_block,
    precision_policy,
)

#: pinned parity band for the 3-step bf16(+bucketed) vs f32 loss
#: trajectory: observed per-step relative deltas are ~1.7e-3 (bf16
#: rounding + the bucketed path's DDP loss-normalization semantics);
#: 2e-2 gives a 10x margin while a real precision bug (a silently-f32
#: layer, a dropped psum, underflowed grads) moves losses far past it
LOSS_BAND_REL = 2e-2


def _three_batches(seed=0, n=3, b=8, hw=64):
    r = np.random.RandomState(seed)
    return [{
        "concat": r.uniform(0, 255, (b, hw, hw, 4)).astype(np.float32),
        "crop_gt": (r.uniform(size=(b, hw, hw)) > 0.7).astype(np.float32),
    } for _ in range(n)]


def _fit3(mesh, model, batches, **step_kw):
    tx = optax.sgd(1e-3, momentum=0.9)
    with mesh:
        state = create_train_state(jax.random.PRNGKey(0), model, tx,
                                   (1, 64, 64, 4), mesh=mesh)
        step = make_train_step(model, tx, mesh=mesh, **step_kw)
        losses = []
        for hb in batches:
            state, loss = step(state, shard_batch(mesh, hb))
            losses.append(float(loss))
    return losses, state


# ------------------------------------------------------------------ policy

class TestPolicy:
    def test_knob_mapping(self):
        assert precision_policy(None) is None
        assert precision_policy("") is None
        assert precision_policy("float32") is None
        p = precision_policy("bfloat16")
        assert isinstance(p, Policy)
        assert p.compute_dtype == "bfloat16"
        assert p.param_dtype == "float32"
        with pytest.raises(ValueError, match="float32 | bfloat16"):
            precision_policy("float16")

    def test_casts(self):
        p = Policy()
        x = {"a": jnp.ones((4,), jnp.float32), "b": jnp.ones((2,), jnp.int32)}
        y = p.cast_to_compute(x)
        assert y["a"].dtype == jnp.bfloat16
        assert y["b"].dtype == jnp.int32  # integer leaves never cast
        out = p.cast_to_loss((jnp.ones((3,), jnp.bfloat16),))
        assert out[0].dtype == jnp.float32

    def test_record_block_schema(self):
        assert precision_block(None) is None
        blk = precision_block(Policy())
        assert blk == {"compute_dtype": "bfloat16",
                       "param_dtype": "float32",
                       "loss_dtype": "float32"}

    def test_ja002_allow_extends_strict_default(self):
        p = Policy()
        allow = p.ja002_allow()
        assert ir.DEFAULT_F32_ACCUM_ALLOW < allow
        assert POLICY_ACCUM_PRIMS <= allow
        # the strict default must NOT contain the policy's declared
        # elementwise accumulation ops — that's what makes the policy
        # declaration load-bearing
        assert "mul" not in ir.DEFAULT_F32_ACCUM_ALLOW
        assert "add" not in ir.DEFAULT_F32_ACCUM_ALLOW


class TestConfigSection:
    def test_round_trip_and_overrides(self):
        from distributedpytorch_tpu.train import config as config_lib

        cfg = config_lib.Config()
        assert cfg.train.precision == "float32"
        assert cfg.train.reduce_buckets == 0
        cfg = config_lib.apply_overrides(
            cfg, ["train.precision=bfloat16", "train.reduce_buckets=8"])
        assert cfg.train.precision == "bfloat16"
        assert cfg.train.reduce_buckets == 8
        back = config_lib.from_json(config_lib.to_json(cfg))
        assert back.train.precision == "bfloat16"
        assert back.train.reduce_buckets == 8

    def test_old_config_json_defaults_train_section(self):
        # configs saved before the `train` section existed must load
        from distributedpytorch_tpu.train import config as config_lib

        cfg = config_lib.from_json('{"task": "instance"}')
        assert cfg.train.precision == "float32"
        assert cfg.train.reduce_buckets == 0


# ----------------------------------------------------------------- buckets

class TestBucketing:
    def _leaves(self, shapes):
        return [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]

    def test_reverse_topological_order(self):
        leaves = self._leaves([(4,), (8,), (16,)])
        buckets = bucket_grad_leaves(leaves, 3)
        # reversed flat order: last leaf (head-side) first
        assert buckets[0][0] == 2
        assert [i for b in buckets for i in b] == [2, 1, 0]

    def test_byte_balanced_cuts(self):
        leaves = self._leaves([(100,)] * 8)
        buckets = bucket_grad_leaves(leaves, 4)
        assert len(buckets) == 4
        assert sorted(len(b) for b in buckets) == [2, 2, 2, 2]

    def test_more_buckets_than_leaves_caps(self):
        leaves = self._leaves([(4,), (4,)])
        buckets = bucket_grad_leaves(leaves, 16)
        assert len(buckets) == 2

    def test_every_leaf_exactly_once(self):
        r = np.random.RandomState(0)
        leaves = self._leaves([tuple(r.randint(1, 64, size=2))
                               for _ in range(23)])
        buckets = bucket_grad_leaves(leaves, 5)
        flat = sorted(i for b in buckets for i in b)
        assert flat == list(range(23))

    def test_invalid_bucket_count_raises(self):
        with pytest.raises(ValueError, match="reduce_buckets"):
            bucket_grad_leaves(self._leaves([(4,)]), 0)


class TestStepValidation:
    """make_train_step's reduce_buckets guards — cheap, no compiles."""

    def test_requires_mesh(self):
        m = build_model("danet", nclass=1, backbone="resnet18",
                        output_stride=8)
        with pytest.raises(ValueError, match="mesh"):
            make_train_step(m, optax.sgd(1e-3), reduce_buckets=4)

    def test_rejects_model_axis_state_shardings(self):
        # Since the planner (parallel/plan.py) buckets compose with
        # data-axis layouts (ZeRO-1, pinned in test_plan), so the guard
        # rejects only MODEL-axis-sharded trees — through the planner,
        # naming the nearest bucket-keeping strategy.
        from jax.sharding import NamedSharding, PartitionSpec as P

        m = build_model("danet", nclass=1, backbone="resnet18",
                        output_stride=8, bn_cross_replica_axis="data")
        msh = make_mesh()
        tp_sh = {"kernel": NamedSharding(msh, P(None, "model"))}
        with pytest.raises(ValueError, match="strategy"):
            make_train_step(m, optax.sgd(1e-3), mesh=msh,
                            reduce_buckets=4, state_shardings=tp_sh)

    def test_requires_cross_replica_bn(self):
        m = build_model("danet", nclass=1, backbone="resnet18",
                        output_stride=8)  # per-replica BN
        with pytest.raises(ValueError, match="bn_cross_replica_axis"):
            make_train_step(m, optax.sgd(1e-3), mesh=make_mesh(),
                            reduce_buckets=4)


# ------------------------------------------------------- 3-step parity gate

@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


@pytest.fixture(scope="module")
def f32_trajectory(mesh):
    model = build_model("danet", nclass=1, backbone="resnet18",
                        output_stride=8)
    return _fit3(mesh, model, _three_batches())


class TestBf16FitParity:
    """The fast gate for the slow full-Trainer bf16 fit: 3 optimizer
    steps of the SHIPPED fast path (bf16 policy + bucketed reduce, the
    train_step_bf16 canonical config) against the f32 reference — same
    batches, same init seed, loss trajectory inside the pinned band."""

    def test_bf16_bucketed_matches_f32_within_band(self, mesh,
                                                   f32_trajectory):
        l_f32, _ = f32_trajectory
        policy = precision_policy("bfloat16")
        model = build_model("danet", nclass=1, backbone="resnet18",
                            output_stride=8, dtype=policy.compute_dtype,
                            bn_cross_replica_axis="data")
        l_bf16, state = _fit3(mesh, model, _three_batches(),
                              precision=policy, reduce_buckets=4)
        for i, (a, b) in enumerate(zip(l_f32, l_bf16)):
            assert np.isfinite(b)
            assert abs(a - b) / abs(a) <= LOSS_BAND_REL, \
                f"step {i}: bf16 loss {b} vs f32 {a} outside the band"
        # master params stay f32 and finite
        for leaf in jax.tree.leaves(state.params):
            assert leaf.dtype == jnp.float32
            assert bool(jnp.isfinite(leaf).all())

    def test_bucketed_f32_matches_gspmd_step(self, mesh, f32_trajectory):
        """reduce_buckets alone (no precision change) against the
        GSPMD-implicit step: identical math up to DDP loss-averaging
        semantics and reassociation — losses in the band, params close."""
        l_ref, s_ref = f32_trajectory
        model = build_model("danet", nclass=1, backbone="resnet18",
                            output_stride=8, bn_cross_replica_axis="data")
        l_bkt, s_bkt = _fit3(mesh, model, _three_batches(),
                             reduce_buckets=2)
        for a, b in zip(l_ref, l_bkt):
            assert abs(a - b) / abs(a) <= LOSS_BAND_REL
        worst = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()),
            s_ref.params, s_bkt.params)))
        assert worst <= 1e-3, f"param divergence {worst}"


# ----------------------------------------------------------- audit / gates

class TestJa002PolicyAudit:
    def test_bf16_step_clean_under_policy_not_under_strict(self):
        # trace-only (compile=False): the satellite acceptance — zero
        # unexpected upcasts under the policy's declared accumulation
        # points, and a strictly-audited bf16 step DOES have findings
        # (the declaration is doing real work, not gutting JA002)
        fn, args, kw = contracts.build_default_programs(
            ("train_step_bf16",))["train_step_bf16"]
        rep = ir.audit(fn, args, name="bf16", compile=False,
                       f32_allow=kw["f32_allow"])
        assert rep["finding_counts"]["dtype_upcast"] == 0
        strict = ir.audit(fn, args, name="bf16_strict", compile=False)
        assert strict["finding_counts"]["dtype_upcast"] > 0

    def test_policy_allow_does_not_mask_alien_f32_math(self):
        # a transcendental on upcast bf16 data is NOT a declared
        # accumulation point — the policy allowlist still flags it
        @jax.jit
        def bad(x):
            return jnp.sin(x.astype(jnp.float32)).sum()

        rep = ir.audit(bad, (jax.ShapeDtypeStruct((32,), jnp.bfloat16),),
                       name="bad", compile=False,
                       f32_allow=Policy().ja002_allow())
        assert rep["finding_counts"]["dtype_upcast"] == 1


class TestAsyncOverlapGate:
    """The contract machinery for async -start collectives — the TPU
    overlap gate, exercised on synthetic reports (no TPU needed)."""

    def _report(self, platform="tpu", hlo=None, overlap=True,
                n_devices=8):
        return {
            "program": "p", "platform": platform, "n_devices": n_devices,
            "overlap_expected": overlap,
            "collectives": {"jaxpr": {"psum": {"data": 4}}, "hlo": hlo},
            "outputs": ["float32[4]"],
            "donation": {"declared_args": 0, "declared_bytes": 0,
                         "aliased_outputs": 0, "alias_bytes": None,
                         "effective": None},
            "constants": {"count": 0, "total_bytes": 0,
                          "largest_bytes": 0, "largest": None},
            "flops": 100.0, "bytes_accessed": None, "findings": [],
            "finding_counts": {c: 0 for c in ir.FINDING_CLASSES},
        }

    def test_async_start_count(self):
        assert ir.async_start_count(None) == 0
        assert ir.async_start_count({"all-reduce": 3}) == 0
        assert ir.async_start_count(
            {"all-reduce": 3, "all-reduce-start": 2,
             "all-gather-start": 1}) == 3

    def test_tpu_contract_pins_async_and_gates_regression(self):
        good = self._report(hlo={"all-reduce": 4, "all-reduce-start": 4})
        contract = contracts.contract_from_report(good)
        assert contract["require_async_starts"] is True
        assert contracts.diff_contract(contract, good) == []
        # the regression: same counts pinned, but every -start gone
        bad = self._report(hlo={"all-reduce": 4})
        drift = contracts.diff_contract(contract, bad)
        assert any("async overlap" in line for line in drift)

    def test_cpu_contract_never_pins_async(self):
        rep = self._report(platform="cpu", hlo={"all-reduce": 4})
        contract = contracts.contract_from_report(rep)
        assert "require_async_starts" not in contract
        assert contracts.diff_contract(contract, rep) == []

    def test_single_chip_tpu_never_pins_async(self):
        # one chip has nothing to overlap: XLA deletes singleton-group
        # all-reduces, so a tpu1 contract pinning -start forms would
        # self-drift forever (the bench's documented 1-chip environment)
        rep = self._report(hlo={}, n_devices=1)
        contract = contracts.contract_from_report(rep)
        assert contract["platform_key"] == "tpu1"
        assert "require_async_starts" not in contract
        assert contracts.diff_contract(contract, rep) == []

    def test_hlo_start_forms_counted_separately(self):
        # a real cpu8 shard_map psum program: sync all-reduce only, no
        # -start keys (the split must not disturb cpu8 contracts)
        from jax.sharding import PartitionSpec as P

        from distributedpytorch_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh()

        def f(x):
            return jax.shard_map(
                lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                in_specs=P("data"), out_specs=P())(x)

        rep = ir.audit(jax.jit(f),
                       (jax.ShapeDtypeStruct((8, 4), jnp.float32),),
                       name="psum8")
        hlo = rep["collectives"]["hlo"]
        assert hlo.get("all-reduce", 0) >= 1
        assert not any(k.endswith("-start") for k in hlo)


# ----------------------------------------------------- slow full-Trainer fit

@pytest.mark.slow
class TestTrainerBf16FitSlow:
    """The full Trainer.fit e2e on the fast path (named fast gate:
    TestBf16FitParity above, per the PR 7 convention)."""

    def test_fit_bf16_bucketed_end_to_end(self, tmp_path):
        from distributedpytorch_tpu.train import config as config_lib
        from distributedpytorch_tpu.train.trainer import Trainer

        cfg = config_lib.Config()
        cfg = dataclasses.replace(
            cfg,
            data=dataclasses.replace(
                cfg.data, fake=True, train_batch=8, val_batch=2,
                num_workers=2, crop_size=(64, 64), relax=10, area_thres=0),
            model=dataclasses.replace(cfg.model, backbone="resnet18",
                                      output_stride=8),
            train=dataclasses.replace(cfg.train, precision="bfloat16",
                                      reduce_buckets=4),
            optim=dataclasses.replace(cfg.optim, lr=1e-4),
            checkpoint=dataclasses.replace(cfg.checkpoint,
                                           async_save=False),
            epochs=1, eval_every=1, seed=0, work_dir=str(tmp_path),
            log_every_steps=1,
        )
        tr = Trainer(cfg)
        assert tr.precision is not None
        history = tr.fit()
        assert all(np.isfinite(l) for l in history["train_loss"])
        # the trainer's own audit hook: JA002-clean under the policy
        reports = tr.audit()
        assert reports["train_step"]["finding_counts"]["dtype_upcast"] \
            == 0
        assert reports["train_step"]["overlap_expected"] is True
        assert reports["train_step"]["collectives"]["jaxpr"].get(
            "psum", {}).get("data", 0) > 0
        tr.close()

    def test_trainer_rejects_buckets_with_tp(self, tmp_path):
        from distributedpytorch_tpu.train import config as config_lib
        from distributedpytorch_tpu.train.trainer import Trainer

        cfg = config_lib.Config()
        cfg = dataclasses.replace(
            cfg,
            data=dataclasses.replace(cfg.data, fake=True, train_batch=8,
                                     val_batch=2, crop_size=(64, 64),
                                     relax=10, area_thres=0),
            model=dataclasses.replace(cfg.model, backbone="resnet18"),
            train=dataclasses.replace(cfg.train, reduce_buckets=4),
            mesh=dataclasses.replace(cfg.mesh, shard_params=True),
            work_dir=str(tmp_path),
        )
        with pytest.raises(ValueError, match="reduce_buckets"):
            Trainer(cfg)
