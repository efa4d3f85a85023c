"""bench.py's record helpers: the --check-regression gate and the record
blocks whose schema its same-config filter keys on."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


class TestCheckRegression:
    """bench.py --check-regression: the committed BENCH_*.json records as
    a throughput regression gate (exit non-zero past the 10% band)."""

    METRIC = "danet_resnet101_512px_b8_train_step_throughput"

    def _history_dir(self, tmp_path, values, platform="tpu",
                     metric=None, wrap=True):
        for i, v in enumerate(values, start=1):
            rec = {"metric": metric or self.METRIC, "value": v,
                   "unit": "imgs/sec/chip", "platform": platform}
            data = {"n": i, "cmd": "python bench.py", "rc": 0,
                    "parsed": rec} if wrap else rec
            with open(tmp_path / f"BENCH_r{i:02d}.json", "w") as f:
                json.dump(data, f)
        return str(tmp_path)

    def _rec(self, value, platform="tpu", metric=None):
        return {"metric": metric or self.METRIC, "value": value,
                "unit": "imgs/sec/chip", "platform": platform}

    def test_history_parses_driver_wrapper_and_bare_records(self,
                                                           tmp_path):
        d = self._history_dir(tmp_path, [60.0])
        with open(tmp_path / "BENCH_r02.json", "w") as f:
            json.dump(self._rec(65.0), f)  # bare record form
        (tmp_path / "BENCH_r03.json").write_text("not json")  # skipped
        hist = bench.load_bench_history(d)
        assert [r["value"] for _, r in hist] == [60.0, 65.0]

    def test_newest_same_config_record_is_the_baseline(self, tmp_path):
        d = self._history_dir(tmp_path, [60.0, 70.0])
        hist = bench.load_bench_history(d)
        # the baseline is 70 (the NEWEST record), not 60: a value equal
        # to the OLD record still fails the 10% band against the new one
        ok, msg = bench.check_regression(self._rec(60.0), hist)
        assert not ok and "BENCH_r02" in msg
        ok, _ = bench.check_regression(self._rec(63.1), hist)
        assert ok  # within 10% of 70

    def test_regression_past_threshold_fails(self, tmp_path):
        hist = bench.load_bench_history(self._history_dir(tmp_path,
                                                          [67.5]))
        ok, msg = bench.check_regression(self._rec(55.0), hist)
        assert not ok and "regression" in msg
        ok, msg = bench.check_regression(self._rec(75.0), hist)
        assert ok  # improvements always pass

    def test_platform_and_metric_never_cross_compare(self, tmp_path):
        hist = bench.load_bench_history(self._history_dir(tmp_path,
                                                          [67.5]))
        # a CPU smoke number must not gate against the TPU record
        ok, msg = bench.check_regression(self._rec(1.2, platform="cpu"),
                                         hist)
        assert ok and "nothing to compare" in msg
        # a different bench config (metric carries model/size/batch)
        ok, msg = bench.check_regression(
            self._rec(1.0, metric="danet_resnet18_64px_b2_x"), hist)
        assert ok and "nothing to compare" in msg

    def test_empty_history_passes(self, tmp_path):
        ok, msg = bench.check_regression(
            self._rec(1.0), bench.load_bench_history(str(tmp_path)))
        assert ok and "nothing to compare" in msg

    def test_precision_and_bucket_variants_never_cross_compare(
            self, tmp_path):
        # a committed bf16+bucketed fast-path record must not baseline
        # an f32/serialized run (slower by design), and vice versa —
        # the filter keys on the record's precision block + bucket count
        fast = self._rec(67.5)
        fast["precision"] = {"compute_dtype": "bfloat16",
                             "param_dtype": "float32",
                             "loss_dtype": "float32"}
        fast["reduce_buckets"] = 8
        with open(tmp_path / "BENCH_r01.json", "w") as f:
            json.dump({"parsed": fast}, f)
        hist = bench.load_bench_history(str(tmp_path))
        # f32 record (precision null, no buckets): different trajectory
        ok, msg = bench.check_regression(self._rec(40.0), hist)
        assert ok and "nothing to compare" in msg
        # the matching fast-path variant DOES gate
        probe = self._rec(50.0)
        probe["precision"] = dict(fast["precision"])
        probe["reduce_buckets"] = 8
        ok, msg = bench.check_regression(probe, hist)
        assert not ok and "regression" in msg

    def test_plan_variants_never_cross_compare(self, tmp_path):
        # a committed dp_tp (sharded-plan) record must never baseline
        # the pure-dp trajectory, and vice versa — the filter keys on
        # the record's plan block (null == the trivial dp default, so
        # committed pre-planner history still gates dp runs)
        tp = self._rec(30.0)
        tp["plan"] = {"strategy": "dp_tp", "data": 4, "model": 2,
                      "slices": 1, "shard_params": True,
                      "shard_opt_state": False}
        with open(tmp_path / "BENCH_r01.json", "w") as f:
            json.dump({"parsed": tp}, f)
        hist = bench.load_bench_history(str(tmp_path))
        # dp record (plan null): different trajectory, never gated by tp
        ok, msg = bench.check_regression(self._rec(10.0), hist)
        assert ok and "nothing to compare" in msg
        # the matching dp_tp record DOES gate
        probe = self._rec(20.0)
        probe["plan"] = dict(tp["plan"])
        ok, msg = bench.check_regression(probe, hist)
        assert not ok and "regression" in msg
        # and a pre-planner record (no plan key at all) still gates a
        # fresh default-dp record whose plan block is null
        old = self._rec(67.5)
        with open(tmp_path / "BENCH_r02.json", "w") as f:
            json.dump({"parsed": old}, f)
        hist = bench.load_bench_history(str(tmp_path))
        fresh = self._rec(50.0)
        fresh["plan"] = None
        ok, msg = bench.check_regression(fresh, hist)
        assert not ok and "BENCH_r02" in msg

    def test_elastic_records_never_baseline_static_ones(self, tmp_path):
        # an elastic-exercised record (its measured window absorbed
        # supervisor re-plans) and a static record are different
        # regimes — the filter keys on the elastic block; null == the
        # static default, so pre-elastic history still compares
        el = self._rec(30.0)
        el["elastic"] = {"topology_changes": 3, "replans": 3,
                         "recovery_p50_s": 2.1}
        with open(tmp_path / "BENCH_r01.json", "w") as f:
            json.dump({"parsed": el}, f)
        hist = bench.load_bench_history(str(tmp_path))
        # static record (elastic null): never gated by the elastic one
        ok, msg = bench.check_regression(self._rec(10.0), hist)
        assert ok and "nothing to compare" in msg
        # the matching elastic record DOES gate
        probe = self._rec(20.0)
        probe["elastic"] = dict(el["elastic"])
        ok, msg = bench.check_regression(probe, hist)
        assert not ok and "regression" in msg
        # and a pre-elastic record (no key at all) still gates a fresh
        # static record whose elastic block is null
        old = self._rec(67.5)
        with open(tmp_path / "BENCH_r02.json", "w") as f:
            json.dump({"parsed": old}, f)
        hist = bench.load_bench_history(str(tmp_path))
        fresh = self._rec(50.0)
        fresh["elastic"] = None
        ok, msg = bench.check_regression(fresh, hist)
        assert not ok and "BENCH_r02" in msg

    def test_elastic_block_schema(self):
        # the block builder (train/elastic.py): null when no supervisor
        # re-planned, the three schema keys when one did
        from distributedpytorch_tpu.train.elastic import (
            ELASTIC_KEYS,
            elastic_block,
        )

        assert elastic_block() is None
        assert elastic_block({"restarts": {"crashed": 2}}) is None
        blk = elastic_block({
            "restarts": {"topology_changed": 3},
            "topology_changes": [{"replan": True}, {"replan": True},
                                 {"replan": False}],
            "topology_recovery_seconds": [1.5, 0.5, 2.5]})
        assert set(blk) == set(ELASTIC_KEYS)
        assert blk["topology_changes"] == 3 and blk["replans"] == 2
        assert blk["recovery_p50_s"] == 1.5

    def test_recorder_armed_records_never_baseline_off_ones(
            self, tmp_path):
        # a record measured with the flight recorder armed (events block
        # populated) and a recorder-off one are different regimes — the
        # filter keys on the block's path; null/missing == off (the
        # default), so pre-recorder committed history still compares
        armed = self._rec(60.0)
        armed["events"] = {"emitted": 12, "dropped": 0,
                           "path": "runs/run_0001/events/h.1.jsonl"}
        with open(tmp_path / "BENCH_r01.json", "w") as f:
            json.dump({"parsed": armed}, f)
        hist = bench.load_bench_history(str(tmp_path))
        # recorder-off candidate: the armed record is not its baseline
        ok, msg = bench.check_regression(self._rec(40.0), hist)
        assert ok and "nothing to compare" in msg
        # recorder-armed candidate gates against the armed record
        cand = self._rec(40.0)
        cand["events"] = {"emitted": 3, "dropped": 0,
                          "path": "runs/run_0002/events/h.2.jsonl"}
        ok, msg = bench.check_regression(cand, hist)
        assert not ok and "regression" in msg
        # an all-null events block is the off regime, same as missing
        nulled = self._rec(58.0)
        nulled["events"] = {"emitted": None, "dropped": None,
                            "path": None}
        prior = self._rec(60.0)
        with open(tmp_path / "BENCH_r02.json", "w") as f:
            json.dump({"parsed": prior}, f)
        ok, _ = bench.check_regression(
            nulled, bench.load_bench_history(str(tmp_path)))
        assert ok

    def test_events_block_schema(self):
        # the block builder (telemetry/events.py): keys ALWAYS present,
        # all null when no log is configured
        from distributedpytorch_tpu.telemetry import events as events_lib

        saved = events_lib._STACK[:]
        events_lib._STACK.clear()
        try:
            blk = events_lib.events_block()
        finally:
            events_lib._STACK.extend(saved)
        assert blk == {"emitted": None, "dropped": None, "path": None}
        assert not bench._events_enabled({"events": blk})
        assert not bench._events_enabled({})
        assert bench._events_enabled(
            {"events": {"emitted": 1, "dropped": 0, "path": "x.jsonl"}})

    def test_quantization_variants_never_cross_compare(self, tmp_path):
        # an int8-quantized serve record and an f32 one run different
        # compiled programs — the filter keys on the quantization
        # block; null == unquantized, so pre-quantization history
        # still gates unquantized records
        int8 = self._rec(60.0, metric="danet_resnet18_64px_serve_b8_x")
        int8["quantization"] = {"weight_dtype": "int8",
                                "granularity": "per_channel",
                                "symmetric": True}
        with open(tmp_path / "BENCH_r01.json", "w") as f:
            json.dump({"parsed": int8}, f)
        hist = bench.load_bench_history(str(tmp_path))
        # unquantized record: different trajectory
        f32 = self._rec(10.0, metric="danet_resnet18_64px_serve_b8_x")
        f32["quantization"] = None
        ok, msg = bench.check_regression(f32, hist)
        assert ok and "nothing to compare" in msg
        # the matching int8 record DOES gate
        probe = self._rec(40.0, metric="danet_resnet18_64px_serve_b8_x")
        probe["quantization"] = dict(int8["quantization"])
        ok, msg = bench.check_regression(probe, hist)
        assert not ok and "regression" in msg
        # pre-quantization history (no key) still gates a fresh
        # unquantized record whose block is null
        old = self._rec(67.5, metric="danet_resnet18_64px_serve_b8_x")
        with open(tmp_path / "BENCH_r02.json", "w") as f:
            json.dump({"parsed": old}, f)
        hist = bench.load_bench_history(str(tmp_path))
        ok, msg = bench.check_regression(f32, hist)
        assert not ok and "BENCH_r02" in msg

    def test_aot_warm_records_never_baseline_cold_ones(self, tmp_path):
        # a warm-cache boot (aot_cache=hit) and a cold-compile one are
        # different cold-start regimes — the filter keys on the
        # cold_start.aot_cache value; a missing cold_start (train
        # records, pre-AOT history) normalizes to "off"
        warm = self._rec(60.0, metric="serve_m")
        warm["cold_start"] = {"warmup_seconds": 0.4,
                              "programs_compiled": 0,
                              "aot_cache": "hit"}
        with open(tmp_path / "BENCH_r01.json", "w") as f:
            json.dump({"parsed": warm}, f)
        hist = bench.load_bench_history(str(tmp_path))
        cold = self._rec(10.0, metric="serve_m")
        cold["cold_start"] = {"warmup_seconds": 8.2,
                              "programs_compiled": 4,
                              "aot_cache": "off"}
        ok, msg = bench.check_regression(cold, hist)
        assert ok and "nothing to compare" in msg
        # matching warm record gates
        probe = self._rec(40.0, metric="serve_m")
        probe["cold_start"] = dict(warm["cold_start"],
                                   warmup_seconds=0.5)
        ok, msg = bench.check_regression(probe, hist)
        assert not ok and "regression" in msg
        # pre-AOT history (no cold_start key) == "off": still gates a
        # fresh cold record
        old = self._rec(67.5, metric="serve_m")
        with open(tmp_path / "BENCH_r02.json", "w") as f:
            json.dump({"parsed": old}, f)
        hist = bench.load_bench_history(str(tmp_path))
        ok, msg = bench.check_regression(cold, hist)
        assert not ok and "BENCH_r02" in msg

    def test_quantize_and_aot_envs_are_non_default_configs(
            self, monkeypatch):
        monkeypatch.setenv("DPTPU_BENCH_QUANTIZE", "int8")
        assert not bench._is_default_config()
        monkeypatch.delenv("DPTPU_BENCH_QUANTIZE")
        monkeypatch.setenv("DPTPU_BENCH_AOT_CACHE", "/tmp/aot")
        assert not bench._is_default_config()
        monkeypatch.delenv("DPTPU_BENCH_AOT_CACHE")

    def test_cold_start_block_schema(self):
        # train records: block null, key present (stamped in main());
        # serve records: the three keys from the service's last warmup
        assert bench._cold_start_block(None) is None
        blk = bench._cold_start_block(
            {"warmup_seconds": 1.25, "programs_compiled": 2,
             "programs_loaded": 0, "aot_cache": "off",
             "programs": []})
        assert blk == {"warmup_seconds": 1.25, "programs_compiled": 2,
                       "aot_cache": "off"}
        assert bench._cold_start_aot({"cold_start": None}) == "off"
        assert bench._cold_start_aot({}) == "off"
        assert bench._cold_start_aot(
            {"cold_start": {"aot_cache": "hit"}}) == "hit"

    def test_feed_source_variants_never_cross_compare(self, tmp_path):
        # a packed-plane record (DPTPU_BENCH_SOURCE=packed) and an fs
        # one measure different input regimes — the filter keys on
        # feed.source; a missing source key (pre-pack history, serve
        # records' feed=null) normalizes to the fs default
        packed = self._rec(30.0)
        packed["feed"] = {"input_wait_fraction": 0.0, "governor": None,
                          "echo_effective": None, "source": "packed"}
        with open(tmp_path / "BENCH_r01.json", "w") as f:
            json.dump({"parsed": packed}, f)
        hist = bench.load_bench_history(str(tmp_path))
        # fs record: different trajectory, never gated by the packed one
        fs = self._rec(10.0)
        fs["feed"] = {"input_wait_fraction": 0.0, "governor": None,
                      "echo_effective": None, "source": "fs"}
        ok, msg = bench.check_regression(fs, hist)
        assert ok and "nothing to compare" in msg
        # the matching packed record DOES gate
        probe = self._rec(20.0)
        probe["feed"] = dict(packed["feed"])
        ok, msg = bench.check_regression(probe, hist)
        assert not ok and "regression" in msg
        # pre-pack history (feed block without a source key) still
        # gates a fresh fs record — missing == "fs"
        old = self._rec(67.5)
        old["feed"] = {"input_wait_fraction": 0.0, "governor": None,
                       "echo_effective": None}
        with open(tmp_path / "BENCH_r02.json", "w") as f:
            json.dump({"parsed": old}, f)
        hist = bench.load_bench_history(str(tmp_path))
        ok, msg = bench.check_regression(fs, hist)
        assert not ok and "BENCH_r02" in msg

    def test_source_env_is_a_non_default_config(self, monkeypatch):
        # DPTPU_BENCH_SOURCE is an A/B knob like strategy/precision:
        # a source variant never gates the default-config trajectory
        monkeypatch.setenv("DPTPU_BENCH_SOURCE", "packed")
        assert not bench._is_default_config()
        monkeypatch.delenv("DPTPU_BENCH_SOURCE")

    def test_strategy_env_is_a_non_default_config(self, monkeypatch):
        # DPTPU_BENCH_STRATEGY is an A/B knob: the regression gate must
        # skip it (a dp_tp run is a measurement, not a trajectory point)
        monkeypatch.setenv("DPTPU_BENCH_STRATEGY", "dp_tp")
        assert not bench._is_default_config()
        monkeypatch.delenv("DPTPU_BENCH_STRATEGY")

    def test_non_default_config_never_gates(self, monkeypatch, capsys):
        # DPTPU_BENCH_* A/B overrides are exploratory measurements: the
        # gate skips them instead of failing a slower-by-design variant
        monkeypatch.setattr(bench, "_is_default_config", lambda: False)
        monkeypatch.setattr(
            bench, "_CLI_ARGS",
            type("A", (), {"check_regression": True})())
        bench._maybe_check_regression(self._rec(1.0))  # no SystemExit
        assert "skipped (non-default A/B config" in capsys.readouterr().err

    def test_repo_history_loads(self):
        # the committed BENCH_r*.json set parses (schema guard)
        hist = bench.load_bench_history()
        assert hist, "no committed BENCH_*.json parsed"
        for _, rec in hist:
            assert "metric" in rec and "value" in rec


class TestFeedBlock:
    """The `feed` record block (data/governor.py) + the
    --check-regression feed gate: ROADMAP item 2's "input_wait ≈ 0 on
    the bench config" acceptance, made mechanical."""

    def _record(self, feed):
        return {"metric": "m", "value": 1.0, "platform": "cpu",
                "feed": feed}

    def test_feed_block_schema_stability(self):
        from distributedpytorch_tpu.data.governor import feed_block

        # keys ALWAYS present, null-valued when off (the PR 4 convention)
        assert feed_block(None) == {"input_wait_fraction": None,
                                    "governor": None,
                                    "echo_effective": None,
                                    "source": "fs"}
        blk = feed_block(
            {"buckets": {"step": 7.0, "compile": 1.0, "input_wait": 2.0,
                         "checkpoint": 99.0, "eval": 99.0}},
            governor="observe", echo_effective=3, source="packed")
        # checkpoint/eval are not feed time: 2 / (7 + 1 + 2)
        assert blk == {"input_wait_fraction": 0.2, "governor": "observe",
                       "echo_effective": 3, "source": "packed"}
        json.dumps(blk)

    def test_ungoverned_record_passes_feed_gate(self):
        ok, msg = bench.check_feed(self._record(
            {"input_wait_fraction": 0.9, "governor": None,
             "echo_effective": None}))
        assert ok and "ungoverned" in msg
        ok, _ = bench.check_feed(self._record(None))
        assert ok  # serve records carry feed=null — never gated

    def test_governed_record_gates_against_target(self):
        ok, _ = bench.check_feed(self._record(
            {"input_wait_fraction": 0.05, "governor": "observe",
             "echo_effective": None}), target=0.1)
        assert ok
        ok, msg = bench.check_feed(self._record(
            {"input_wait_fraction": 0.3, "governor": "observe",
             "echo_effective": None}), target=0.1)
        assert not ok and "above the" in msg

    def test_governed_without_measurement_fails(self):
        ok, msg = bench.check_feed(self._record(
            {"input_wait_fraction": None, "governor": "auto",
             "echo_effective": None}), target=0.1)
        assert not ok and "no measured" in msg

    def test_default_target_is_the_config_default(self):
        from distributedpytorch_tpu.train.config import DataConfig

        assert bench._governor_target() == DataConfig().governor_target

    def test_env_overrides_target(self, monkeypatch):
        monkeypatch.setenv("DPTPU_BENCH_GOVERNOR_TARGET", "0.03")
        assert bench._governor_target() == 0.03


class TestPrecisionBlock:
    def test_bench_precision_block_schema(self):
        # the bench stamps `precision` into every record: null when f32,
        # the policy dtypes under bf16 — via the one shared helper
        from distributedpytorch_tpu.train.precision import (
            precision_block,
            precision_policy,
        )

        assert precision_block(precision_policy("float32")) is None
        blk = precision_block(precision_policy("bfloat16"))
        assert blk == {"compute_dtype": "bfloat16",
                       "param_dtype": "float32",
                       "loss_dtype": "float32"}
