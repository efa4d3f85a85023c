"""Profiling utilities: StepTimer semantics, annotate/trace no-crash."""

import os

import pytest

import jax.numpy as jnp

from distributedpytorch_tpu.utils import StepTimer, annotate, trace


class TestStepTimer:
    def test_warmup_skipped(self):
        t = StepTimer(warmup=2)
        for _ in range(5):
            t.tick(jnp.zeros(()))
        # 5 ticks = 4 intervals; first 2 are warmup
        assert t.summary()["steps"] == 2

    def test_items_per_sec(self):
        t = StepTimer(warmup=0)
        for _ in range(3):
            t.tick()
        s = t.summary(items_per_step=10)
        assert s["steps"] == 2
        assert s["items_per_sec"] > 0
        assert s["min_s"] <= s["p50_s"] <= s["max_s"]

    def test_empty_summary(self):
        assert StepTimer().summary() == {"steps": 0}

    def test_summary_percentiles(self):
        t = StepTimer(warmup=0)
        for _ in range(6):
            t.tick()
        s = t.summary()
        assert s["p50_s"] <= s["p99_s"] <= s["max_s"]

    def test_tick_blocks_without_materializing(self, monkeypatch):
        # a tick waits for the device (block_until_ready) and never copies
        # the outputs to the host
        import distributedpytorch_tpu.utils.profiling as prof
        calls = []
        monkeypatch.setattr(prof.jax, "block_until_ready",
                            lambda o: calls.append(("block", o)))
        monkeypatch.setattr(prof.jax, "device_get",
                            lambda o: calls.append(("get", o)))
        t = StepTimer(warmup=0)
        t.tick(jnp.zeros(()))
        assert [kind for kind, _ in calls] == ["block"]


class TestPercentile:
    """Nearest-rank percentile — shared by StepTimer and serve/metrics."""

    def test_nearest_rank_is_an_observed_sample(self):
        from distributedpytorch_tpu.utils.profiling import percentile
        values = [5.0, 1.0, 3.0, 2.0, 4.0]
        assert percentile(values, 0.0) == 1.0
        assert percentile(values, 50.0) == 3.0
        assert percentile(values, 99.0) == 5.0
        assert percentile(values, 100.0) == 5.0
        # every answer is a member, never an interpolation
        for q in (0.0, 10.0, 37.5, 50.0, 90.0, 99.0, 100.0):
            assert percentile(values, q) in values

    def test_errors(self):
        from distributedpytorch_tpu.utils.profiling import percentile
        with pytest.raises(ValueError, match="no samples"):
            percentile([], 50.0)
        with pytest.raises(ValueError, match="0, 100"):
            percentile([1.0], 101.0)


class TestTrace:
    def test_annotate_context(self):
        with annotate("region"):
            x = jnp.ones((4,)) * 2
        assert float(x.sum()) == 8.0

    @pytest.mark.slow  # tier-1 budget (PR 18): a real XPlane capture
    # start/stop costs ~30s on the CPU mesh; the annotate path keeps its
    # fast gate (test_annotate_context) and the captured-trace contents
    # stay covered by test_telemetry's slow XPlane lowering test
    def test_trace_writes_files(self, tmp_path):
        d = str(tmp_path / "prof")
        with trace(d):
            jnp.ones((8, 8)).sum().block_until_ready()
        assert os.path.isdir(d) and len(os.listdir(d)) > 0


class TestThroughput:
    def test_counts_and_rate(self):
        from distributedpytorch_tpu.utils.profiling import throughput
        calls = []

        def step():
            calls.append(1)
            return jnp.ones((2, 2)).sum()

        s = throughput(step, steps=3, warmup=2, items_per_step=4)
        assert len(calls) == 5  # warmup excluded from timing, included in calls
        assert s["steps"] == 3 and s["total_s"] > 0
        assert s["items_per_sec"] == pytest.approx(12 / s["total_s"])


def test_device_memory_stats_shape():
    from distributedpytorch_tpu.utils.profiling import device_memory_stats

    stats = device_memory_stats()
    assert set(stats) == {"bytes_in_use", "peak_bytes_in_use", "bytes_limit"}
    assert all(isinstance(v, int) and v >= 0 for v in stats.values())
