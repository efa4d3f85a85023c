"""Measurement helpers: percentile, throughput, device memory.  (Device
traces are TraceCapture's: tests/test_scopes.py, tests/test_telemetry.py.)"""

import pytest

import jax.numpy as jnp


class TestPercentile:
    """Nearest-rank percentile — shared by serve/metrics and the registry."""

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
