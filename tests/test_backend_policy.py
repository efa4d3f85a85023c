"""One device policy (backend_health): the platform comes from
``JAX_PLATFORMS`` alone, a measurement without a chip fails instead of
falling back, the compile cache is placed from outside, and a device the
peak table does not hold is an error."""

import os
import subprocess
import sys

import jax
import pytest

from distributedpytorch_tpu import backend_health
from distributedpytorch_tpu.telemetry.goodput import peak_flops_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCachePlacement:
    @pytest.fixture(autouse=True)
    def _restore_cache_dir(self):
        # jax latches the directory at its first compile, so flipping the
        # option here cannot move the session's live cache
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_variable_wins_and_nothing_is_set_in_code(self, monkeypatch,
                                                          tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "as-jax-read-it")
        assert backend_health.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "as-jax-read-it"

    def test_default_is_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert backend_health.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want


class TestPeakTable:
    def test_v5e_as_jax_names_it(self):
        assert peak_flops_for("TPU v5 lite") == (197e12, "v5 lite")

    @pytest.mark.parametrize("kind", ["TPU v9x", "cpu"])
    def test_unknown_kind_raises(self, kind):
        with pytest.raises(ValueError, match="no published peak"):
            peak_flops_for(kind)


class TestRequireAccelerator:
    def test_cpu_only_when_asked_for_by_name(self, monkeypatch):
        assert os.environ["JAX_PLATFORMS"] == "cpu"  # conftest's request
        assert backend_health.require_accelerator("t") == "cpu"
        monkeypatch.delenv("JAX_PLATFORMS")
        with pytest.raises(SystemExit, match="no TPU"):
            backend_health.require_accelerator("t")

    def test_bench_without_chip_or_request_prints_no_record(self):
        """``python bench.py`` on a machine with no TPU and JAX_PLATFORMS
        unset: JAX falls back to the CPU quietly; bench.py must not."""
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
        out = subprocess.run([sys.executable, "bench.py"], cwd=REPO, env=env,
                             capture_output=True, text=True, timeout=240)
        assert out.returncode != 0
        assert out.stdout.strip() == ""
        assert "no TPU" in out.stderr
