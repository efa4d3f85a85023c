"""Unified telemetry: registry/spans/goodput/MFU/Prometheus/trace.

The acceptance surface of the telemetry layer: attribution sums to
wall-clock, a real (tiny) ``fit`` populates the compile/checkpoint/eval/
input-wait buckets and lands goodput + MFU in ``metrics.jsonl``, the
Prometheus exposition parses with stable names and monotonic counters,
the on-demand trace trigger writes a bounded XPlane capture, and the
instrumentation primitives cost <= 2% of a step.
"""

import dataclasses
import json
import re
import time

import pytest

from distributedpytorch_tpu.telemetry import (
    GoodputAccountant,
    MetricsRegistry,
    TraceCapture,
    mfu_estimate,
    peak_flops_for,
    render_text,
    span,
)
from distributedpytorch_tpu.telemetry.prometheus import CONTENT_TYPE


class TestRegistry:
    def test_counter_gauge_histogram_roundtrip(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total", "requests")
        c.inc()
        c.inc(2)
        assert c.value == 3
        g = reg.gauge("depth")
        g.set(5)
        g.inc()
        g.dec(2)
        assert g.value == 4
        h = reg.histogram("lat_seconds")
        for v in (0.1, 0.3, 0.2):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 3 and snap["sum"] == pytest.approx(0.6)
        # nearest-rank: always an observed sample
        assert h.percentile(50.0) == 0.2
        assert h.percentile(99.0) == 0.3

    def test_get_or_create_is_same_child(self):
        reg = MetricsRegistry()
        assert reg.counter("x_total") is reg.counter("x_total")
        a = reg.counter("y_total", labels={"k": "1"})
        b = reg.counter("y_total", labels={"k": "2"})
        assert a is not b
        assert reg.counter("y_total", labels={"k": "1"}) is a

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("z_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("z_total")

    def test_bad_names_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="bad metric name"):
            reg.counter("no spaces")
        with pytest.raises(ValueError, match="bad label name"):
            reg.counter("ok_total", labels={"bad-label": "v"})

    def test_counter_rejects_negative(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match=">= 0"):
            reg.counter("n_total").inc(-1)

    def test_histogram_reservoir_bounds_tail_window(self):
        reg = MetricsRegistry()
        h = reg.histogram("w_seconds", reservoir=4)
        for v in (9.0, 9.0, 9.0, 1.0, 1.0, 1.0, 1.0):
            h.observe(v)
        # totals stay monotonic across the wrap; the tail is CURRENT
        assert h.count == 7
        assert h.percentile(99.0) == 1.0


class TestSpans:
    def test_nested_paths_recorded(self):
        reg = MetricsRegistry()
        with span("fit", registry=reg):
            with span("checkpoint", registry=reg):
                pass
        outer = reg.histogram("span_seconds", labels={"span": "fit"})
        inner = reg.histogram("span_seconds",
                              labels={"span": "fit/checkpoint"})
        assert outer.count == 1 and inner.count == 1

    def test_stack_unwinds_on_exception(self):
        reg = MetricsRegistry()
        with pytest.raises(RuntimeError):
            with span("a", registry=reg):
                raise RuntimeError("boom")
        with span("b", registry=reg):
            pass
        # a leaked stack would record b as "a/b"
        assert reg.histogram("span_seconds", labels={"span": "b"}).count == 1


class TestGoodputAccountant:
    def test_buckets_sum_to_wall_clock(self):
        acct = GoodputAccountant(registry=MetricsRegistry())
        with acct.account("step"):
            time.sleep(0.02)
        with acct.account("input_wait"):
            time.sleep(0.01)
        rep = acct.report(publish=False)
        # idle is derived, so the sum is exact by construction — the
        # invariant the ±5% fit-level check builds on
        assert sum(rep["buckets"].values()) == pytest.approx(
            rep["total_s"], rel=1e-9)
        assert rep["buckets"]["step"] >= 0.015
        assert rep["goodput"] == pytest.approx(
            rep["buckets"]["step"] / rep["total_s"])

    def test_nested_attribution_is_exclusive(self):
        acct = GoodputAccountant(registry=MetricsRegistry())
        with acct.account("eval"):
            time.sleep(0.02)
            with acct.account("checkpoint"):  # pauses the eval clock
                time.sleep(0.03)
            time.sleep(0.01)
        rep = acct.report(publish=False)
        assert rep["buckets"]["checkpoint"] >= 0.025
        assert 0.02 <= rep["buckets"]["eval"] < 0.05
        assert rep["counts"] == {"step": 0, "compile": 0, "checkpoint": 1,
                                 "eval": 1, "input_wait": 0}

    def test_unknown_bucket_raises(self):
        acct = GoodputAccountant(registry=MetricsRegistry())
        with pytest.raises(ValueError, match="unknown goodput bucket"):
            with acct.account("vibes"):
                pass

    def test_disabled_is_noop(self):
        acct = GoodputAccountant(registry=MetricsRegistry())
        acct.reset(enabled=False)
        with acct.account("step"):
            time.sleep(0.01)
        rep = acct.report(publish=False)
        assert rep["buckets"]["step"] == 0.0

    def test_publish_lands_registry_gauges(self):
        reg = MetricsRegistry()
        acct = GoodputAccountant(registry=reg)
        with acct.account("step"):
            time.sleep(0.005)
        acct.report()
        assert reg.gauge("goodput_seconds",
                         labels={"bucket": "step"}).value > 0
        assert 0.0 < reg.gauge("goodput_ratio").value <= 1.0


class TestMFU:
    def test_known_kind_uses_table(self):
        peak, source = peak_flops_for("TPU v5e chip")
        assert peak == 197e12 and source == "v5e"

    def test_estimate_math(self):
        est = mfu_estimate(197e12 * 0.5, 1.0, device_kind="v5e")
        assert est["mfu"] == pytest.approx(0.5)
        assert est["peak_source"] == "v5e"
        with pytest.raises(ValueError):
            mfu_estimate(0.0, 1.0, device_kind="v5e")


_METRIC_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"            # name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""  # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" (NaN|[+-]Inf|-?[0-9.e+-]+)$")


class TestPrometheusExposition:
    def _assert_parseable(self, text: str) -> dict:
        values = {}
        for line in text.strip().splitlines():
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                continue
            assert _METRIC_LINE.match(line), f"unparseable line: {line!r}"
            name, _, val = line.rpartition(" ")
            values[name] = float(val) if val not in ("NaN",) else val
        return values

    def test_output_parses_and_types_declared(self):
        reg = MetricsRegistry()
        reg.counter("a_total", "things").inc(2)
        reg.gauge("b_depth").set(1.5)
        reg.histogram("c_seconds", labels={"span": "x/y"}).observe(0.25)
        text = render_text(reg)
        assert "# TYPE a_total counter" in text
        assert "# TYPE b_depth gauge" in text
        assert "# TYPE c_seconds summary" in text
        values = self._assert_parseable(text)
        assert values["a_total"] == 2
        assert values['c_seconds{span="x/y",quantile="0.5"}'] == 0.25
        assert values['c_seconds_count{span="x/y"}'] == 1
        assert "version=0.0.4" in CONTENT_TYPE

    def test_counters_render_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("mono_total")
        c.inc(3)
        v1 = self._assert_parseable(render_text(reg))["mono_total"]
        c.inc(4)
        v2 = self._assert_parseable(render_text(reg))["mono_total"]
        assert v2 >= v1 and (v1, v2) == (3, 7)

    def test_serve_metric_names_stable(self):
        # the scrape-side contract: dashboards key on these exact names
        reg = MetricsRegistry()
        from distributedpytorch_tpu.serve.metrics import ServeMetrics
        m = ServeMetrics(registry=reg)
        m.count("requests")
        m.observe_batch(4, 3)
        m.observe_latency(0.01)
        text = render_text(reg)
        for name in ("serve_requests_total", "serve_batches_total",
                     "serve_shed_queue_full_total",
                     "serve_retrace_failures_total",
                     'serve_batch_dispatches_total{bucket="4"}',
                     "serve_latency_seconds_count"):
            assert name in text, f"{name} missing from exposition"
        self._assert_parseable(text)

    def test_hostile_label_values_escape_and_parse_back(self):
        # the 0.0.4 label contract: backslash, double-quote and line
        # feed must escape — a path label with any of them must round
        # trip through the exposition, not corrupt the line shape
        reg = MetricsRegistry()
        hostile = 'C:\\runs\\"prod"\nnext'
        reg.counter("paths_total", labels={"path": hostile}).inc(3)
        text = render_text(reg)
        (line,) = [ln for ln in text.splitlines()
                   if ln.startswith("paths_total{")]
        # one physical line (the newline escaped, not emitted)
        assert "\n" not in line
        # parse back per spec: value after the closing brace, label
        # value unescaped in reverse order of the escape
        m = re.match(r'^paths_total\{path="((?:\\.|[^"\\])*)"\} (\S+)$',
                     line)
        assert m, f"unparseable hostile-label line: {line!r}"
        unescaped = (m.group(1).replace("\\n", "\n")
                     .replace('\\"', '"').replace("\\\\", "\\"))
        assert unescaped == hostile
        assert float(m.group(2)) == 3

    def test_help_text_escapes_backslash_newline_only(self):
        # HELP escaping differs from label escaping: \\ and \n only —
        # a double-quote in HELP must pass through literally
        reg = MetricsRegistry()
        reg.counter("h_total", 'reads "raw" lines\nfrom C:\\logs').inc()
        text = render_text(reg)
        (help_line,) = [ln for ln in text.splitlines()
                        if ln.startswith("# HELP h_total ")]
        assert help_line == ('# HELP h_total reads "raw" '
                             'lines\\nfrom C:\\\\logs')

    def test_serve_metrics_view_is_per_service(self):
        # two services sharing one process/registry must each report
        # "monotonic since service start", not each other's traffic
        reg = MetricsRegistry()
        from distributedpytorch_tpu.serve.metrics import ServeMetrics
        a = ServeMetrics(registry=reg)
        a.count("requests", 5)
        b = ServeMetrics(registry=reg)
        b.count("requests", 2)
        assert a.requests == 7  # a sees the whole process since ITS start
        assert b.requests == 2
        assert b.snapshot()["requests"] == 2


class TestTraceCapture:
    @pytest.mark.slow  # tier-1 budget (PR 7): real XPlane capture
    # (~18s); arming/refusal logic stays fast-gated below
    def test_bounded_capture_writes_xplane(self, tmp_path):
        import jax.numpy as jnp
        trig = TraceCapture(str(tmp_path), default_steps=2)
        target = trig.request()
        assert target is not None
        assert trig.request() is None, "double-arm must be refused"
        for _ in range(4):
            trig.tick(1)
            jnp.ones((4, 4)).sum().block_until_ready()
        trig.close()
        import os
        assert os.path.isdir(target) and os.listdir(target)
        # re-armable for a second, distinct capture
        assert trig.request(steps=1) not in (None, target)

    def test_steps_clamped_to_max(self, tmp_path):
        trig = TraceCapture(str(tmp_path), max_steps=5)
        trig.request(steps=10**6)
        assert trig._want == 5
        trig._want = 0  # disarm without starting

    def test_query_steps_parser(self):
        from distributedpytorch_tpu.telemetry.trace import query_steps
        assert query_steps("steps=7") == 7
        assert query_steps("", default=3) == 3
        assert query_steps("steps=nope", default=3) == 3


def _tiny_cfg(work):
    from distributedpytorch_tpu.train import Config
    cfg = Config()
    return dataclasses.replace(
        cfg,
        data=dataclasses.replace(
            cfg.data, fake=True, train_batch=8, val_batch=2, num_workers=2,
            crop_size=(64, 64), relax=10, area_thres=0),
        model=dataclasses.replace(cfg.model, backbone="resnet18",
                                  output_stride=8),
        optim=dataclasses.replace(cfg.optim, lr=1e-4, schedule="poly"),
        checkpoint=dataclasses.replace(cfg.checkpoint, async_save=False),
        epochs=3, eval_every=3, seed=0, work_dir=work, log_every_steps=1,
    )


class TestGoodputEndToEnd:
    @pytest.mark.slow  # tier-1 budget (PR 20): 3-step real fit (~15s);
    # fast gate: TestGoodputAccountant units +
    # test_telemetry_disabled_fit_still_works +
    # TestInstrumentationOverhead
    def test_three_step_fit_breakdown_and_mfu(self, tmp_path):
        """The acceptance scenario: a 3-step CPU fake-data fit produces a
        goodput breakdown whose buckets sum to wall-clock (±5%), in both
        the history and metrics.jsonl — and no MFU: that is a device
        metric and a CPU has no peak to divide by."""
        import os

        from distributedpytorch_tpu.train import Trainer
        tr = Trainer(_tiny_cfg(str(tmp_path / "runs")))
        hist = tr.fit()
        tr.close()
        rep = hist["goodput"]
        total = rep["total_s"]
        assert abs(sum(rep["buckets"].values()) - total) <= 0.05 * total
        for bucket in ("step", "compile", "checkpoint", "eval",
                       "input_wait"):
            assert rep["buckets"][bucket] > 0, f"{bucket} bucket empty"
        # compile (first trace+XLA of the step) dwarfs a single tiny step
        assert rep["buckets"]["compile"] > rep["buckets"]["step"] / 10
        assert "mfu" not in hist
        # the same numbers must be greppable from the run record
        lines = [json.loads(line, parse_constant=lambda s: None)
                 for line in open(os.path.join(tr.run_dir,
                                               "metrics.jsonl"))]
        good = [rec for rec in lines if "goodput/total_s" in rec]
        assert good, "no goodput record in metrics.jsonl"
        rec = good[-1]
        assert "mfu" not in rec
        assert rec["goodput/productive_frac"] == pytest.approx(
            rep["goodput"], abs=1e-3)

    @pytest.mark.slow  # full fit; test_disabled_is_noop is the fast gate
    def test_telemetry_disabled_fit_still_works(self, tmp_path):
        from distributedpytorch_tpu.telemetry import (
            MetricsRegistry,
            is_enabled,
            set_enabled,
            span,
        )
        from distributedpytorch_tpu.train import Trainer
        cfg = dataclasses.replace(_tiny_cfg(str(tmp_path / "runs")),
                                  telemetry=False, epochs=1, eval_every=1)
        tr = Trainer(cfg)
        try:
            hist = tr.fit()
            tr.close()
            assert len(hist["train_loss"]) == 1
            assert "goodput" not in hist  # no books kept, none reported
            # the knob disables ALL optional instrumentation, spans too —
            # the true zero-instrumentation baseline
            assert not is_enabled()
            reg = MetricsRegistry()
            with span("should_not_record", registry=reg):
                pass
            assert not reg.collect()
        finally:
            set_enabled(True)  # process-wide flag; restore for the suite


class TestInstrumentationOverhead:
    def test_overhead_at_most_two_percent_of_step(self):
        """The <=2% contract, measured: the per-step instrumentation cost
        (input-wait account + step account + trace tick) against the mean
        step time of a representative (tiny, device-backed) train step."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            # representative small-step cost (~ms): far below any real
            # train step (the tiny-fit step above is ~1s on CPU), so the
            # 2% bound here is the conservative end of the contract
            return (x @ x @ x).sum()

        x = jnp.ones((256, 256))
        float(step(x))  # compile outside the clock
        t0 = time.perf_counter()
        n_steps = 30
        for _ in range(n_steps):
            float(step(x))
        step_s = (time.perf_counter() - t0) / n_steps

        acct = GoodputAccountant(registry=MetricsRegistry())
        trig = TraceCapture("/tmp/unused-trace")  # never armed: idle cost
        reps = 2000
        t0 = time.perf_counter()
        for _ in range(reps):
            with acct.account("input_wait"):
                pass
            trig.tick(1)
            with acct.account("step"):
                pass
        per_step_overhead = (time.perf_counter() - t0) / reps
        assert per_step_overhead <= 0.02 * step_s, (
            f"instrumentation {per_step_overhead * 1e6:.1f}us/step vs "
            f"step {step_s * 1e6:.1f}us")

    def test_event_emission_at_most_two_percent_of_step(self, tmp_path):
        """The flight recorder's armed emit() — a full event line,
        serialized and written — pinned to the same <=2%-of-step
        contract as the span/account primitives, against the same
        representative tiny step."""
        import jax
        import jax.numpy as jnp

        from distributedpytorch_tpu.telemetry import events as events_lib

        @jax.jit
        def step(x):
            return (x @ x @ x).sum()

        x = jnp.ones((256, 256))
        float(step(x))  # compile outside the clock
        t0 = time.perf_counter()
        n_steps = 30
        for _ in range(n_steps):
            float(step(x))
        step_s = (time.perf_counter() - t0) / n_steps

        log = events_lib.configure(str(tmp_path))
        try:
            reps = 2000
            t0 = time.perf_counter()
            for i in range(reps):
                events_lib.emit("trainer", "tick", step=i,
                                payload={"loss": 0.5, "stall": 0.01})
            per_step_overhead = (time.perf_counter() - t0) / reps
        finally:
            events_lib.release(log)
        assert log.block()["emitted"] == reps
        assert per_step_overhead <= 0.02 * step_s, (
            f"event emission {per_step_overhead * 1e6:.1f}us/step vs "
            f"step {step_s * 1e6:.1f}us")

    def test_unconfigured_emit_is_nanoseconds(self):
        """The recorder-off path (no configure) must cost one list
        check — the chaos-seam discipline applied to observability."""
        from distributedpytorch_tpu.telemetry import events as events_lib

        saved = events_lib._STACK[:]
        events_lib._STACK.clear()  # force the unconfigured path
        try:
            assert events_lib.current() is None
            reps = 20000
            t0 = time.perf_counter()
            for i in range(reps):
                events_lib.emit("trainer", "tick", step=i)
            per_call = (time.perf_counter() - t0) / reps
        finally:
            events_lib._STACK.extend(saved)
        assert per_call < 5e-6, f"no-op emit {per_call * 1e9:.0f}ns"
