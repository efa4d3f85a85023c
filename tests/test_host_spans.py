"""The program's host spans and counters (PR 39): one span rule, a capture
that holds the whole epoch, the input feed's spans and counters, and a
second ``fit`` on one trainer.

One tiny DANet trains on a fixture of a few batches, once for the module:
two epochs under the trainer's own capture, then a third epoch through a
second ``fit``.  Everything else here runs no model."""

import collections
import contextlib
import dataclasses
import glob
import json
import os
import time

import jax
import numpy as np
import pytest

from distributedpytorch_tpu.data import DataLoader, make_fake_voc
from distributedpytorch_tpu.parallel import make_mesh, prefetch_to_device
from distributedpytorch_tpu.telemetry import (GoodputAccountant,
                                              MetricsRegistry, TraceCapture,
                                              feed, scopes, span)
from distributedpytorch_tpu.telemetry import trace as trace_lib
from distributedpytorch_tpu.train import Config, Trainer

BATCH = 8


def _cfg(work: str, root: str) -> Config:
    cfg = Config()
    return dataclasses.replace(
        cfg,
        data=dataclasses.replace(
            cfg.data, root=root, train_batch=BATCH, val_batch=2,
            num_workers=2, crop_size=(64, 64), relax=10, area_thres=0),
        model=dataclasses.replace(cfg.model, backbone="resnet18",
                                  output_stride=8),
        optim=dataclasses.replace(cfg.optim, lr=1e-4),
        checkpoint=dataclasses.replace(cfg.checkpoint, async_save=False),
        epochs=3, eval_every=1, seed=0, work_dir=work, log_every_steps=2)


def _host_events(trace_dir: str) -> list:
    """``[name, start_ns, end_ns, thread, args]`` of the host plane."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1, files
    data = jax.profiler.ProfileData.from_file(files[0])
    out = []
    for plane in data.planes:
        if plane.name != scopes.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            out.extend([e.name, e.start_ns, e.start_ns + e.duration_ns, i,
                        dict(e.stats)] for e in line.events)
    return out


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("host_spans")
    root = make_fake_voc(str(tmp / "voc"), n_images=24, size=(96, 128),
                         n_val=3, seed=3)
    tr = Trainer(_cfg(str(tmp / "runs"), root))
    try:
        n_batches = len(tr.train_loader)
        assert n_batches >= 2, "the fixture must give an epoch of batches"
        counts0 = feed.COUNTS.snapshot()
        trace_dir = str(tmp / "trace")
        with tr._trace.region(trace_dir):
            first = tr.fit(epochs=2)
        counts1 = feed.COUNTS.snapshot()
        after_first = tr.start_epoch, int(tr.state.step)
        second = tr.fit()
        yield {
            "events": _host_events(trace_dir), "n_batches": n_batches,
            "first": first, "second": second, "after_first": after_first,
            "after_second": (tr.start_epoch, int(tr.state.step)),
            "counts": {k: counts1[k] - counts0[k] for k in counts0},
            "saved": tr.ckpt.all_steps(), "run_dir": tr.run_dir,
        }
    finally:
        tr.close()


def test_one_trace_holds_the_loop_the_feed_validation_and_the_save(run):
    events = run["events"]
    names = collections.Counter(e[0] for e in events)
    for name in ("goodput/step", "goodput/input_wait", "goodput/eval",
                 "goodput/checkpoint", scopes.STEP_ANNOTATION,
                 scopes.INPUT_BATCH, scopes.INPUT_PLACE,
                 "eval/dispatch", "eval/pasteback",
                 "checkpoint/save", "checkpoint/wait"):
        assert names[name] > 0, (name, sorted(names))
    n = run["n_batches"]
    assert names[scopes.STEP_ANNOTATION] == 2 * n
    assert names["goodput/eval"] == 2
    # every name the program put there is in the declared vocabulary
    program = scopes.program_spans(events)
    assert {e[0] for e in program} >= {
        k for k in names if k.startswith(("goodput/", "input/", "eval/",
                                          "checkpoint/"))}
    # the loop's thread holds the steps and the buckets, never the feed
    loop = {e[3] for e in events if e[0] == scopes.STEP_ANNOTATION}
    assert len(loop) == 1
    on_loop = {e[0] for e in scopes.loop_thread_spans(program)}
    assert {"goodput/step", "goodput/input_wait", "goodput/eval",
            "goodput/checkpoint", "eval/pasteback",
            "checkpoint/save"} <= on_loop
    feed_threads = {e[3] for e in events if e[0].startswith("input/")}
    assert feed_threads and not feed_threads & loop
    assert not on_loop & {scopes.INPUT_BATCH, scopes.INPUT_PLACE}
    # eval/* lies beneath goodput/eval, checkpoint/* beneath its bucket
    for inner, outer in (("eval/pasteback", "goodput/eval"),
                         ("checkpoint/save", "goodput/checkpoint")):
        outers = [e for e in events if e[0] == outer]
        for e in (e for e in events if e[0] == inner):
            assert any(o[1] <= e[1] and e[2] <= o[2] and o[3] == e[3]
                       for o in outers), (inner, e)
    # a batch can be followed by its index: placement in order, twice over
    # (two epochs), and the loader's producer built each of those
    placed = [e[4]["batch"] for e in sorted(
        (e for e in events if e[0] == scopes.INPUT_PLACE),
        key=lambda e: e[1])]
    assert placed == list(range(n)) * 2
    built = collections.Counter(
        e[4]["batch"] for e in events if e[0] == scopes.INPUT_BATCH)
    assert all(built[i] >= 2 for i in range(n))
    steps = [e[4]["step_num"] for e in sorted(
        (e for e in events if e[0] == scopes.STEP_ANNOTATION),
        key=lambda e: e[1])]
    assert steps == list(range(2 * n))


def test_feed_counters_count_what_the_loop_consumed(run):
    c, n = run["counts"], run["n_batches"]
    assert c["input_fetch_total"] == 2 * n
    assert 0 <= c["input_fetch_ready_total"] <= c["input_fetch_total"]
    # the validation loader hands its batches out too
    assert c["input_batch_total"] > 2 * n
    assert 0 <= c["input_batch_ready_total"] <= c["input_batch_total"]
    # a fit's last record says the two shares beside its goodput buckets
    with open(os.path.join(run["run_dir"], "metrics.jsonl")) as f:
        ends = [r for r in map(json.loads, f) if "goodput/total_s" in r]
    assert len(ends) == 2
    for rec in ends:
        assert 0 <= rec["input/batch_ready_frac"] <= 1
        assert 0 <= rec["input/fetch_ready_frac"] <= 1


class _Rows:
    """``n`` one-pixel samples; ``delay`` seconds to load each."""

    def __init__(self, n, delay=0.0):
        self.n, self.delay = n, delay

    def __len__(self):
        return self.n

    def __getitem__(self, i, rng=None):
        time.sleep(self.delay)
        return {"concat": np.full((4, 4, 1), i, np.float32)}


@pytest.mark.parametrize("load_s,consume_s,starved", [
    (0.03, 0.0, True),    # the decode is the slower side: the loop waits
    (0.0, 0.06, False),   # the loop is: the producer keeps ahead
])
def test_the_batch_counters_tell_a_starved_loop_from_a_fed_one(
        load_s, consume_s, starved):
    loader = DataLoader(_Rows(128, load_s), 8, num_workers=2, prefetch=2)
    before = feed.COUNTS.snapshot()
    for _ in prefetch_to_device(iter(loader), make_mesh(), size=2):
        time.sleep(consume_s)
    after = feed.COUNTS.snapshot()
    d = {k: after[k] - before[k] for k in after}
    assert d["input_batch_total"] == d["input_fetch_total"] == 16
    # the placement is never the wait here, so its share cannot tell
    assert d["input_fetch_ready_total"] >= 12, d
    if starved:
        assert d["input_batch_ready_total"] <= 3, d
    else:
        # all but the first few, which prefetch_to_device takes at once
        # (and room for a busy machine)
        assert d["input_batch_ready_total"] >= 9, d


def test_a_second_fit_continues_and_compiles_nothing(run):
    n = run["n_batches"]
    assert run["after_first"] == (2, 2 * n)
    assert len(run["first"]["train_loss"]) == 2
    assert run["first"]["goodput"]["counts"]["compile"] >= 1
    assert run["after_second"] == (3, 3 * n)
    assert len(run["second"]["train_loss"]) == 1
    assert len(run["second"]["val"]) == 1
    assert run["second"]["val"][0]["epoch"] == 2
    assert run["second"]["goodput"]["counts"]["compile"] == 0
    assert run["second"]["goodput"]["counts"]["step"] >= n
    assert run["saved"][-1] == 3 * n


class _Raises:
    def __init__(self, *a, **kw):
        raise AssertionError("a jax.profiler call with no capture recording")


def test_with_no_capture_nothing_calls_the_profiler(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Raises)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", _Raises)
    assert not trace_lib.capturing()
    reg = MetricsRegistry()
    with span("checkpoint/save", registry=reg) as path:
        assert path == "checkpoint/save"
    assert reg.histogram("span_seconds",
                         labels={"span": "checkpoint/save"}).count == 1
    acct = GoodputAccountant(registry=reg)
    with acct.account("eval"), span("eval/readback", registry=reg):
        pass
    with trace_lib.annotation(scopes.INPUT_BATCH, batch=0):
        pass
    mesh = make_mesh()
    before = feed.COUNTS.snapshot()
    for workers in (0, 2):
        loader = DataLoader(_Rows(32), 8, num_workers=workers)
        got = [np.asarray(b["concat"])[:, 0, 0, 0] for b in
               prefetch_to_device(iter(loader), mesh, size=2)]
        assert [int(g[0]) for g in got] == [0, 8, 16, 24]
    after = feed.COUNTS.snapshot()
    for name in ("input_batch_total", "input_fetch_total"):
        assert after[name] - before[name] == 8
    assert feed.publish(reg) == after
    assert reg.counter("input_fetch_total").value == after["input_fetch_total"]


def test_while_a_capture_records_the_feed_annotates(monkeypatch, tmp_path):
    calls = []

    class Counting:
        def __init__(self, name, **kw):
            calls.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    reg = MetricsRegistry()
    cap = TraceCapture(str(tmp_path), registry=reg)
    with cap.region(str(tmp_path / "p")):
        loader = DataLoader(_Rows(16), 8, num_workers=2)
        loader.set_epoch(0, start_batch=1)
        list(prefetch_to_device(iter(loader), make_mesh(), size=2, start=1))
        with span("eval/dispatch", registry=reg):
            pass
    assert sorted(calls, key=lambda c: c[0]) == [
        ("eval/dispatch", {}), (scopes.INPUT_BATCH, {"batch": 1}),
        (scopes.INPUT_PLACE, {"batch": 1})]


def test_a_profiler_that_raises_leaves_the_span_stack_clean(monkeypatch,
                                                            tmp_path):
    broken = []

    class Annotation(contextlib.nullcontext):
        def __init__(self, name, **kw):
            if broken:
                raise RuntimeError("the profiler is gone")
            super().__init__()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    reg = MetricsRegistry()
    cap = TraceCapture(str(tmp_path), registry=reg)
    with cap.region(str(tmp_path / "p")):
        with span("checkpoint", registry=reg):
            broken.append(True)
            with pytest.raises(RuntimeError):
                with span("save", registry=reg):
                    pass
            broken.clear()
            with span("wait", registry=reg) as path:
                assert path == "checkpoint/wait"
    with span("eval", registry=reg) as path:
        assert path == "eval"


def test_only_a_region_capture_drops_the_python_tracer(monkeypatch, tmp_path):
    started = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: started.append(profiler_options))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    cap = TraceCapture(str(tmp_path), registry=MetricsRegistry())
    with cap.region(str(tmp_path / "p")):
        pass
    assert cap.request(steps=1) is not None
    cap.tick()
    cap.tick()
    cap.close()
    region, bounded = started
    assert region.python_tracer_level == 0
    assert bounded is None


def test_instrumentation_overhead_with_the_feeds_call_sites():
    """``TestInstrumentationOverhead``'s bound (two percent of a small
    step) with everything a step now passes through with no capture: the
    two accounts, the trace tick, the feed's two spans, its four counters."""
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return (x @ x @ x).sum()

    x = jnp.ones((256, 256))
    float(step(x))
    t0 = time.perf_counter()
    for _ in range(30):
        float(step(x))
    step_s = (time.perf_counter() - t0) / 30

    acct = GoodputAccountant(registry=MetricsRegistry())
    trig = TraceCapture("/tmp/unused-trace")
    reps = 2000
    t0 = time.perf_counter()
    for i in range(reps):
        with acct.account("input_wait"):
            pass
        trig.tick(1)
        with acct.account("step"):
            pass
        for name in (scopes.INPUT_BATCH, scopes.INPUT_PLACE):
            with trace_lib.annotation(name, batch=i):
                pass
        feed.COUNTS.batch += 1
        feed.COUNTS.batch_ready += True
        feed.COUNTS.fetch += 1
        feed.COUNTS.fetch_ready += True
    per_step = (time.perf_counter() - t0) / reps
    assert per_step <= 0.02 * step_s, (
        f"instrumentation {per_step * 1e6:.1f}us/step vs step "
        f"{step_s * 1e6:.1f}us")
