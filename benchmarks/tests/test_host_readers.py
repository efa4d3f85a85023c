"""The host-side readers (PR 39) on a small recorded host-and-device trace
kept as JSON: a whole epoch's host plane kept per thread, device idle named
by the loop thread's spans only; and the metric files that name them.  The
``Trainer.fit`` cell these metrics are for is not in the manifest yet
(PERF.md section 7): until a kind builds their reading from a chip's trace,
these tests are what holds the readers.
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import inspect
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (HERE, BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the per-layer metrics of the epoch cell, by layer (PERF.md section 3)
HOST_METRICS = {
    "input": {"input_wait_ms_per_step", "input_idle_ms_per_step",
              "input_batch_host_ms", "input_place_ms", "input_ready_share",
              "input_batch_ready_share"},
    "eval": {"eval_ms_per_epoch", "eval_device_idle_share",
             "eval_pasteback_ms_per_image"},
    "checkpoint": {"checkpoint_stall_ms_per_save",
                   "checkpoint_idle_ms_per_save"},
    "loop": {"idle_unnamed_share", "window_compiles"},
}


# ------------------------------------------------------------ the readers
@pytest.fixture(scope="module")
def reading():
    import hosttrace

    with open(os.path.join(HERE, "small_host_trace.json")) as f:
        raw = json.load(f)
    summary = hosttrace.summarize(raw, "train")
    return {"summary": summary, "step_name": "train", "val_images": 4,
            "images_per_step": 8, "chips": 1,
            "breakdown": hosttrace.breakdown(summary),
            "counters": {"input_ready_share": 75.0,
                         "input_batch_ready_share": 12.5,
                         "window_compiles": 0}}


def _read(reader, reading, **args):
    import harness

    ctx = types.SimpleNamespace(bench_dir=BENCH)
    return harness.load_module(BENCH, "readers", reader).read(
        ctx, reading, **args)


def test_summary_spans_the_whole_epoch_on_the_loops_thread(reading):
    s = reading["summary"]
    assert s["steps"] == 2
    # from the loop's first span to its last one's end: the wait for the
    # first batch and the wait for the save are the epoch's
    assert s["span_s"] == pytest.approx(3550e-9)
    assert s["busy_s"] == pytest.approx(2150e-9)
    assert {h[3] for h in s["loop"]} == {0}
    assert _read("idle_share", reading) == pytest.approx(100 * 1400 / 3550)


def test_span_ms_reader(reading):
    ms = 1e-6
    assert _read("span_ms", reading, span="goodput/input_wait") == \
        pytest.approx((200 + 450) * ms / 2)
    assert _read("span_ms", reading, span="goodput/eval", per="epoch") == \
        pytest.approx(930 * ms)
    assert _read("span_ms", reading, span="eval/pasteback", per="image") == \
        pytest.approx((30 + 180) * ms / 4)
    # a bucket inside one of its own name counts once; one save
    assert _read("span_ms", reading, span="goodput/checkpoint",
                 per="save") == pytest.approx(630 * ms)
    # the feed's spans live on other threads: none on the loop's
    assert _read("span_ms", reading, span="input/batch") is None
    assert _read("span_ms", reading, span="input/batch", thread="any",
                 per="span") == pytest.approx((250 + 600 + 45) * ms / 3)
    # the train loader's batches alone: the third began after the last step
    assert _read("span_ms", reading, span="input/batch", thread="any",
                 per="span", phase="train") == pytest.approx(425 * ms)
    assert _read("span_ms", reading, span="input/place", thread="any",
                 per="span") == pytest.approx(37.5 * ms)
    assert _read("span_ms", reading, span="serve/batch") is None


def test_idle_under_reader_names_gaps_by_the_loops_spans_only(reading):
    ms = 1e-6
    assert _read("idle_under", reading, span="goodput/input_wait") == \
        pytest.approx((300 + 400) * ms / 2)
    assert _read("idle_under", reading, span="goodput/eval",
                 per="span_time") == pytest.approx(100 * 300 / 930)
    assert _read("idle_under", reading, span="goodput/checkpoint",
                 per="save") == pytest.approx(300 * ms)
    # 100 of 1,400 idle ns lie under no span of the loop, though a worker's
    # input/batch runs across them
    assert _read("idle_under", reading, span=None, per="idle") == \
        pytest.approx(100 * 100 / 1400)
    assert _read("idle_under", reading, span="serve/batch") is None
    b = reading["breakdown"]
    assert b["idle_gaps"][0] == ["goodput/input_wait", pytest.approx(400e-9)]
    assert b["idle_by_span"] == {
        "goodput/input_wait": pytest.approx(700e-9),
        "checkpoint/wait": pytest.approx(300e-9),
        "eval/pasteback": pytest.approx(200e-9),
        "goodput/eval": pytest.approx(100e-9),
        "no program span": pytest.approx(100e-9)}
    assert not any(name.startswith("input/") for name, _ in b["idle_gaps"])


def test_readers_find_nothing_in_a_step_cells_reading():
    """The step kinds' summary keeps no thread: the new readers leave their
    metric out and do not raise (as they do over a program with no spans)."""
    step_reading = {"summary": {"steps": 8, "span_s": 1.0, "busy_s": 0.9,
                                "host": [], "devices": []}}
    assert _read("span_ms", step_reading, span="goodput/eval") is None
    assert _read("idle_under", step_reading, span=None, per="idle") is None
    assert _read("counter", step_reading, name="window_compiles") is None


def test_counter_reader_reads_the_windows_counters(reading):
    assert _read("counter", reading, name="input_ready_share") == 75.0
    assert _read("counter", reading, name="input_batch_ready_share") == 12.5
    assert _read("counter", reading, name="window_compiles") == 0


# ------------------------------------------------------ the metrics' files
def test_each_metric_file_names_a_reader_and_arguments_it_takes(reading):
    import harness

    for name in sorted(set().union(*HOST_METRICS.values())):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        reader = harness.load_module(BENCH, "readers", spec["reader"]).read
        inspect.signature(reader).bind(None, reading, **spec["args"])
        value = reader(types.SimpleNamespace(bench_dir=BENCH), reading,
                       **spec["args"])
        assert value is not None and value >= 0, name


def test_the_benchmarks_list_of_span_names_is_the_programs():
    from distributedpytorch_tpu.telemetry import scopes

    with open(os.path.join(BENCH, "host_spans.json")) as f:
        spans = json.load(f)
    assert spans["step"] == scopes.STEP_ANNOTATION
    assert tuple(spans["prefixes"]) == scopes.PROGRAM_SPAN_PREFIXES
