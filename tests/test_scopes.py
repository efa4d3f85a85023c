"""telemetry/scopes.py: the join of a traced device op and the part of the
step it belongs to; the scopes the step adds; the capture path that writes
the answer beside every trace; one clock for host and device."""

import contextlib
import functools
import json
import os
import re
import signal

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

from distributedpytorch_tpu.parallel import plan as plan_lib
from distributedpytorch_tpu.parallel.step import TrainState
from distributedpytorch_tpu.telemetry import events as events_lib
from distributedpytorch_tpu.telemetry import goodput, lowering, scopes
from distributedpytorch_tpu.telemetry import trace as trace_lib
from distributedpytorch_tpu.telemetry.registry import MetricsRegistry


def time_limit(seconds: int):
    """A limit of its own for each test (no pytest-timeout here): SIGALRM
    in the worker's main thread."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*a, **kw):
            def late(signum, frame):
                raise TimeoutError(f"{fn.__name__} passed {seconds} s")
            prev = signal.signal(signal.SIGALRM, late)
            signal.alarm(seconds)
            try:
                return fn(*a, **kw)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, prev)
        return run
    return wrap


# ------------------------------------------------------------------ the toy
class _Backbone(nn.Module):
    axis: str | None = None

    @nn.compact
    def __call__(self, x, train):
        x = nn.Conv(8, (3, 3), name="conv1")(x)
        x = nn.BatchNorm(use_running_average=not train, axis_name=self.axis,
                         name="bn1")(x)
        return nn.relu(x)


class _Head(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Conv(1, (1, 1), name="cls")(x)


class Toy(nn.Module):
    bn_cross_replica_axis: str | None = None

    @nn.compact
    def __call__(self, x, train=False):
        x = _Backbone(self.bn_cross_replica_axis, name="backbone")(x, train)
        return (_Head(name="head")(x),)


def _toy_step(reduce_buckets: int):
    """``(jitted step, state, batch, mesh)`` of the toy on the 8-device
    CPU mesh, made through the planner as the trainer makes it."""
    model = Toy("data" if reduce_buckets else None)
    plan = plan_lib.resolve_plan("dp", n_devices=8)
    mesh = plan.make_mesh(jax.devices())
    tx = optax.sgd(1e-2, momentum=0.9)
    x = jnp.ones((8, 16, 16, 4))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    state = TrainState(step=jnp.zeros((), jnp.int32),
                       params=variables["params"],
                       batch_stats=variables["batch_stats"],
                       opt_state=tx.init(variables["params"]),
                       rng=jax.random.PRNGKey(1))
    step = plan.make_train_step(model, tx, mesh=mesh, state=state,
                                loss_type="multi_sigmoid", donate=False,
                                reduce_buckets=reduce_buckets)
    batch = {"concat": x, "crop_gt": jnp.ones((8, 16, 16, 1))}
    return step, state, batch, mesh


@pytest.fixture(scope="module")
def toy_gspmd():
    step, state, batch, mesh = _toy_step(0)
    with mesh:
        text = lowering.lower_cached(step, state, batch).compiled.as_text()
    return step, state, batch, mesh, text


@pytest.fixture(scope="module")
def toy_bucketed():
    step, state, batch, mesh = _toy_step(2)
    with mesh:
        text = lowering.lower_cached(step, state, batch).compiled.as_text()
    return text


# ------------------------------------------------------------- the vocabulary
@pytest.mark.parametrize("op_name,want", [
    ("jit(step_fn)/jvp(DANet)/DANet._encode/backbone/layer3_5/conv2/"
     "conv_general_dilated", ("backbone", "backbone/layer3_5/conv2", "fwd")),
    ("jit(step_fn)/transpose(jvp(DANet))/DANet._decode/head/pam/pam_bwd/"
     "transpose(jvp())/while/body/closed_call/bnc,bmc->bnm/dot_general",
     ("head", "head/pam/pam_bwd", "bwd")),
    ("jit(step_fn)/transpose(jvp(DeepLabV3))/pam_bwd/mul",
     ("head", "pam_bwd", "bwd")),
    ("jit(step_fn)/jvp(DANet)/DANet._decode/head/cam/cam_energy/pallas_call",
     ("head", "head/cam/cam_energy", "fwd")),
    ("jit(step_fn)/shard_map/jvp(loss)/jit(_where)/select_n",
     ("loss", "loss", "fwd")),
    ("jit(step_fn)/shard_map/transpose(jvp(loss))/mul",
     ("loss", "loss", "bwd")),
    ("jit(step_fn)/shard_map/grad_reduce/b1/psum",
     ("grad_reduce", "grad_reduce/b1", "fwd")),
    ("jit(step_fn)/optimizer/add", ("optimizer", "optimizer", "opt")),
    ("jit(step_fn)/jvp(DANet)/DANet._decode/jit(_resize)/dot_general",
     ("model", "", "fwd")),
    ("jit(step_fn)/jit(_threefry_split)/slice", ("other", "", "fwd")),
    ("state.params['backbone']['conv1']['kernel']", ("other", "", "fwd")),
    (None, ("other", "", "fwd")),
])
def test_scope_of_op_name(op_name, want):
    s = scopes.scope_of(op_name)
    assert (s.layer, s.path, s.phase) == want


def test_joined_op_names_of_two_layers_are_mixed():
    s = scopes.scope_of("jit(f)/jvp(M)/backbone/conv/mul;jit(f)/optimizer/add")
    assert (s.layer, s.mixed) == ("backbone", True)


# --------------------------------------------------------- hand-written HLO
_HLO = """HloModule jit_step_fn, is_scheduled=true

%fused_wgrad (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %conv = f32[8]{0} convolution(%p0, %p1), metadata={op_name="jit(step_fn)/transpose(jvp(Net))/backbone/conv1/conv_general_dilated"}
  ROOT %upd = f32[8]{0} add(%conv, %p1), metadata={op_name="jit(step_fn)/optimizer/add"}
}

%fused_plain (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %neg = f32[8]{0} negate(%p0.1), metadata={op_name="jit(step_fn)/jvp(Net)/head/cls/neg"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8]{0} get-tuple-element(%arg), index=1
  %dot.7 = f32[8]{0} dot(%x, %x), metadata={op_name="jit(step_fn)/transpose(jvp(Net))/head/pam/pam_bwd/while/body/dot_general"}
  ROOT %out = (s32[], f32[8]{0}) tuple(%i, %dot.7)
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[8], b: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="state.params['backbone']['conv1']['kernel']"}
  %b = f32[8]{0} parameter(1)
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %multiply_add_fusion = f32[8]{0} fusion(%copy-done.1, %b), kind=kOutput, calls=%fused_wgrad, metadata={op_name="jit(step_fn)/optimizer/add"}
  %fusion.2 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fused_plain
  %init = (s32[], f32[8]{0}) tuple(%b, %fusion.2)
  %while.1 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body
  %all-reduce.3 = f32[8]{0} all-reduce(%multiply_add_fusion), replica_groups={}, to_apply=%cond, metadata={op_name="jit(step_fn)/shard_map/grad_reduce/b0/psum"}
  ROOT %r = f32[8]{0} add(%all-reduce.3, %fusion.2)
}
"""


def test_fusion_over_two_layers_goes_to_the_conv_and_is_mixed():
    t = scopes.scope_table(_HLO)
    s = t["multiply_add_fusion"]
    assert (s.layer, s.path, s.phase, s.mixed, s.opcode) == \
        ("backbone", "backbone/conv1", "bwd", True, "fusion")
    plain = t["fusion.2"]  # no metadata of its own: its insides name it
    assert (plain.layer, plain.phase, plain.mixed) == ("head", "fwd", False)
    # the insides of a fusion are no traced events
    assert "conv" not in t and "neg" not in t


def test_while_resolves_through_its_body_and_movers_through_their_user():
    t = scopes.scope_table(_HLO)
    assert (t["while.1"].layer, t["while.1"].path, t["while.1"].phase) == \
        ("head", "head/pam/pam_bwd", "bwd")
    assert t["dot.7"].path == "head/pam/pam_bwd"  # the nested event itself
    # the compiler's prefetch carries no scope: it is its user's work
    assert t["copy-done.1"].layer == "backbone"
    assert t["copy-start.1"].layer == "backbone"
    assert t["b"].layer == "other" and t["a"].layer == "other"


def test_attribute_counts_self_time_once_and_keeps_unknown_names_apart():
    t = scopes.scope_table(_HLO)
    ops = [
        ["%while.1 = (s32[], f32[8]{0}) while(%init)", 0, 1000],
        ["%dot.7 = f32[8]{0} dot(%x, %x)", 100, 400],     # nested in the loop
        ["%dot.7 = f32[8]{0} dot(%x, %x)", 500, 800],
        ["%multiply_add_fusion fusion", 1000, 3000],     # the short form
        ["%all-reduce.3 all-reduce", 3000, 3500],
        ["%fusion.999 fusion", 3500, 3600],               # not in the table
    ]
    a = scopes.attribute(ops, t)
    assert a["busy_s"] == pytest.approx(3600e-9)
    assert a["by_layer"]["head"] == pytest.approx(1000e-9)   # not 1600
    assert a["by_path"]["head/pam/pam_bwd"] == pytest.approx(1000e-9)
    assert a["by_layer_phase"]["backbone.bwd"] == pytest.approx(2000e-9)
    assert a["mixed_s"] == pytest.approx(2000e-9)
    assert a["by_layer"]["grad_reduce"] == pytest.approx(500e-9)
    assert a["collective_by_layer"] == {"grad_reduce": pytest.approx(500e-9)}
    assert a["unresolved_s"] == pytest.approx(100e-9)


# ------------------------------------------------------ the toy's real steps
@time_limit(120)
def test_every_instruction_of_the_compiled_step_resolves(toy_gspmd):
    *_, text = toy_gspmd
    table = scopes.scope_table(text)
    entry = text[text.index("\nENTRY "):]
    names = re.findall(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ", entry, re.M)
    assert len(names) > 20
    assert [n for n in names if n not in table] == []
    layers = {s.layer for s in table.values()}
    assert {"backbone", "head", "loss", "optimizer"} <= layers
    phases = {(s.layer, s.phase) for s in table.values()}
    assert {("backbone", "fwd"), ("backbone", "bwd"), ("loss", "bwd"),
            ("optimizer", "opt")} <= phases
    for s in table.values():  # forward and backward split by transpose(
        if s.phase == "bwd":
            assert s.layer not in ("optimizer", "other")


@time_limit(120)
def test_bucketed_psums_sit_under_grad_reduce_buckets(toy_bucketed):
    paths = scopes.scope_paths(toy_bucketed, depth=2)
    assert {"grad_reduce", "grad_reduce/b0", "grad_reduce/b1"} <= paths
    table = scopes.scope_table(toy_bucketed)
    reduces = [s for s in table.values()
               if s.layer == "grad_reduce" and scopes.is_collective(s.opcode)]
    assert reduces, "no collective under grad_reduce"
    assert {s.path for s in reduces} <= {
        "grad_reduce", "grad_reduce/b0", "grad_reduce/b1"}


@time_limit(120)
def test_kernel_reverse_passes_sit_under_their_scopes():
    from distributedpytorch_tpu.ops import pallas_attention as pa

    def loss(q, k, v):
        with jax.named_scope("Net"), jax.named_scope("head"):
            return (pa.flash_position_attention(
                q, k, v, 8, 8, None, True).sum()
                + pa.flash_channel_attention(v, 8, True).sum())

    q = jnp.ones((1, 16, 4))
    v = jnp.ones((1, 16, 8))
    text = jax.jit(jax.grad(loss, argnums=(0, 2))).lower(
        q, q, v).compile().as_text()
    paths = scopes.scope_paths(text, depth=3)
    assert {"head/pam_bwd", "head/cam_bwd"} <= paths
    bwd = [s for s in scopes.scope_table(text).values()
           if s.path.endswith(("pam_bwd", "cam_bwd"))]
    assert bwd and all(s.layer == "head" for s in bwd)
    # the PAM reverse pass is Mosaic calls of its own, named so that the
    # trace's %pam_bwd_... is told from the forward's %pam
    lowered = jax.jit(jax.grad(loss, argnums=(0, 2))).lower(q, q, v).as_text(
        dialect="hlo", debug_info=True)
    calls = {p.rsplit("/", 1)[-1] for p in scopes.scope_paths(lowered, 3)
             if p.startswith("head/pam_bwd/")}
    assert calls and all(c.startswith(scopes.PAM_BWD) for c in calls)
    assert calls <= {scopes.PAM_BWD_FUSED, scopes.PAM_BWD_DKV,
                     scopes.PAM_BWD_DQ}
    # the calls carry their names (the trace's %pam, %cam_energy, %cam_apply)
    fwd = scopes.scope_paths(jax.jit(loss).lower(q, q, v).as_text(
        dialect="hlo", debug_info=True), depth=3)
    assert {"head/pam", "head/cam_energy", "head/cam_apply"} <= fwd


@time_limit(120)
def test_scopes_change_no_program(toy_gspmd, monkeypatch):
    """Metadata only: the step lowers to the same StableHLO with every
    ``named_scope`` of the step taken out."""
    step, state, batch, mesh, _ = toy_gspmd
    with mesh:
        with_scopes = step.lower(state, batch).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_step, *_ = _toy_step(0)
    with mesh:
        without = bare_step.lower(state, batch).as_text()
    assert with_scopes == without


def test_a_reduce_over_one_device_is_deleted_not_stale(toy_bucketed):
    """On one chip the bucketed step's ``psum``s reduce nothing and XLA
    deletes them: the lowering names ``grad_reduce/b<k>``, the executable
    does not, and that is no other tree's executable (chip_smoke on one
    chip, PR 26).  A scope the compiler cannot delete still counts."""
    gone = re.sub(r'op_name="[^"]*grad_reduce[^"]*"', 'op_name=""',
                  toy_bucketed)
    assert "grad_reduce" not in scopes.scope_paths(gone)
    assert scopes.differing_paths(gone, toy_bucketed) == ()
    no_loss = re.sub(r"jvp\(loss\)", "jvp()", toy_bucketed)
    assert scopes.differing_paths(no_loss, toy_bucketed) == ("loss",)
    assert scopes.differing_paths(toy_bucketed, no_loss) == ("loss",)


# ------------------------------------------------------- the stale-cache trap
@time_limit(120)
def test_table_from_a_cache_entry_older_than_the_scopes_is_stale(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    def body(x):
        return jnp.tanh(x @ x).sum()

    def old(x):          # the tree that compiled first: no scope
        return body(x)

    def new(x):          # this tree: same program, one scope more
        with jax.named_scope("loss"):
            return body(x)

    x = jnp.ones((16, 16))
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()
    try:
        # both lower to one HLO module name, so one cache key
        old.__name__ = new.__name__ = "step_fn"
        jax.jit(old).lower(x).compile()
        assert os.listdir(tmp_path), "nothing was cached"
        jitted = jax.jit(new)
        stale = scopes.table_for(jitted, x)
        assert stale.stale and "loss" in stale.differing
        assert "loss" not in {s.layer for s in stale.table.values()}
        fresh = scopes.table_for(jitted, x, allow_recompile=True)
        assert fresh.recompiled and not fresh.stale and not fresh.differing
        assert "loss" in {s.layer for s in fresh.table.values()}
        # metadata steers no pass: the instruction names are the same
        assert set(fresh.table) == set(stale.table)
        assert fresh.module == stale.module == "jit_step_fn"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        cc.reset_cache()


@time_limit(120)
def test_table_for_shares_the_lowering_cache(toy_gspmd):
    step, state, batch, mesh, _ = toy_gspmd
    before = lowering.cache_info()["entries"]
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (state, batch))
    with mesh:
        t = scopes.table_for(step, *abstract)
    assert lowering.cache_info()["entries"] == before  # no second lowering
    assert not t.stale and t.module == "jit_step_fn"
    assert scopes.ScopeTable.from_json(
        json.loads(json.dumps(t.to_json()))).table == t.table


# --------------------------------------------------- one clock, and only then
class _CountingAnnotation:
    calls = []

    def __init__(self, name, **kw):
        type(self).calls.append((name, kw))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture()
def fake_profiler(monkeypatch):
    """``start_trace`` / ``stop_trace`` that record nothing, annotations
    that count: no test here starts the real profiler."""
    _CountingAnnotation.calls = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                        _CountingAnnotation)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    return _CountingAnnotation.calls


def test_account_annotates_only_while_a_capture_is_active(
        fake_profiler, tmp_path):
    acct = goodput.GoodputAccountant(registry=MetricsRegistry())
    with acct.account("input_wait"):
        pass
    assert fake_profiler == [] and not trace_lib.capturing()
    cap = trace_lib.TraceCapture(str(tmp_path), registry=MetricsRegistry())
    with cap.region(str(tmp_path / "profile")):
        assert trace_lib.capturing() and cap.active
        with acct.account("input_wait"):
            pass
        cap.tick(1)  # a region is closed by its ``with``, not by ticks
        assert cap.active
    assert [n for n, _ in fake_profiler] == ["goodput/input_wait"]
    assert not trace_lib.capturing() and not cap.active
    with acct.account("step"):
        pass
    assert len(fake_profiler) == 1
    assert acct.report(publish=False)["counts"]["input_wait"] == 2


_RAW = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit_step_fn(1)", 0, 2000], ["jit_step_fn(1)", 2100, 4100]],
        "ops": [["%multiply_add_fusion = f32[8]{0} fusion(...)", 0, 1500],
                ["%all-reduce.3 = f32[8]{0} all-reduce(...)", 1500, 2000],
                ["%multiply_add_fusion = f32[8]{0} fusion(...)", 2100, 3600],
                ["%fusion.2 = f32[8]{0} fusion(...)", 3600, 4100]]}},
    # [name, start, end, thread]: the loop's thread holds the step; a feed
    # worker's span across the gap's middle must not name it
    "host": [["train", 0, 1900, 0], ["goodput/input_wait", 1990, 2110, 0],
             ["input/batch", 2040, 2060, 1], ["outer", 0, 5000, 0]],
}


@time_limit(120)
def test_capture_leaves_table_summary_and_event(
        fake_profiler, tmp_path, monkeypatch):
    reg = MetricsRegistry()
    cap = trace_lib.TraceCapture(str(tmp_path / "t"), default_steps=2,
                                 registry=reg)
    monkeypatch.setattr(scopes, "read_device_events", lambda d: _RAW)
    monkeypatch.setattr(
        scopes, "table_for", lambda fn, *a, allow_recompile: scopes.ScopeTable(
            scopes.scope_table(_HLO), module="jit_step_fn"))
    log = events_lib.configure(str(tmp_path / "run"))
    try:
        target = cap.request()
        cap.note_program(lambda s: s, (jnp.ones((9,)),))  # not active yet
        cap.tick(1)
        cap.note_program(lambda s: s, (jnp.ones((2, 2)),))
        cap.note_program(lambda s: s, (jnp.ones((3,)),))  # the first stays
        assert cap._program[1][0].shape == (2, 2)
        cap.tick(1)
        cap.tick(1)  # two steps done: this tick stops the capture
        assert not cap.active
    finally:
        events_lib.release(log)
    table = json.load(open(os.path.join(target, "scope_table.json")))
    assert table["module"] == "jit_step_fn" and not table["stale"]
    assert table["instructions"]["while.1"][:3] == \
        ["head", "head/pam/pam_bwd", "bwd"]
    summary = json.load(open(os.path.join(target, "scope_summary.json")))
    assert summary["steps"] == 2 and summary["devices"] == 1
    assert summary["ms_per_step_by_layer"]["backbone"] == \
        pytest.approx(1500e-6)
    assert summary["ms_per_step_by_layer_phase"]["head.fwd"] == \
        pytest.approx(250e-6)
    assert summary["ms_per_step_collectives_by_layer"] == {
        "grad_reduce": pytest.approx(250e-6)}
    assert summary["mixed_share"] == pytest.approx(3000 / 4000)
    assert summary["unresolved_share"] == 0
    assert summary["idle_gaps"] == [["goodput/input_wait", 100e-9]]
    assert summary["idle_by_span_s"] == {"goodput/input_wait": 100e-9}
    assert summary["host_spans"] == {"goodput/input_wait": 1, "train": 1,
                                     "input/batch": 1}  # not "outer"
    recorded = [e for e in events_lib.read_events_file(log.path)
                if e["kind"] == "trace_summary"]
    assert len(recorded) == 1
    assert recorded[0]["payload"]["ms_per_step_by_layer"]["backbone"] > 0
    assert reg.counter("trace_captures_total", "").value == 1


def test_a_failing_summary_is_counted_and_never_raised(
        fake_profiler, tmp_path, monkeypatch):
    reg = MetricsRegistry()
    cap = trace_lib.TraceCapture(str(tmp_path), registry=reg)

    def broken(d):
        raise FileNotFoundError("no .xplane.pb")
    monkeypatch.setattr(scopes, "read_device_events", broken)
    with cap.region(str(tmp_path / "p")):
        cap.note_program(jax.jit(lambda x: x + 1), (jnp.ones(2),))
    assert reg.counter("trace_capture_failures_total", "").value == 1
    assert not cap.active and not trace_lib.capturing()
    # and with no program named (the serve front) there is only the trace
    with cap.region(str(tmp_path / "q")):
        pass
    assert reg.counter("trace_capture_failures_total", "").value == 1
    assert not os.path.exists(tmp_path / "q" / "scope_summary.json")


def test_doctor_shows_the_trace_summary(tmp_path):
    from distributedpytorch_tpu.telemetry import doctor

    log = events_lib.configure(str(tmp_path))
    try:
        events_lib.emit("trainer", "fit_start", step=0, epoch=0, payload={})
        events_lib.emit("telemetry", "trace_summary", payload={
            "trace_dir": "/r/trace_000", "steps": 4, "stale": False,
            "ms_per_step_by_layer": {"backbone": 80.5, "head": 30.25}})
        events_lib.emit("trainer", "fit_end", step=4, epoch=0,
                        payload={"completed": True})
    finally:
        events_lib.release(log)
    report = doctor.diagnose(str(tmp_path))
    assert report["traces"][0]["trace_dir"] == "/r/trace_000"
    assert "backbone 80.50, head 30.25" in doctor.render(report)


@pytest.mark.slow  # a real XPlane capture start/stop is ~30 s on the CPU mesh
def test_region_writes_trace_files(tmp_path):
    cap = trace_lib.TraceCapture(str(tmp_path))
    d = str(tmp_path / "prof")
    with cap.region(d):
        jnp.ones((8, 8)).sum().block_until_ready()
    assert os.path.isdir(d) and len(os.listdir(d)) > 0
