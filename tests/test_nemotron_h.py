"""``nemotron_h`` (models/nemotron_h.py) against its plain reference
(benchmarks/reference/nemotron_h.py), and the ``tokens`` task through the
step and the trainer.  Tiny widths, seeded weights, float32, on the CPU.

Tolerances, with their reasons: program and reference both compute in
float32 here, but in different forms — the chunked scan against the
recurrence (sums of products of decays against a running product), the
grouped product against a masked loop, blocked attention against a whole
one — so outputs agree to rounding of float32 sums in another order: 2e-5
of the largest value for outputs, 1e-4 of a leaf's largest gradient for
gradients (a gradient passes the same re-ordered sums twice).  A wrong
term (a decay off by one step, a missing head) moves them by 1e-2 or more.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from reference import nemotron_h as ref  # noqa: E402

import expert_remat_cases as kept_cases  # noqa: E402
from distributedpytorch_tpu.models import build_model  # noqa: E402
from distributedpytorch_tpu.models import nemotron_h as nh  # noqa: E402
from distributedpytorch_tpu.parallel import (  # noqa: E402
    NEXT_TOKEN,
    create_train_state,
    make_eval_step,
    make_train_step,
)
from distributedpytorch_tpu.telemetry import scopes  # noqa: E402

OUT_RTOL, GRAD_RTOL = 2e-5, 1e-4


def tiny(**over):
    cfg = dict(nh.PRESETS["tiny"], **over)
    return cfg


def one_block(kind, **over):
    return tiny(hybrid_override_pattern=kind, num_nextn_predict_layers=0,
                **over)


def rel_gap(got, want):
    return float(jnp.abs(got - want).max()) / (float(jnp.abs(want).max())
                                               + 1e-12)


def assert_trees_close(got, want, rtol):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert rel_gap(a, b) <= rtol, (jax.tree_util.keystr(path),
                                       rel_gap(a, b))


def _block_apply(kind, cfg, params, u):
    cls = {"M": nh.MambaMixer, "*": nh.Attention, "E": nh.LatentMoE}[kind]
    return cls(nh.LMConfig.from_dict(cfg), jnp.float32).apply(
        {"params": params}, u, mutable=["counters"])[0]


def _block_ref(kind, cfg, params, u):
    fn = {"M": ref.mamba_mixer, "*": ref.attention, "E": ref.latent_moe}[kind]
    return u + fn(params, ref.rms_norm(u, params["norm"], cfg["norm_eps"]),
                  cfg)


# ------------------------------------------------------------- the mixers
@pytest.mark.parametrize("kind,length", [("M", 21), ("M", 16), ("*", 21),
                                         ("E", 21)])
def test_mixer_forward_and_gradients_equal_the_reference(kind, length):
    """21 is no multiple of the chunk (8): the chunked scan pads; 16 is."""
    cfg = one_block(kind)
    params = ref.make_weights(jax.random.PRNGKey(0), cfg)["l00"]
    u = jax.random.normal(jax.random.PRNGKey(1),
                          (2, length, cfg["hidden_size"]))
    assert rel_gap(_block_apply(kind, cfg, params, u),
                   _block_ref(kind, cfg, params, u)) <= OUT_RTOL

    def loss(fn):
        return lambda p, v: jnp.sum(jnp.square(fn(kind, cfg, p, v)))

    got = jax.grad(loss(_block_apply), argnums=(0, 1))(params, u)
    want = jax.grad(loss(_block_ref), argnums=(0, 1))(params, u)
    assert_trees_close(got, want, GRAD_RTOL)


def test_chunked_scan_equals_the_recurrence_step_by_step():
    """``ssd_chunked`` alone against the recurrence written as a Python
    loop, at a length (13) that is no chunk (4) multiple, two groups."""
    b, length, h, p, g, n = 2, 13, 4, 3, 2, 5
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (b, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, length, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, length, g, n))
    cm = jax.random.normal(ks[4], (b, length, g, n))
    got = nh.ssd_chunked(x, dt, a, bm, cm, 4)
    state = np.zeros((b, h, p, n))
    want = np.zeros((b, length, h, p))
    bh = np.repeat(np.asarray(bm), h // g, axis=2)
    ch = np.repeat(np.asarray(cm), h // g, axis=2)
    for t in range(length):
        decay = np.exp(np.asarray(dt[:, t]) * np.asarray(a))
        state = decay[..., None, None] * state + (
            np.asarray(dt[:, t])[..., None] * np.asarray(x[:, t])
        )[..., None] * bh[:, t][:, :, None, :]
        want[:, t] = np.einsum("bhpn,bhn->bhp", state, ch[:, t])
    assert rel_gap(got, jnp.asarray(want, jnp.float32)) <= OUT_RTOL


# -------------------------------------------------------- the head shares
def test_mamba_head_shares_add_up_to_the_uncut_mixer():
    """Four heads in two groups, cut into two shares of two heads and one
    group each (the tensor-parallel cut the benchmark's configuration
    states): in_proj's columns, the convolution's channels and the per-head
    parameters are sliced, out_proj's rows are; the shares' outputs add up
    to the uncut mixer's."""
    cfg = one_block("M")
    s = ref.dims(cfg)
    full = ref.make_weights(jax.random.PRNGKey(3), cfg)["l00"]
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 19, s["d"]))
    want = _block_ref("M", cfg, full, u) - u
    inner, gn, hp = s["inner"], s["mn"], s["mp"]
    got = jnp.zeros_like(u)
    for share in range(2):
        heads = np.arange(2 * share, 2 * share + 2)
        chan = (heads[:, None] * hp + np.arange(hp)).ravel()   # of inner
        grp = share * gn + np.arange(gn)                        # of g * n
        xbc = np.concatenate([chan, inner + grp, inner + 2 * gn + grp])
        cols = np.concatenate([chan, inner + xbc,
                               2 * inner + 4 * gn + heads])
        p = {"norm": full["norm"], "in_proj": full["in_proj"][:, cols],
             "conv_w": full["conv_w"][:, xbc], "conv_b": full["conv_b"][xbc],
             "dt_bias": full["dt_bias"][heads], "A_log": full["A_log"][heads],
             "D": full["D"][heads], "gnorm": full["gnorm"][chan],
             "out_proj": full["out_proj"][chan]}
        cut = dict(cfg, mamba_num_heads=2, n_groups=1)
        got = got + (_block_apply("M", cut, p, u) - u)
    assert rel_gap(got, want) <= OUT_RTOL


def test_attention_head_shares_add_up_to_the_uncut_mixer():
    """Four query heads on two key/value heads, cut into two shares of two
    query heads and one key/value head."""
    cfg = one_block("*")
    s = ref.dims(cfg)
    full = ref.make_weights(jax.random.PRNGKey(5), cfg)["l00"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 17, s["d"]))
    want = _block_ref("*", cfg, full, u) - u
    hd = s["hd"]
    got = jnp.zeros_like(u)
    for share in range(2):
        q = np.arange(2 * share * hd, (2 * share + 2) * hd)
        kv = np.arange(share * hd, (share + 1) * hd)
        p = {"norm": full["norm"], "q_proj": full["q_proj"][:, q],
             "k_proj": full["k_proj"][:, kv], "v_proj": full["v_proj"][:, kv],
             "o_proj": full["o_proj"][q]}
        cut = dict(cfg, num_attention_heads=2, num_key_value_heads=1)
        got = got + (_block_apply("*", cut, p, u) - u)
    assert rel_gap(got, want) <= OUT_RTOL


# --------------------------------------------------------- the whole model
@pytest.fixture(scope="module")
def share_cfg():
    cfg = tiny(n_routed_experts=4, expert_offset=4)
    cfg["published"] = {"n_routed_experts": 16}
    return cfg


@pytest.fixture(scope="module")
def whole(share_cfg):
    model = build_model("nemotron_h", lm_config=share_cfg, dtype="float32")
    params = ref.make_weights(jax.random.PRNGKey(7), share_cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 21), 0,
                                share_cfg["vocab_size"])
    return model, params, tokens


def test_parameter_tree_is_the_references(whole, share_cfg):
    model, params, tokens = whole

    def shapes(tree):
        return {jax.tree_util.keystr(p): v.shape for p, v in
                jax.tree_util.tree_leaves_with_path(tree)}

    init = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens,
                                             train=False))["params"]
    assert shapes(init) == shapes(params)


def test_model_logits_loss_and_gradients_equal_the_reference(whole,
                                                             share_cfg):
    from distributedpytorch_tpu.parallel.step import _loss_and_updates

    model, params, tokens = whole
    logits, mtp = model.apply({"params": params}, tokens, train=True,
                              mutable=["counters"])[0]
    want_logits, want_mtp = ref.forward(params, tokens, share_cfg)
    assert rel_gap(logits, want_logits) <= OUT_RTOL
    assert rel_gap(mtp, want_mtp) <= OUT_RTOL
    # without the prediction module when not training
    assert len(model.apply({"params": params}, tokens, train=False)) == 1

    def program_loss(p):
        return _loss_and_updates(
            model, p, {}, {"tokens": tokens}, jax.random.PRNGKey(0),
            (1.0, share_cfg["mtp_loss_weight"]), True, NEXT_TOKEN)[0]

    got, got_grads = jax.jit(jax.value_and_grad(program_loss))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, tokens, share_cfg)))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    # the loss holds the prediction term: more than the next-token part
    next_only = ref.shifted_xent(want_logits, tokens, 1)
    assert float(want) > float(next_only) + 0.1
    assert_trees_close(got_grads, want_grads, GRAD_RTOL)
    # the score-correction bias is a parameter the loss does not reach
    assert float(jnp.abs(got_grads["l01"]["router_bias"]).max()) == 0.0


@pytest.fixture()
def forced_kernels(monkeypatch, interpreted_kernels):
    """The causal flash kernels in the einsum form's place whatever the
    backend and the compute dtype (the rule, ``danet.auto_wants_flash``,
    wants a TPU and bfloat16), through the pallas interpreter."""
    from distributedpytorch_tpu.models import danet

    monkeypatch.setattr(danet, "auto_wants_flash", lambda dtype: True)


def test_flash_kernels_give_the_einsum_models_logits_loss_and_gradients(
        whole, share_cfg, monkeypatch, request):
    """The ``tiny`` model (trunk ``*`` and the prediction module's) with the
    kernels forced against itself in the einsum form, float32: the same
    sums tile by tile, within the file's tolerances."""
    from distributedpytorch_tpu.ops import pallas_attention
    from distributedpytorch_tpu.parallel.step import _loss_and_updates

    model, params, tokens = whole

    def logits_loss_grads():
        logits = model.apply({"params": params}, tokens, train=True,
                             mutable=["counters"])[0]

        def loss(p):
            return _loss_and_updates(
                model, p, {}, {"tokens": tokens}, jax.random.PRNGKey(0),
                (1.0, share_cfg["mtp_loss_weight"]), True, NEXT_TOKEN)[0]

        return logits, *jax.value_and_grad(loss)(params)

    want = logits_loss_grads()
    calls = []
    flash = pallas_attention.flash_causal_attention
    monkeypatch.setattr(
        pallas_attention, "flash_causal_attention",
        lambda *a, **kw: calls.append(a[0].shape) or flash(*a, **kw))
    request.getfixturevalue("forced_kernels")
    got = logits_loss_grads()
    # both attention layers, forward and under value_and_grad
    assert len(calls) >= 4 and set(calls) == {(2, 21, 4, 16)}
    for g, w in zip(got[0], want[0]):
        assert rel_gap(g, w) <= OUT_RTOL
    assert abs(float(got[1]) - float(want[1])) <= 1e-5 * float(want[1])
    assert_trees_close(got[2], want[2], GRAD_RTOL)


def test_reference_blocks_change_memory_not_arithmetic(share_cfg,
                                                       monkeypatch):
    """The reference's time, query and loss blocks (what makes 8,192
    positions fit on the chip) give the sums of the unblocked forms."""
    params = ref.make_weights(jax.random.PRNGKey(9), share_cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(10), (1, 32), 0,
                                share_cfg["vocab_size"])
    plain = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, tokens, share_cfg)))(params)
    monkeypatch.setattr(ref, "TIME_BLOCK", 4)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "LOSS_BLOCK", 16)
    blocked = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, tokens, share_cfg, None, True)))(params)
    assert abs(float(plain[0]) - float(blocked[0])) <= 1e-6 * float(plain[0])
    assert_trees_close(blocked[1], plain[1], GRAD_RTOL)


# ------------------------------------------------------------------ the step
@pytest.fixture(scope="module")
def token_step(whole, share_cfg):
    model, _, tokens = whole
    tx = optax.sgd(1e-2, momentum=0.9)
    state = create_train_state(jax.random.PRNGKey(11), model, tx,
                               tokens.shape, input_dtype=jnp.int32)
    step = make_train_step(
        model, tx, loss_type=NEXT_TOKEN, donate=False,
        loss_weights=(1.0, share_cfg["mtp_loss_weight"]))
    return model, tx, state, step, {"tokens": tokens}


def test_step_follows_the_reference_and_hands_back_counters(token_step,
                                                            share_cfg):
    model, tx, state, step, batch = token_step
    opt = {"learning_rate": 1e-2, "momentum": 0.9}
    new, (loss, counters) = step(state, batch)
    assert new.batch_stats == {}
    assert int(counters["moe_tokens_dropped"]) == 0
    assert float(counters["moe_expert_load_max_over_mean"]) >= 1.0
    # every expert layer's row buffer is one chunk here, and it holds tokens
    assert float(counters["moe_chunks_run_share"]) == 1.0
    zeros = jax.tree.map(jnp.zeros_like, state.params)

    def ref_step(**kw):
        return jax.jit(lambda p, t: ref.train_step(share_cfg, opt, p, t,
                                                   batch, **kw))(
            state.params, zeros)

    params, _, want = ref_step(remat=False)
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    assert_trees_close(new.params, params, 1e-5)
    # half the tokens, or no routed experts, is another step
    for fault in ({"rows": batch["tokens"].size // 2},
                  {"drop_routed": True}):
        _, _, other = ref_step(**fault)
        assert abs(float(other) - float(want)) > 1e-3 * float(want)


def test_accumulated_and_monitored_steps_keep_the_counters(token_step,
                                                           share_cfg):
    model, tx, state, _, batch = token_step
    step = make_train_step(
        model, tx, loss_type=NEXT_TOKEN, donate=False, accum_steps=2,
        sentinel_metrics=True,
        loss_weights=(1.0, share_cfg["mtp_loss_weight"]))
    _, (loss, aux, counters) = step(state, batch)
    assert aux.shape == (2,) and np.isfinite(float(loss))
    assert int(counters["moe_tokens_dropped"]) == 0


@pytest.mark.parametrize("sows", [True, False])
def test_any_model_that_sows_counters_gets_them_back(sows):
    """The step shapes its output on what the model sowed, not on the loss
    type: an image model with a declared counter gets ``(loss, counters)``,
    the same model without it the bare loss."""
    from flax import linen as nn

    from distributedpytorch_tpu.telemetry import counters

    name = counters.declare("test_pixels_seen", "sum")

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            if sows:
                for _ in range(2):      # two "layers": combined by sum
                    self.sow(counters.COLLECTION, name,
                             jnp.float32(x[..., 0].size))
            return (nn.Conv(1, (1, 1))(x),)

    model, tx = Net(), optax.sgd(1e-2)
    state = create_train_state(jax.random.PRNGKey(0), model, tx,
                               (1, 8, 8, 4))
    batch = {"concat": jnp.ones((2, 8, 8, 4)), "crop_gt": jnp.ones((2, 8, 8))}
    _, out = make_train_step(model, tx, donate=False)(state, batch)
    if sows:
        loss, got = out
        assert float(got[name]) == 2 * 2 * 8 * 8
    else:
        loss = out
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="already declared"):
        counters.declare(name, "max")


def test_eval_step_is_the_mean_next_token_loss(token_step, share_cfg):
    model, _, state, _, batch = token_step
    outputs, loss = make_eval_step(model, loss_type=NEXT_TOKEN)(state, batch)
    assert outputs == ()
    logits, _ = ref.forward(state.params, batch["tokens"], share_cfg)
    want = ref.shifted_xent(logits, batch["tokens"], 1)
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)


def test_reduce_buckets_is_refused_by_name(token_step):
    from distributedpytorch_tpu.parallel.plan import PlanError

    model, tx, *_ = token_step
    with pytest.raises(PlanError, match="tokens task"):
        make_train_step(model, tx, loss_type=NEXT_TOKEN, reduce_buckets=2)


def test_scope_table_holds_every_new_layer(token_step):
    """Every layer of the token model, and the parts a metric reads alone,
    are in the table of the compiled step, forward and backward; what could
    be a traced event and resolves to no layer stays under 1% of it."""
    *_, state, step, batch = token_step
    table = scopes.scope_table(step.lower(state, batch).compile().as_text())
    layers = {s.layer for s in table.values()}
    assert set(scopes.TOKEN_LAYERS) <= layers
    assert {scopes.LOSS, scopes.OPTIMIZER} <= layers
    parts = {(s.layer, part) for s in table.values()
             for part in s.path.split("/")[1:]}
    for layer, part in [
            (scopes.MAMBA, scopes.MAMBA_IN_PROJ),
            (scopes.MAMBA, scopes.MAMBA_CONV),
            (scopes.MAMBA, scopes.MAMBA_SCAN),
            (scopes.MAMBA, scopes.MAMBA_OUT_PROJ),
            (scopes.MOE, scopes.MOE_ROUTER), (scopes.MOE, scopes.MOE_DISPATCH),
            (scopes.MOE, scopes.MOE_ROUTED_EXPERTS),
            (scopes.MOE, scopes.MOE_COMBINE),
            (scopes.MOE, scopes.MOE_SHARED_EXPERT),
            (scopes.MOE, scopes.MOE_LATENT),
            (scopes.MTP, scopes.MOE_DISPATCH), (scopes.MTP, scopes.ATTN)]:
        assert (layer, part) in parts, (layer, part)
    # an expert layer's chunk loop (parallel/moe.py): its three parts keep
    # their names inside the loop's body, forward and reverse, in the trunk
    # and in the prediction module: the three per-layer metrics that read
    # them cannot silently empty
    for layer in (scopes.MOE, scopes.MTP):
        for part in (scopes.MOE_DISPATCH, scopes.MOE_ROUTED_EXPERTS,
                     scopes.MOE_COMBINE):
            for phase in ("fwd", "bwd"):
                assert any(s.layer == layer and s.phase == phase
                           and s.path.endswith("/" + part)
                           for s in table.values()), (layer, part, phase)
    # a block's reverse pass sits under the same block: the path of a
    # rematerialised block does not start over at the root
    assert not any("NemotronH" in s.path for s in table.values())
    assert any(s.phase == "bwd" and s.path.startswith("mamba/l02")
               for s in table.values())
    # nothing the model asked for is lost: what resolves to no layer is the
    # step's own key split and layout copies of its arguments, never an op
    # whose name stack passes through the model.  (The share of the busy
    # time that the table does not hold is guarded where there is a trace:
    # the benchmark's scope readers raise above 1%.)
    text = step.lower(state, batch).compile().as_text()
    named = {i.name: i.op_name or "" for instrs in
             scopes.parse_hlo(text).values() for i in instrs}
    lost = [k for k, s in table.items() if s.layer == scopes.OTHER
            and "NemotronH" in named.get(k, "")]
    assert len(lost) < 0.01 * len(table), lost[:5]
    # and those parts' ops do sit in the loop's body
    in_loop = [n for n in named.values() if "/while/body/" in n]
    for part in (scopes.MOE_DISPATCH, scopes.MOE_ROUTED_EXPERTS,
                 scopes.MOE_COMBINE):
        assert any(f"/{part}/" in n.partition("/while/body")[2]
                   or f"({part})" in n for n in in_loop), part


def test_causal_attention_scopes_stay_in_their_block(whole, share_cfg,
                                                      forced_kernels):
    """The step with the kernels forced (interpreter: each call is a loop of
    plain ops that carry its name stack): everything the forward call and
    the reverse pass leave in the scope table resolves to ``attn`` for the trunk layer and to ``mtp`` for the
    prediction module's, forward and reverse, none to ``other``."""
    model, _, tokens = whole
    tx = optax.sgd(1e-2, momentum=0.9)
    state = jax.eval_shape(lambda: create_train_state(
        jax.random.PRNGKey(11), model, tx, tokens.shape,
        input_dtype=jnp.int32))
    step = make_train_step(
        model, tx, loss_type=NEXT_TOKEN, donate=False,
        loss_weights=(1.0, share_cfg["mtp_loss_weight"]))
    text = step.lower(state, {"tokens": tokens}).compile().as_text()
    table = scopes.scope_table(text)
    named = {i.name: i.op_name or "" for instrs in
             scopes.parse_hlo(text).values() for i in instrs}
    mine = {k: s for k, s in table.items()
            if scopes.CAUSAL_ATTN in named.get(k, "")
            and ";" not in named[k]}
    seen = set()
    for k, s in mine.items():
        parts = s.path.split("/")
        layer = scopes.MTP if "/mtp/" in named[k] else scopes.ATTN
        assert s.layer == layer, (k, s, named[k])
        assert parts[:2] == ([scopes.MTP, scopes.ATTN] if layer == scopes.MTP
                             else [scopes.ATTN, "l00"]), (k, s)
        kernel = next(p for p in parts if p.startswith(scopes.CAUSAL_ATTN))
        seen.add((layer, kernel, s.phase))
    for layer in (scopes.ATTN, scopes.MTP):
        assert {(layer, scopes.CAUSAL_ATTN, "fwd"),
                (layer, scopes.CAUSAL_ATTN_BWD, "bwd")} <= seen, seen
        # the block keeps the call's output and log-sum-exp across its
        # recomputation (``_KEEP_FLASH_RESIDUALS``): no second forward call
        assert (layer, scopes.CAUSAL_ATTN, "bwd") not in seen
    assert not any(s.phase == "fwd" and scopes.CAUSAL_ATTN_BWD in s.path
                   for s in mine.values())


@pytest.mark.parametrize("op_name,want", [
    ("jit(step_fn)/jvp(NemotronH)/mamba/l02/ssd_scan/mul",
     ("mamba", "mamba/l02/ssd_scan", "fwd")),
    ("jit(step_fn)/transpose(jvp(NemotronH))/moe/jvp(NemotronH)/moe/"
     "checkpoint/rematted_computation/l03/dispatch/gather",
     ("moe", "moe/l03/dispatch", "bwd")),
    ("jit(step_fn)/jvp(NemotronH)/mtp/moe/l01/routed_experts/ragged_dot",
     ("mtp", "mtp/moe/l01/routed_experts", "fwd")),
    ("jit(step_fn)/jvp(NemotronH)/lm_head/dot_general",
     ("lm_head", "lm_head", "fwd")),
    # the chunk loop of an expert layer: the loop's own elements (``while``,
    # ``body``, a ``cond``'s branch) are stepped over, forward ...
    ("jit(step_fn)/jvp(NemotronH)/moe/l01/while/body/dispatch/jit(_take)/"
     "gather", ("moe", "moe/l01/dispatch", "fwd")),
    ("jit(step_fn)/jvp(NemotronH)/mtp/moe/l01/while/body/routed_experts/"
     "ragged_dot", ("mtp", "mtp/moe/l01/routed_experts", "fwd")),
    ("jit(step_fn)/jvp(NemotronH)/moe/l05/while/body/cond/branch_1_fun/"
     "combine/scatter-add", ("moe", "moe/l05/combine", "fwd")),
    # ... recomputed in the reverse pass, and in the reverse pass's own loop,
    # where the chunk's products are differentiated inside the body
    ("jit(step_fn)/transpose(jvp(NemotronH))/moe/jvp(NemotronH)/moe/"
     "checkpoint/rematted_computation/l01/while/body/combine/scatter-add",
     ("moe", "moe/l01/combine", "bwd")),
    ("jit(step_fn)/transpose(jvp(NemotronH))/moe/jvp(NemotronH)/moe/"
     "checkpoint/l03/while/body/transpose(jvp(routed_experts))/dot_general",
     ("moe", "moe/l03/routed_experts", "bwd")),
    ("jit(step_fn)/transpose(jvp(NemotronH))/moe/jvp(NemotronH)/moe/"
     "checkpoint/l03/while/body/transpose(jvp(combine))/mul",
     ("moe", "moe/l03/combine", "bwd")),
    ("jit(step_fn)/transpose(jvp(NemotronH))/mtp/moe/jvp(NemotronH)/mtp/moe/"
     "checkpoint/l01/while/body/dispatch/scatter-add",
     ("mtp", "mtp/moe/l01/dispatch", "bwd")),
    # the causal attention kernels (the executable's own op_names, compiled
    # for a described v5e): the forward call, a recomputed forward call
    # (a block rematerialised under a policy that does not keep the call's
    # results) and the custom-VJP reverse pass keep the block's prefix, so
    # the trunk layer's calls are ``attn`` and the prediction module's
    # ``mtp``
    ("jit(step_fn)/jvp(NemotronH)/attn/l00/causal_attn/pallas_call",
     ("attn", "attn/l00/causal_attn", "fwd")),
    ("jit(step_fn)/jvp(NemotronH)/mtp/attn/l00/shard_map/causal_attn/"
     "pallas_call", ("mtp", "mtp/attn/l00/causal_attn", "fwd")),
    ("jit(step_fn)/transpose(jvp(NemotronH))/attn/jvp(NemotronH)/attn/"
     "checkpoint/rematted_computation/l00/causal_attn/pallas_call",
     ("attn", "attn/l00/causal_attn", "bwd")),
    ("jit(step_fn)/transpose(jvp(NemotronH))/attn/jvp(NemotronH)/attn/"
     "checkpoint/l00/causal_attn_bwd/causal_attn_bwd_fused/pallas_call",
     ("attn", "attn/l00/causal_attn_bwd/causal_attn_bwd_fused", "bwd")),
    ("jit(step_fn)/transpose(jvp(NemotronH))/mtp/attn/jvp(NemotronH)/mtp/"
     "attn/checkpoint/l00/causal_attn_bwd/shard_map/causal_attn_bwd_dq/"
     "pallas_call",
     ("mtp", "mtp/attn/l00/causal_attn_bwd/causal_attn_bwd_dq", "bwd")),
    ("jit(step_fn)/transpose(jvp(NemotronH))/mtp/attn/jvp(NemotronH)/mtp/"
     "attn/checkpoint/l00/causal_attn_bwd/reduce_sum",
     ("mtp", "mtp/attn/l00/causal_attn_bwd", "bwd")),
])
def test_token_op_names_resolve_to_their_layer(op_name, want):
    s = scopes.scope_of(op_name)
    assert (s.layer, s.path, s.phase) == want


# ------------------------------------------- the image step is left alone
#: sha256 of the lowered text of the DANet-r18 32x32 b2 steps below, taken on
#: the parent of the PR that brought the tokens task (9ed87f6) and equal on
#: its own tree: the task's branches in parallel/step.py leave the image
#: step's program as it was.  Re-pin when the image step itself changes.
DANET_R18_LOWERED = {
    "train": "84540e7b59d1898ef2c9283bdcfa205c447ad7d9f9a67a4fd799b53217a7ee8b",
    "eval": "55b5cf405c33db7e5e67dba27d6a7faaf80d7ce04b5691ce46f7e85f5ea80dae",
}


@pytest.mark.parametrize("which", ["train", "eval"])
def test_danet_r18_step_program_is_unchanged(which):
    model = build_model("danet", nclass=1, backbone="resnet18",
                        output_stride=8)
    tx = optax.sgd(1e-3, momentum=0.9)
    state = jax.eval_shape(lambda: create_train_state(
        jax.random.PRNGKey(0), model, tx, (1, 32, 32, 4)))
    batch = {"concat": jax.ShapeDtypeStruct((2, 32, 32, 4), jnp.float32),
             "crop_gt": jax.ShapeDtypeStruct((2, 32, 32), jnp.float32)}
    fn = make_train_step(model, tx, donate=False) if which == "train" \
        else make_eval_step(model)
    text = fn.lower(state, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        DANET_R18_LOWERED[which]


# ------------------------------------------------------------- the trainer
def _token_cfg(tmp_path, *extra):
    from distributedpytorch_tpu.train import Config, apply_overrides
    import dataclasses

    cfg = apply_overrides(Config(), [
        "task=tokens", "model.name=nemotron_h", "data.train_batch=8",
        "data.val_batch=8", "data.seq_len=24", "data.token_samples=16",
        "data.token_val_samples=8", "optim.lr=1e-2", "epochs=1",
        "checkpoint.async_save=false", "log_every_steps=1", *extra])
    return dataclasses.replace(cfg, work_dir=str(tmp_path / "runs"))


def test_trainer_fits_two_steps_on_the_synthetic_source(tmp_path):
    from distributedpytorch_tpu.train import Trainer

    tr = Trainer(_token_cfg(tmp_path))
    try:
        assert str(tr.train_set).startswith("SyntheticTokens(n=16")
        hist = tr.fit()
    finally:
        tr.close()
    assert int(tr.state.step) == 2
    assert np.isfinite(hist["train_loss"][0])
    val = hist["val"][0]
    assert np.isfinite(val["loss"]) and val["perplexity"] > 1.0
    lines = [json.loads(ln) for ln in open(
        os.path.join(tr.run_dir, "metrics.jsonl"))]
    flat = {k: v for ln in lines for k, v in ln.items()}
    assert flat["train/moe_tokens_dropped"] == 0
    assert flat["train/moe_expert_load_max_over_mean"] >= 1.0
    assert "val/new_best_neg_loss" in flat


def test_trainer_reads_the_packed_token_file(tmp_path):
    from distributedpytorch_tpu.data import PackedTokens, write_token_file
    from distributedpytorch_tpu.train import Trainer

    ids = np.random.default_rng(0).integers(0, 256, 24 * 24 + 5)
    path = write_token_file(str(tmp_path / "ids.bin"), ids)
    whole_file = PackedTokens(path, 24)
    assert len(whole_file) == 24
    np.testing.assert_array_equal(whole_file[3]["tokens"], ids[72:96])
    tr = Trainer(_token_cfg(tmp_path, f"data.token_file={path}"))
    try:
        assert len(tr.train_set) == 16 and len(tr.val_set) == 8
        np.testing.assert_array_equal(tr.val_set[0]["tokens"],
                                      ids[16 * 24:17 * 24])
        assert np.isfinite(tr.train_epoch(0))
    finally:
        tr.close()
    with pytest.raises(ValueError, match="vocabulary rows"):
        PackedTokens(write_token_file(str(tmp_path / "big.bin"),
                                      ids + 1000), 24, vocab_size=256)[0]


def test_tokens_task_and_model_go_together(tmp_path):
    from distributedpytorch_tpu.train import Trainer, apply_overrides

    # the model's registry entry says which tasks it trains under; the
    # trainer holds a configuration to that, both ways
    with pytest.raises(ValueError, match="instance | semantic"):
        Trainer(apply_overrides(_token_cfg(tmp_path),
                                ["model.name=danet"]))
    with pytest.raises(ValueError, match="trains under task=tokens"):
        Trainer(apply_overrides(_token_cfg(tmp_path), ["task=semantic"]))


def test_auto_plan_costs_a_token_model_by_its_own_activations(tmp_path):
    """``strategy=auto``: the memory model takes the token model's own
    activation estimate (a batch of ids is a few KB; the input-bytes rule of
    the image nets would cost it at nothing)."""
    from distributedpytorch_tpu.parallel import plan as plan_lib
    from distributedpytorch_tpu.train import Trainer

    tr = Trainer(_token_cfg(tmp_path, "parallel.strategy=auto",
                            "parallel.hbm_budget_gb=4"))
    try:
        assert tr.plan.strategy == "dp"
        struct, batch_bytes, act = tr._plan_memory_inputs()
        assert batch_bytes == 8 * 24 * 4
        assert act == tr.model.activation_bytes(1, 24) > 100 * batch_bytes
        mem = plan_lib.estimate_plan_memory(tr.plan, struct, batch_bytes,
                                            activation_bytes=act)
        assert mem["activations"] == act and mem["batch_stats"] == 0
    finally:
        tr.close()


# ------------------------------------------------- the benchmark's config
def test_activation_bytes_follow_the_attention_form_that_runs(monkeypatch):
    """The planner's memory model charges the einsum form its three float32
    (q_heads, 8192, 8192) arrays and the flash kernels none of them."""
    from distributedpytorch_tpu.models import danet

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron3_super_stage_tp8_ep64.json")) as f:
        cfg = json.load(f)
    model = nh.build_nemotron_h(cfg, dtype=jnp.bfloat16)
    float32 = nh.build_nemotron_h(cfg, dtype=jnp.float32)
    scores = 3 * 4 * 8192 * 8192 * 4
    einsum = model.activation_bytes(1, 8192)
    einsum32 = float32.activation_bytes(1, 8192)
    monkeypatch.setattr(danet, "_on_tpu", lambda: True)
    assert einsum - model.activation_bytes(1, 8192) > 0.7 * scores
    # float32 keeps the einsum form on a TPU too
    assert float32.activation_bytes(1, 8192) == einsum32


def test_benchmark_configuration_keeps_every_published_width():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron3_super_stage_tp8_ep64.json")) as f:
        cfg = json.load(f)
    widths = {"hidden_size": 4096, "head_dim": 128, "mamba_head_dim": 64,
              "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
              "moe_latent_size": 1024, "moe_intermediate_size": 2688,
              "moe_shared_expert_intermediate_size": 5376,
              "num_experts_per_tok": 22, "expand": 2,
              "intermediate_size": 2688, "routed_scaling_factor": 5}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["published"]["n_routed_experts"] == 512
    assert cfg["hybrid_override_pattern"] == \
        cfg["published"]["hybrid_override_pattern"][25:36]
    # the held counts keep the published ratios (one eighth of each)
    for key in ("mamba_num_heads", "n_groups", "num_attention_heads",
                "vocab_size"):
        assert cfg[key] * 8 == cfg["published"][key], key
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads", "vocab_size"}
    # the operation count is the formula's, term by term
    terms = ref.flops_per_sequence(cfg, 8192)
    assert cfg["model_flops_per_image"] == int(round(terms["total"]))
    per_token = terms["total"] / 8192 / 1e9
    assert 3.4 < per_token < 3.8
    # and the parameters are what the issue reckoned: 838 M, 12 B each
    n = sum(int(np.prod(s)) for s, _ in jax.tree.leaves(
        ref.param_spec(cfg), is_leaf=ref._is_leaf))
    assert 837e6 < n < 839e6


# ------------------------------- what a rematerialised expert block keeps
@pytest.mark.parametrize("policy,runs", [("kept", 1), ("bare", 2)])
def test_replay_of_an_expert_block_holds_no_product_selection_or_loop(
        policy, runs):
    """The gradient of a block that keeps ``EXPERT_KEPT`` runs the top-22,
    the gather of the chosen scores, the router's, ``latent_down``'s and
    ``shared_up``'s products and the forward chunk loop once; under a bare
    ``nn.remat`` each runs twice (the routed sum feeds ``latent_up``'s
    weight gradient, so the loop is live in the replay)."""
    cfg = nh.LMConfig.from_dict(tiny())
    got = kept_cases.replay_counts(
        *kept_cases.block_case(
            nh.LatentMoE, cfg,
            nh._KEEP_EXPERT_RESIDUALS if policy == "kept" else None),
        widths=(cfg.experts_total, cfg.latent_size, cfg.shared_hidden),
        k=cfg.experts_per_token)
    assert got == {"top_k": runs, "gathers": runs, "products": [runs] * 3,
                   "forward_loops": runs}


def test_model_rematerialises_its_expert_blocks_under_the_policy(whole,
                                                                 share_cfg):
    """``_run_blocks`` wraps every ``E`` block, the prediction module's
    too: the whole model's gradient holds one router product and one top-k
    a block."""
    model, params, tokens = whole
    assert model.remat
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: sum(
        (o ** 2).sum() for o in model.apply(
            {"params": p}, tokens, train=True,
            mutable=["counters"])[0])))(params).jaxpr
    blocks = (share_cfg["hybrid_override_pattern"]
              + share_cfg["mtp_hybrid_override_pattern"]).count("E")
    assert blocks == 3
    assert len(kept_cases.eqns_named(jaxpr, "top_k")) == blocks
    assert kept_cases.forward_products(
        jaxpr, tokens.size, share_cfg["hidden_size"],
        share_cfg["published"]["n_routed_experts"]) == blocks


def test_kept_expert_block_gives_the_bare_blocks_loss_and_gradients():
    kept_cases.assert_kept_block_is_the_bare_blocks(
        nh.LatentMoE, nh.LMConfig.from_dict(tiny()),
        nh._KEEP_EXPERT_RESIDUALS, GRAD_RTOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_activation_bytes_count_what_an_expert_block_keeps(dtype):
    """One more ``E`` block costs the planner its input and the bytes of
    the arrays the block names; with no recomputation nothing is named
    apart."""
    cfg, batch, length = tiny(num_nextn_predict_layers=0), 2, 24
    item = jnp.dtype(dtype).itemsize

    def act(pattern, remat=True):
        return nh.build_nemotron_h(
            dict(cfg, hybrid_override_pattern=pattern), dtype=dtype,
            remat=remat).activation_bytes(batch, length)

    block_input = batch * length * cfg["hidden_size"] * item
    kept = kept_cases.kept_bytes(nh.LatentMoE, nh.LMConfig.from_dict(cfg),
                                 nh.EXPERT_KEPT, batch, length, dtype)
    tokens = batch * length
    # logits and chosen scores float32, ids, rows and group sizes int32;
    # three in ``dtype``
    assert kept == 4 * (tokens * (8 + 2 * 3) + tokens * 3 + 8) \
        + item * tokens * (2 * 32 + 96)
    assert act("MEE") - act("ME") == block_input + kept
    assert act("MEE", remat=False) - act("ME", remat=False) \
        == 8 * block_input
