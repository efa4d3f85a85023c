"""``kanana_lm`` (models/kanana_lm.py) against its plain reference
(benchmarks/reference/kanana_lm.py): multi-head latent attention with the
decoupled, interleaved rotary part and one shared key head, values narrower
than keys through the causal paths, the leading dense layer, the sigmoid
router with its score-correction bias, the shared experts; the expert
layer's share of a deployment; the model through the step and the trainer;
the shared code that Nemotron's step and the causal flash calls keep.  Tiny
widths, seeded weights, on the CPU.

Tolerances, with their reasons: program and reference both compute in
float32 here, in different forms — the whole row against query blocks, the
rotary with its pairs interleaved against the published de-interleaved
form, the grouped product against a masked loop — so outputs agree to
rounding of float32 sums in another order: 2e-5 of the largest value for
outputs and logits, 1e-5 of the loss, 1e-4 of a leaf's largest gradient for
gradients.  Under the bfloat16 policy the program rounds every product's
operands to 8 bits of mantissa where the reference holds 24: losses agree to
1e-2, a leaf's change to 3e-2 of its norm.  Each part of the mathematics
left out (the routed or the shared experts, the latent's norm, the
decoupled rotary) moves the gradients by 1e-2 or more
(``test_each_fault_of_the_reference_is_another_step``), so none of these
tolerances passes a program without it.
"""

import dataclasses
import functools
import gzip
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from reference import kanana_lm as ref  # noqa: E402

import expert_remat_cases as kept_cases  # noqa: E402
import router_jaxpr_cases  # noqa: E402
from conftest import assert_grads_close  # noqa: E402
from distributedpytorch_tpu.models import build_model  # noqa: E402
from distributedpytorch_tpu.models import kanana_lm as kl  # noqa: E402
from distributedpytorch_tpu.ops import pallas_attention as pa  # noqa: E402
from distributedpytorch_tpu.ops.attention import causal_attention  # noqa: E402
from distributedpytorch_tpu.parallel import (NEXT_TOKEN,  # noqa: E402
                                             create_train_state,
                                             make_train_step)
from distributedpytorch_tpu.parallel import moe as moe_lib  # noqa: E402
from distributedpytorch_tpu.parallel.step import _loss_and_updates  # noqa: E402
from distributedpytorch_tpu.telemetry import scopes  # noqa: E402
from distributedpytorch_tpu.train.precision import precision_policy  # noqa: E402

OUT_RTOL, LOSS_RTOL, GRAD_RTOL = 2e-5, 1e-5, 1e-4
FAULTS = ("rows", "drop_routed", "no_shared", "no_kv_norm",
          "whole_head_rope")
CELL_CONFIG = "kanana2_30b_a3b_stage_ep8.json"


def tiny(**over):
    return dict(kl.PRESETS["tiny"], **over)


def rel_gap(got, want):
    return float(jnp.abs(got - want).max()) / (float(jnp.abs(want).max())
                                               + 1e-12)


def leaves_with_names(tree):
    return [(jax.tree_util.keystr(p), x)
            for p, x in jax.tree_util.tree_leaves_with_path(tree)]


def assert_trees_close(got, want, rtol):
    for (name, a), b in zip(leaves_with_names(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert rel_gap(a, b) <= rtol, (name, rel_gap(a, b))


@pytest.fixture(scope="module")
def whole():
    cfg = tiny()
    model = build_model("kanana_lm", lm_config=cfg)
    params = ref.make_weights(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0,
                                cfg["vocab_size"])
    return cfg, model, params, tokens


def program_loss(model, params, tokens):
    """The step's own objective, with the counters it hands back."""
    loss, _, counters = _loss_and_updates(
        model, params, {}, {"tokens": tokens}, jax.random.PRNGKey(0),
        model.loss_weights, True, NEXT_TOKEN)
    return loss, counters


# ------------------------------------------------- (a) program = reference
def test_parameter_tree_is_the_references(whole):
    cfg, model, params, tokens = whole
    made = model.init(jax.random.PRNGKey(1), tokens)["params"]
    assert jax.tree.map(lambda x: x.shape, made) == \
        jax.tree.map(lambda x: x.shape, params)
    spec = jax.tree.leaves(ref.param_spec(cfg), is_leaf=ref._is_leaf)
    assert [s for s, _ in spec] == [x.shape for x in jax.tree.leaves(made)]
    # layer 0's second block is dense, every later one an expert layer
    assert "router" not in made["l01"] and made["l01"]["w1"].shape == (
        64, 2 * 96)
    assert made["l03"]["router"].shape == (64, 8)
    assert made["l03"]["w1"].shape == (4, 64, 32)
    # the latent and the shared key head are one down-projection
    assert made["l00"]["kv_a_proj"].shape == (64, 32 + 8)


def test_logits_loss_and_gradients_equal_the_reference(whole):
    cfg, model, params, tokens = whole
    with jax.default_matmul_precision("highest"):
        (logits,), _ = model.apply({"params": params}, tokens,
                                   mutable=["counters"])
        want_logits = ref.forward(params, tokens, cfg)
        (loss, _), grads = jax.value_and_grad(
            lambda p: program_loss(model, p, tokens), has_aux=True)(params)
        want, want_grads = jax.value_and_grad(ref.loss_fn)(params, tokens,
                                                           cfg)
    assert logits.shape == (2, 32, cfg["vocab_size"])
    assert logits.dtype == jnp.float32
    assert rel_gap(logits, want_logits) <= OUT_RTOL
    assert abs(float(loss) - float(want)) <= LOSS_RTOL * float(want)
    assert_trees_close(grads, want_grads, GRAD_RTOL)
    for name, g in leaves_with_names(want_grads):
        # no gradient reaches the score-correction bias: the selection
        # passes none
        assert (float(jnp.abs(g).max()) == 0) == (
            "e_score_correction_bias" in name), name


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_three_steps_follow_the_reference(whole, precision):
    cfg, model, params, tokens = whole
    policy = precision_policy(precision)
    if policy is not None:
        model = build_model("kanana_lm", lm_config=cfg,
                            dtype=policy.compute_dtype)
    tx = optax.sgd(1e-2, momentum=0.9)
    opt = {"learning_rate": 1e-2, "momentum": 0.9}
    state = create_train_state(jax.random.PRNGKey(11), model, tx,
                               tokens.shape, input_dtype=jnp.int32)
    state = state.replace(params=params, opt_state=tx.init(params))
    step = make_train_step(model, tx, loss_type=NEXT_TOKEN, donate=False,
                           loss_weights=model.loss_weights, precision=policy)
    ref_step = jax.jit(functools.partial(ref.train_step, cfg, opt,
                                         remat=False))
    loss_tol = LOSS_RTOL if policy is None else 1e-2
    p, trace = params, jax.tree.map(jnp.zeros_like, params)
    with jax.default_matmul_precision("highest"):
        for _ in range(3):
            state, (loss, counters) = step(state, {"tokens": tokens})
            p, trace, want = ref_step(p, trace, {"tokens": tokens})
            assert abs(float(loss) - float(want)) <= loss_tol * float(want)
    change = jax.tree.map(lambda a, b: a - b, state.params, params)
    want_change = jax.tree.map(lambda a, b: a - b, p, params)
    if policy is None:
        assert_trees_close(change, want_change, 5e-4)
    else:  # leaf by leaf, the norm of the change, as the benchmark compares
        for (name, a), b in zip(leaves_with_names(change),
                                jax.tree.leaves(want_change)):
            na, nb = float(jnp.linalg.norm(a)), float(jnp.linalg.norm(b))
            assert abs(na - nb) <= 3e-2 * nb, (name, na, nb)
    assert int(counters["moe_tokens_dropped"]) == 0
    assert float(counters["moe_expert_load_max_over_mean"]) >= 1


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_of_the_reference_is_another_step(whole, fault):
    cfg, _, params, tokens = whole
    opt = {"learning_rate": 1e-2, "momentum": 0.9}
    zeros = jax.tree.map(jnp.zeros_like, params)
    kw = {"rows": tokens.size // 2} if fault == "rows" else {fault: True}
    sound = ref.train_step(cfg, opt, params, zeros, {"tokens": tokens},
                           remat=False)
    other = ref.train_step(cfg, opt, params, zeros, {"tokens": tokens},
                           remat=False, **kw)
    moved = [rel_gap(a, b) for a, b in zip(jax.tree.leaves(other[1]),
                                           jax.tree.leaves(sound[1]))]
    assert max(moved) > 1e-2, fault


def test_reference_blocks_change_memory_not_arithmetic(whole, monkeypatch):
    cfg, _, params, tokens = whole
    want = jax.value_and_grad(ref.loss_fn)(params, tokens, cfg)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "LOSS_BLOCK", 16)
    monkeypatch.setattr(sys.modules["reference.nemotron_h"], "LOSS_BLOCK", 16)
    got = jax.value_and_grad(functools.partial(ref.loss_fn, remat=True))(
        params, tokens, cfg)
    assert abs(float(got[0]) - float(want[0])) <= 1e-6 * float(want[0])
    assert_trees_close(got[1], want[1], GRAD_RTOL)


def test_kernel_path_gives_the_einsum_models_loss_and_gradients(
        whole, monkeypatch, interpreted_kernels):
    """The flash kernels at q and k 24 wide, v 16 give the einsum form's
    loss and gradients; a rematerialised block runs one forward call."""
    from distributedpytorch_tpu.models import danet

    cfg, model, params, tokens = whole

    def loss(p):
        return program_loss(model, p, tokens)[0]

    want = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(danet, "auto_wants_flash", lambda dtype: True)
    got = jax.value_and_grad(loss)(params)
    assert abs(float(got[0]) - float(want[0])) <= LOSS_RTOL * float(want[0])
    assert_trees_close(got[1], want[1], GRAD_RTOL)
    text = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert text.count(f"name={scopes.CAUSAL_ATTN}\n") == \
        cfg["num_hidden_layers"], "a replayed block runs no forward call"


# ------------------------------- (b) values narrower than keys, causally
def _heads(s, hq, hkv, d, dv, seed):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(2, s, h, w), jnp.float32)
                 for h, w in ((hq, d), (hkv, d), (hkv, dv)))


def _dense_softmax(q, k, v):
    """Causal attention written out over whole arrays, the keys and values
    repeated for every query head of their group."""
    r = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) \
        / math.sqrt(q.shape[-1])
    pos = jnp.arange(q.shape[1])
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                      precision=jax.lax.Precision.HIGHEST)


def _sq(fn):
    return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()


@pytest.fixture()
def tiles(monkeypatch, request):
    """Tiles of 128 a side, forward and reverse, in the named reverse
    schedule: 64 tokens are one padded tile, 300 are 3 x 3 with 84 padded
    keys and queries in the last."""
    monkeypatch.setattr(pa, "_CAUSAL_TILE", (128, 128))
    monkeypatch.setattr(pa, "_bwd_plan",
                        lambda n, ck: (128, request.param == "fused"))
    return request.param


@pytest.mark.parametrize("tiles", ["fused", "two_sweeps"], indirect=True)
@pytest.mark.parametrize("s", [64, 300])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 1)])
def test_narrow_values_flash_einsum_and_dense_softmax_agree(tiles, s, hq,
                                                            hkv):
    """q and k 24 wide, v 16: the flash kernels (interpreted), the einsum
    form and the softmax written out agree forward and reverse, the output
    as wide as v, the scale that of q's width."""
    q, k, v = _heads(s, hq, hkv, 24, 16, seed=31)
    flash = functools.partial(pa.flash_causal_attention, interpret=True)
    with jax.default_matmul_precision("highest"):
        outs = [fn(q, k, v) for fn in (flash, causal_attention,
                                       _dense_softmax)]
        grads = [jax.grad(_sq(fn), (0, 1, 2))(q, k, v)
                 for fn in (flash, causal_attention, _dense_softmax)]
    for out in outs:
        assert out.shape == (2, s, hq, 16)
    for out, g in zip(outs[:2], grads[:2]):
        np.testing.assert_allclose(out, outs[2], rtol=2e-5, atol=2e-6)
        assert_grads_close(grads[2], g)
    assert [x.shape for x in grads[0]] == [x.shape for x in (q, k, v)]


@pytest.mark.parametrize("tiles", ["fused", "two_sweeps"], indirect=True)
def test_narrow_values_calls_read_each_width_once(tiles):
    """Each call reads q and k at their width and v at its own; the reverse
    pass hands back dv as narrow as v."""
    q, k, v = _heads(300, 4, 4, 24, 16, seed=32)
    flash = functools.partial(pa.flash_causal_attention, interpret=True)
    jaxpr = jax.make_jaxpr(jax.grad(_sq(flash), (0, 1, 2)))(q, k, v).jaxpr
    calls = kept_cases.eqns_named(jaxpr, "pallas_call")
    assert [e.params["name"] for e in calls] == {
        "fused": ["causal_attn", "causal_attn_bwd_fused"],
        "two_sweeps": ["causal_attn", "causal_attn_bwd_dkv",
                       "causal_attn_bwd_dq"]}[tiles]
    for e in calls:
        assert [x.aval.shape[-1] for x in e.invars[:3]] == [24, 24, 16]
    with pytest.raises(ValueError, match="keys 20 wide"):
        pa.flash_causal_attention(q, k[..., :20], v)


# ------------------------------------------------------ (c) the mechanisms
def test_interleaved_rotary_is_the_published_deinterleaved_form():
    """Turning the pairs ``(2i, 2i + 1)`` in place is the published form
    (de-interleave, then rotate-half) with the channels in another order:
    de-interleaving the program's result gives the reference's, and q.k is
    the same either way."""
    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(2, 12, 3, 64), jnp.float32)
    y = jnp.asarray(r.randn(2, 12, 3, 64), jnp.float32)
    positions = ref.text_positions(2, 12)
    ang = kl.rotary_angles(kl.text_positions(2, 12), 32, 1e6)
    got = kl.rotate_pairs(x, ang)
    want = ref.rotary(ref.deinterleave(x), positions, 1e6)
    assert rel_gap(ref.deinterleave(got), want) <= OUT_RTOL
    np.testing.assert_array_equal(got[:, 0], x[:, 0])      # position 0
    # pair (0, 1) turns by the position itself, pair (31, 32) slowest
    np.testing.assert_allclose(
        got[:, 5, :, 0], x[:, 5, :, 0] * np.cos(5.0)
        - x[:, 5, :, 1] * np.sin(5.0), rtol=1e-5, atol=1e-6)
    dots = jnp.einsum("bshd,bshd->bsh", got, kl.rotate_pairs(y, ang))
    want_dots = jnp.einsum("bshd,bshd->bsh", want, ref.rotary(
        ref.deinterleave(y), positions, 1e6))
    assert rel_gap(dots, want_dots) <= OUT_RTOL


def test_every_query_head_reads_the_one_shared_key_head(whole, monkeypatch):
    """The block's keys carry the same rotary part in every head: moving
    the shared key head's weights moves every head's output."""
    cfg, _, params, _ = whole
    c = kl.LMConfig.from_dict(cfg)
    block = kl.LatentAttention(c)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 16, c.hidden_size))
    p = params["l00"]
    moved = dict(p, kv_a_proj=p["kv_a_proj"].at[:, c.kv_rank:].multiply(2.0))
    grab = {}

    def spy(q, k, v):
        grab["k"] = k
        return causal_attention(q, k, v)

    monkeypatch.setattr(kl, "causal_attention", spy)
    out = block.apply({"params": p}, u)
    k = grab["k"]
    other = block.apply({"params": moved}, u)
    assert k.shape == (2, 16, c.heads, c.qk_dim)
    rope = k[..., c.qk_nope_dim:]
    np.testing.assert_array_equal(rope, jnp.broadcast_to(
        rope[:, :, :1], rope.shape))
    per_head = jnp.abs(other - out).reshape(2, 16, -1).max(axis=(0, 2))
    assert float(per_head[1:].min()) > 0


def test_attention_block_is_causal(whole):
    cfg, _, params, _ = whole
    block = kl.LatentAttention(kl.LMConfig.from_dict(cfg))
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg["hidden_size"]))
    moved = u.at[:, 9].add(1.0)
    diff = jnp.abs(block.apply({"params": params["l00"]}, moved)
                   - block.apply({"params": params["l00"]}, u))
    changed = np.flatnonzero(np.asarray(diff.max(axis=(0, 2))) > 0)
    assert list(changed) == list(range(9, 16))


def test_router_is_the_references(whole):
    """``parallel/moe.py::sigmoid_route`` gives the reference's choice and
    the held experts' weights ``s_e / sum of the chosen s x 2.448``."""
    cfg, _, params, _ = whole
    p = params["l03"]
    x = jax.random.normal(jax.random.PRNGKey(8), (64, cfg["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        weights, idx = moe_lib.sigmoid_route(
            x, p["router"], p["e_score_correction_bias"], k=3, held=4,
            offset=4, norm_topk=True, scale=2.448)
        scores, want_idx, denom = ref.route(p, x, cfg)
    np.testing.assert_array_equal(idx, want_idx)
    assert rel_gap(weights, 2.448 * scores[:, 4:] / denom[:, None]) \
        <= OUT_RTOL


def test_bias_moves_the_choice_and_not_the_weights(whole):
    """A score-correction bias that favours two experts takes them into
    tokens' choices; the weights of the experts chosen are still the
    sigmoid scores' shares, without the bias; the program's expert block
    follows the reference at that bias."""
    cfg, _, params, _ = whole
    p = params["l03"]
    x = jax.random.normal(jax.random.PRNGKey(9), (64, cfg["hidden_size"]))
    bias = jnp.zeros(8).at[jnp.array([1, 6])].set(0.3)
    route = functools.partial(moe_lib.sigmoid_route, x, p["router"], k=3,
                              held=8, offset=0, norm_topk=True, scale=1.0)
    with jax.default_matmul_precision("highest"):
        w0, idx0 = route(jnp.zeros(8))
        w1, idx1 = route(bias)
        scores = jax.nn.sigmoid(x @ p["router"])
    assert (np.asarray(idx0) != np.asarray(idx1)).any()
    for w, idx in ((w0, idx0), (w1, idx1)):
        chosen = jnp.take_along_axis(scores, idx, axis=-1).sum(-1)
        assert rel_gap(w, scores / chosen[:, None]) <= OUT_RTOL
    sets0, sets1 = (np.sort(np.asarray(i), -1) for i in (idx0, idx1))
    same = (sets0 == sets1).all(-1)
    assert same.any()
    np.testing.assert_allclose(np.asarray(w0)[same], np.asarray(w1)[same],
                               rtol=1e-6)
    # the whole expert block at that bias, program against reference
    biased = dict(p, e_score_correction_bias=bias)
    u = jax.random.normal(jax.random.PRNGKey(10), (2, 16, 64))
    block = kl.SigmoidMoE(kl.LMConfig.from_dict(cfg))
    with jax.default_matmul_precision("highest"):
        got, _ = block.apply({"params": biased}, u, mutable=["counters"])
        want = u + ref.sigmoid_moe(
            biased, ref.rms_norm(u, p["norm"], cfg["rms_norm_eps"]), cfg)
        plain, _ = block.apply({"params": p}, u, mutable=["counters"])
    assert rel_gap(got, want) <= OUT_RTOL
    assert float(jnp.abs(got - plain).max()) > 1e-3


def test_expert_shares_add_up_to_the_uncut_layer():
    """The two shares of the published experts (4 each, the router as wide
    as all 8) give routed parts that, with the shared experts and the
    residual every chip computes alike counted once, add up to the uncut
    reference's layer; each share of the program is the reference's."""
    uncut = tiny(n_routed_experts=8)
    full = ref.make_weights(jax.random.PRNGKey(5), uncut)["l03"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 64))
    x = ref.rms_norm(u, full["norm"], uncut["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        want = u + ref.sigmoid_moe(full, x, uncut)
        alike = u + ref.swiglu(x, full["shared_w1"], full["shared_w2"])
        total = alike
        for off in (0, 4):
            cfg = tiny(n_routed_experts=4, expert_offset=off)
            share = dict(full, w1=full["w1"][off:off + 4],
                         w2=full["w2"][off:off + 4])
            part = ref.sigmoid_moe(share, x, cfg, no_shared=True)
            out, _ = kl.SigmoidMoE(kl.LMConfig.from_dict(cfg)).apply(
                {"params": share}, u, mutable=["counters"])
            assert rel_gap(out - alike, part) <= OUT_RTOL
            total = total + part
    assert rel_gap(total, want) <= OUT_RTOL
    assert float(jnp.abs(want - alike).max()) > 1e-2


# ------------------------------------------- (d) the code the others share
@pytest.mark.parametrize("precision", router_jaxpr_cases.PRECISIONS)
def test_nemotron_step_traces_as_before(precision):
    """Nemotron's whole step at ``tiny``, with its router now the shared
    ``sigmoid_route``, traces to the jaxpr its inline router gave
    (``tests/router_jaxpr_cases.py``)."""
    with gzip.open(os.path.join(
            REPO, "tests", "fixtures", "router_jaxprs",
            f"nemotron_tiny_step_{precision}.txt.gz"), "rt") as f:
        want = f.read()
    assert router_jaxpr_cases.traced(precision) == want


@pytest.mark.parametrize("rule", ["causal", "mla"])
def test_only_kananas_causal_rule_names_the_calls_operands(rule):
    """:data:`CAUSAL` — the rule of Nemotron's, ZAYA1's and the sparse
    path's calls — names the call's output and log-sum-exp and no operand;
    Kanana's rule names its heads-first q, k and v besides.  Both run the
    calls named ``causal_attn…``, which the roofline reader finds."""
    q, k, v = _heads(256, 4, 1, 24, 16, 0)
    the_rule = pa.CAUSAL if rule == "causal" else kl.MLA_CAUSAL
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: (pa.flash_causal_attention(
            *a, interpret=True, rule=the_rule) ** 2).sum(),
        (0, 1, 2)))(q, k, v).jaxpr
    names = [e.params["name"] for e in kept_cases.eqns_named(jaxpr, "name")]
    assert names == [*the_rule.kept_operands, *pa.CAUSAL.kept]
    assert pa.CAUSAL.kept_operands == ()
    assert [e.params["name"] for e in kept_cases.eqns_named(
        jaxpr, "pallas_call")] == [scopes.CAUSAL_ATTN,
                                   scopes.CAUSAL_ATTN_BWD_FUSED]


# ----------------------------------------------- scopes and remat policies
def test_scope_table_holds_the_latent_path_and_the_experts(whole):
    cfg, model, params, tokens = whole
    tx = optax.sgd(1e-2, momentum=0.9)
    state = create_train_state(jax.random.PRNGKey(11), model, tx,
                               tokens.shape, input_dtype=jnp.int32)
    step = make_train_step(model, tx, loss_type=NEXT_TOKEN, donate=False)
    table = scopes.scope_table(
        step.lower(state, {"tokens": tokens}).compile().as_text())
    parts = {(s.layer, part, s.phase) for s in table.values()
             for part in s.path.split("/")[1:]}
    for phase in ("fwd", "bwd"):
        assert (scopes.ATTN, scopes.MLA_LATENT, phase) in parts, phase
        assert (scopes.MOE, scopes.MOE_ROUTER, phase) in parts, phase
        assert (scopes.MOE, scopes.MOE_SHARED_EXPERT, phase) in parts, phase
    for part in (scopes.MOE_DISPATCH, scopes.MOE_ROUTED_EXPERTS,
                 scopes.MOE_COMBINE):
        assert (scopes.MOE, part, "fwd") in parts, part
    assert {scopes.EMBED, scopes.ATTN, scopes.MLP, scopes.MOE,
            scopes.LM_HEAD, scopes.LOSS, scopes.OPTIMIZER} <= {
                s.layer for s in table.values()}
    assert not any("KananaLM" in s.path for s in table.values())
    assert any(s.path.startswith("attn/l02/mla_latent")
               for s in table.values())
    assert any(s.path.startswith("mlp/l01") for s in table.values())
    assert not any(s.path.startswith("moe/l01") for s in table.values())


def input_products(jaxpr, c: kl.LMConfig, positions: tuple) -> list:
    """The forward products of an attention block over ``positions`` in
    ``jaxpr``: q's and the latent's of the normed input, the
    up-projection's of the normed latent."""
    x, latent = positions + (c.hidden_size,), positions + (c.kv_rank,)
    return [kept_cases.input_products(jaxpr, shape, width) for shape, width
            in ((x, c.heads * c.qk_dim), (x, c.kv_rank + c.qk_rope_dim),
                (latent, c.heads * (c.qk_nope_dim + c.v_dim)))]


@pytest.mark.parametrize("policy,runs", [("kept", 1), ("bare", 2)])
def test_replay_of_an_attention_block_runs_no_down_projection(
        whole, attn_form, policy, runs):
    """A block that keeps :data:`ATTN_KEPT` runs no product of its input in
    its replay — q's product, the key/value down-projection and the
    up-projection each run once in the gradient, where a bare ``nn.remat``
    runs each twice; on the kernel path q and the keys go heads-first once
    each, where the bare block transposes them twice."""
    from flax import linen as nn

    cfg, _, params, _ = whole
    c = kl.LMConfig.from_dict(cfg)
    cls = nn.remat(kl.LatentAttention, policy=kl.KEEP_ATTN_RESIDUALS
                   if policy == "kept" else None)
    block = cls(c, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(2),
                          kept_cases.POSITIONS + (c.hidden_size,))

    def loss(p, u):
        return (block.apply({"params": p}, u) ** 2).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params["l00"], u).jaxpr
    assert input_products(jaxpr, c, kept_cases.POSITIONS) == [runs] * 3
    if attn_form == "kernels":
        assert kept_cases.heads_first_transposes(
            jaxpr, kept_cases.POSITIONS + (c.heads, c.qk_dim)) == 2 * runs


def test_kept_attention_block_gives_the_bare_blocks_loss_and_gradients(
        attn_form):
    kept_cases.assert_kept_block_is_the_bare_blocks(
        kl.LatentAttention, kl.LMConfig.from_dict(tiny()),
        kl.KEEP_ATTN_RESIDUALS, GRAD_RTOL)


def test_kernel_path_keeps_the_attention_blocks_operands_by_name(
        whole, monkeypatch, interpreted_kernels):
    """The whole model's gradient on the kernel path names every array an
    attention block keeps once a layer — the flash call's heads-first q, k
    and v among them — and holds each forward product of an attention
    block and each transpose of q and the keys to heads-first once a
    layer: none is replayed."""
    from distributedpytorch_tpu.models import danet

    cfg, model, params, tokens = whole
    monkeypatch.setattr(danet, "auto_wants_flash", lambda dtype: True)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: program_loss(model, p, tokens)[0]))(params).jaxpr
    c, layers = model.cfg, cfg["num_hidden_layers"]
    names = [e.params["name"] for e in kept_cases.eqns_named(jaxpr, "name")]
    for name in kl.ATTN_KEPT:
        assert names.count(name) == layers, name
    operands = {e.params["name"]: e.invars[0].aval.shape
                for e in kept_cases.eqns_named(jaxpr, "name")
                if e.params["name"] in kl.MLA_CAUSAL.kept_operands}
    b, s = tokens.shape
    assert operands == {
        "mla_attn_q": (b * c.heads, s, c.qk_dim),
        "mla_attn_k": (b * c.heads, s, c.qk_dim),
        "mla_attn_v": (b * c.heads, s, c.v_dim)}
    assert input_products(jaxpr, c, tokens.shape) == [layers] * 3
    assert kept_cases.heads_first_transposes(
        jaxpr, tokens.shape + (c.heads, c.qk_dim)) == 2 * layers


def test_activation_bytes_count_what_an_attention_block_keeps(
        attn_form, monkeypatch):
    """The planner is charged every layer's named arrays: the latent, q, k
    and v, and on the kernel path the call's output and log-sum-exp."""
    cfg, batch, length = tiny(), 2, 24
    model = kl.build_kanana_lm(cfg)
    names = kl.ATTN_KEPT if attn_form == "kernels" \
        else kl.ATTN_KEPT[:-len(kl.MLA_CAUSAL.kept)]
    kept = kept_cases.kept_bytes(kl.LatentAttention, model.cfg, names,
                                 batch, length, jnp.float32)

    def acts():
        return [kl.build_kanana_lm(cfg, remat=r).activation_bytes(batch,
                                                                  length)
                for r in (True, False)]

    charged = acts()
    monkeypatch.setattr(kl, "attn_kept_bytes", lambda c, t, item, flash: 0)
    kept_once, plain = (a - b for a, b in zip(charged, acts()))
    assert (kept_once, plain) == (cfg["num_hidden_layers"] * kept, 0)


# ------------------------------------------------------------- the trainer
def _token_cfg(tmp_path, *extra):
    from distributedpytorch_tpu.train import Config, apply_overrides

    cfg = apply_overrides(Config(), [
        "task=tokens", "model.name=kanana_lm", "data.train_batch=8",
        "data.val_batch=8", "data.seq_len=24", "data.token_samples=16",
        "data.token_val_samples=8", "optim.lr=1e-2", "epochs=1",
        "checkpoint.async_save=false", "log_every_steps=1", *extra])
    return dataclasses.replace(cfg, work_dir=str(tmp_path / "runs"))


def test_trainer_fits_two_steps(tmp_path):
    from distributedpytorch_tpu.train import Trainer

    tr = Trainer(_token_cfg(tmp_path))
    try:
        assert tr.task.loss_type == NEXT_TOKEN
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


@pytest.mark.parametrize("over,match", [
    ({"q_lora_rank": 16}, "q_lora_rank"),
    ({"rope_interleave": False}, "rope_interleave"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"scoring_func": "softmax"}, "sigmoid"),
    ({"n_group": 8, "topk_group": 4}, "topk_group"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"qk_head_dim": 32}, "qk_head_dim"),
])
def test_configuration_refuses_what_the_model_does_not_build(over, match):
    with pytest.raises(ValueError, match=match):
        build_model("kanana_lm", lm_config=tiny(**over))


# ------------------------------------------------- the benchmark's config
def _cell_config():
    with open(os.path.join(REPO, "benchmarks", "configs", CELL_CONFIG)) as f:
        return json.load(f)


def test_benchmark_configuration_keeps_every_published_width():
    cfg = _cell_config()
    catalog = {  # the catalog row's ``config``, key for key
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 128, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(cfg["reduced"]) == set(cfg["published"]) == reduced
    for key, value in catalog.items():
        assert (cfg["published"][key] if key in reduced else cfg[key]) \
            == value, key
    assert cfg["n_routed_experts"] * 8 == 128
    assert cfg["vocab_size"] * 8 == 128256
    assert cfg["num_hidden_layers"] == 7
    assert "8 chips share each layer" in cfg["deployment"]
    assert {"rotary_interleave", "score_correction_bias", "optimizer",
            "init", "recomputation", "batch", "held_experts",
            "expert_chunk_rows"} <= set(cfg["assumed"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == cfg["source"]


def test_benchmark_configuration_is_the_stated_cut():
    """1 dense + 6 expert layers + the embedding and head: 799,049,984
    parameters, 9.59 GB at 12 B a parameter."""
    cfg = _cell_config()
    spec = ref.param_spec(cfg)

    def count(tree):
        return sum(int(np.prod(s)) for s, _ in
                   jax.tree.leaves(tree, is_leaf=ref._is_leaf))

    n = count(spec)
    assert n == cfg["parameters"] == 799_049_984
    assert 9.58e9 < 12 * n < 9.60e9
    assert count(spec["l00"]) == 26_345_472 + 512 + 2048   # MLA and its norm
    assert count(spec["l01"]) == 37_748_736 + 2048         # the dense layer
    assert count(spec["l03"]) == 2048 + 262_144 + 128 + 9_437_184 \
        + 16 * 4_718_592                                    # an expert layer
    assert count(spec["embed"]) + count(spec["lm_head"]) \
        == 2 * 16032 * 2048                                 # untied
    model = kl.build_kanana_lm(cfg, dtype=jnp.bfloat16)
    c = model.cfg
    assert (c.experts_total, c.experts_held, c.experts_per_token) == (
        128, 16, 6)
    assert (c.qk_dim, c.v_dim, c.kv_rank, c.shared_hidden) == (
        192, 128, 512, 1536)
    assert c.expert_chunk_rows == 7168 >= 6144 + 3 * 72


def test_benchmark_flops_are_the_formulas():
    cfg = _cell_config()
    terms = ref.flops_per_sequence(cfg, 8192)
    assert cfg["model_flops_per_image"] == int(round(terms["total"]))
    layers = cfg["num_hidden_layers"]
    assert terms["mla_scores"] / layers == \
        2 * 8192 ** 2 / 2 * 32 * (4 * 192 + 3 * 128)
    assert terms["lm_head"] == 6 * 8192 * 2048 * 16032
    assert 3.375e13 < terms["total"] < 3.376e13
    for name, per_token in cfg["model_flops_terms_gflop_per_token"].items():
        assert abs(terms[name] / 8192 / 1e9 - per_token) < 1e-4, name


def test_activation_bytes_follow_the_attention_form_that_runs(monkeypatch):
    from distributedpytorch_tpu.models import danet

    model = kl.build_kanana_lm(_cell_config(), dtype=jnp.bfloat16)
    einsum = model.activation_bytes(1, 8192)
    monkeypatch.setattr(danet, "_on_tpu", lambda: True)
    flash = model.activation_bytes(1, 8192)
    # the einsum form holds 32 heads' float32 (8192, 8192) arrays; the
    # kernel path's attention blocks keep 346.0 MB a layer by name
    kept = 7 * kl.attn_kept_bytes(model.cfg, 8192, 2, True)
    assert 2.4e9 < kept < 2.45e9
    assert einsum > 3 * 32 * 8192 * 8192 * 4 > flash
    assert 1.05e9 < flash - kept < 6e9   # the logits and their gradient too
