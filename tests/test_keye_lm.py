"""``keye_lm`` (models/keye_lm.py) against its plain reference
(benchmarks/reference/keye_lm.py), the learned sparse attention's ops and
kernels, and the model through the step and the trainer.  Tiny widths,
seeded weights, float32, on the CPU.

Tolerances, with their reasons: program and reference both compute in
float32 here, in different forms — the whole row against query blocks, the
grouped product against a masked loop, the kernels' tiles against whole
arrays — so outputs agree to rounding of float32 sums in another order: 2e-5
of the largest value for outputs, 1e-4 of a leaf's largest gradient for
gradients.  The index-score kernel's reverse pass multiplies in bfloat16
(its gradient products, as every gradient product of a bfloat16 program):
the indexer's gradients through it agree to 2^-7.  A wrong term (a key set
off by one key, a missing head, a rotary section swapped) moves them by 1e-2
or more.
"""

import functools
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

from reference import keye_lm as ref  # noqa: E402

import expert_remat_cases as kept_cases  # noqa: E402
from distributedpytorch_tpu.models import (MODEL_TASKS, TOKEN_MODELS,  # noqa: E402
                                           build_model)
from distributedpytorch_tpu.models import keye_lm as kl  # noqa: E402
from distributedpytorch_tpu.models import nemotron_h as nh  # noqa: E402
from distributedpytorch_tpu.ops import attention as attention_ops  # noqa: E402
from distributedpytorch_tpu.ops import pallas_attention as pa  # noqa: E402
from distributedpytorch_tpu.parallel import (NEXT_TOKEN,  # noqa: E402
                                             create_train_state,
                                             make_train_step)
from distributedpytorch_tpu.parallel.step import _loss_and_updates  # noqa: E402
from distributedpytorch_tpu.telemetry import scopes  # noqa: E402

OUT_RTOL, GRAD_RTOL, BF16_GRAD_RTOL = 2e-5, 1e-4, 2.0 ** -7
INDEXER_LEAVES = ("index_q", "index_k", "index_w", "index_k_scale",
                  "index_k_bias")


def tiny(**over):
    return dict(kl.PRESETS["tiny"], **over)


def rel_gap(got, want):
    return float(jnp.abs(got - want).max()) / (float(jnp.abs(want).max())
                                               + 1e-12)


def leaves_with_names(tree):
    return [(jax.tree_util.keystr(p), x)
            for p, x in jax.tree_util.tree_leaves_with_path(tree)]


def assert_trees_close(got, want, rtol, indexer_rtol=None):
    for (name, a), b in zip(leaves_with_names(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        tol = indexer_rtol if indexer_rtol and any(
            x in name for x in INDEXER_LEAVES) else rtol
        assert rel_gap(a, b) <= tol, (name, rel_gap(a, b))


def is_indexer(name: str) -> bool:
    return any(x in name for x in INDEXER_LEAVES)


@pytest.fixture(scope="module")
def whole():
    cfg = tiny()
    model = build_model("keye_lm", lm_config=cfg)
    params = ref.make_weights(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0,
                                cfg["vocab_size"])
    return cfg, model, params, tokens


def program_loss(model, params, tokens, weight=None):
    """The step's own objective: next-token loss + the model's auxiliary
    weight x what it sowed into ``losses``."""
    weight = model.aux_loss_weight if weight is None else weight
    return _loss_and_updates(
        model, params, {}, {"tokens": tokens}, jax.random.PRNGKey(0),
        model.loss_weights, True, NEXT_TOKEN, aux_loss_weight=weight)[0]


@pytest.fixture()
def sparse_kernels(monkeypatch, interpreted_kernels):
    """The Mosaic path forced on the CPU, in the pallas interpreter."""
    from distributedpytorch_tpu.models import danet

    monkeypatch.setattr(danet, "auto_wants_flash", lambda dtype: True)


# ------------------------------------------------- (a) program = reference
def test_parameter_tree_is_the_references(whole):
    cfg, model, params, tokens = whole
    made = model.init(jax.random.PRNGKey(1), tokens)["params"]
    assert jax.tree.map(lambda x: x.shape, made) == \
        jax.tree.map(lambda x: x.shape, params)
    spec = jax.tree.leaves(ref.param_spec(cfg), is_leaf=ref._is_leaf)
    assert [s for s, _ in spec] == [x.shape for x in jax.tree.leaves(made)]


def test_model_loss_and_gradients_equal_the_reference(whole):
    cfg, model, params, tokens = whole
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: program_loss(model, p, tokens))(params)
        want, want_grads = jax.value_and_grad(ref.loss_fn)(params, tokens,
                                                           cfg)
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    assert_trees_close(grads, want_grads, GRAD_RTOL)
    # the indexer's leaves are among them, and they do get a gradient
    names = [n for n, _ in leaves_with_names(grads)]
    assert sum(map(is_indexer, names)) == 5 * cfg["num_hidden_layers"]
    for name, g in leaves_with_names(want_grads):
        assert float(jnp.abs(g).max()) > 0, name


def test_three_steps_follow_the_reference(whole):
    cfg, model, params, tokens = whole
    tx = optax.sgd(1e-2, momentum=0.9)
    opt = {"learning_rate": 1e-2, "momentum": 0.9}
    state = create_train_state(jax.random.PRNGKey(11), model, tx,
                               tokens.shape, input_dtype=jnp.int32)
    state = state.replace(params=params, opt_state=tx.init(params))
    step = make_train_step(model, tx, loss_type=NEXT_TOKEN, donate=False,
                           loss_weights=model.loss_weights,
                           aux_loss_weight=model.aux_loss_weight)
    ref_step = jax.jit(functools.partial(ref.train_step, cfg, opt,
                                         remat=False))
    p, trace = params, jax.tree.map(jnp.zeros_like, params)
    with jax.default_matmul_precision("highest"):
        for _ in range(3):
            state, (loss, counters) = step(state, {"tokens": tokens})
            p, trace, want = ref_step(p, trace, {"tokens": tokens})
            assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    change = jax.tree.map(lambda a, b: a - b, state.params, params)
    want_change = jax.tree.map(lambda a, b: a - b, p, params)
    assert_trees_close(change, want_change, 5e-4)
    assert int(counters["moe_tokens_dropped"]) == 0
    assert float(counters["sparse_attn_keys_over_topk"]) == 0
    selected, causal = ref.pairs(32, cfg["sa_config"]["topk"])
    assert abs(float(counters["sparse_attn_kept_share"])
               - selected / causal) < 1e-6


@pytest.mark.parametrize("fault", ["rows", "drop_routed", "dense_attention",
                                   "drop_align"])
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
    if fault == "drop_align":  # the indexer's leaves then get nothing
        for name, g in leaves_with_names(other[1]):
            assert (float(jnp.abs(g).max()) == 0) == is_indexer(name), name


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


# ---------------------------------------------------------- (b) the share
def _sdar_share():
    """``sdar_lm``'s expert layer is this model's ``GatedMoE`` under its own
    configuration, against its own plain reference (8 shares of 1: the
    benchmark cell's eight-way cut)."""
    from reference import sdar_lm as sdar_ref

    from distributedpytorch_tpu.models import sdar_lm

    return sdar_ref, sdar_lm.LMConfig, dict(sdar_lm.PRESETS["tiny"])


@pytest.mark.parametrize("held,family", [(4, "keye"), (1, "keye"),
                                         (8, "keye"), (1, "sdar")])
def test_expert_shares_add_up_to_the_uncut_layer(held, family):
    """The routed parts that every share of the experts computes (each with
    the router as wide as all of them; nothing is shared by the shares: no
    shared expert) add up to what the uncut reference gives for the whole
    layer; the program's share is the reference's."""
    ref_mod, config_cls, preset = (ref, kl.LMConfig, tiny()) \
        if family == "keye" else _sdar_share()
    uncut = dict(preset, num_experts=8)
    full = ref_mod.make_weights(jax.random.PRNGKey(5), uncut)["l01"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 64))
    x = ref_mod.rms_norm(u, full["norm"], uncut["rms_norm_eps"])
    want = ref_mod.gated_moe(full, x, uncut)
    total = jnp.zeros_like(want)
    for off in range(0, 8, held):
        cfg = dict(preset, num_experts=held, expert_offset=off,
                   published={"num_experts": 8})
        share = dict(full, w1=full["w1"][off:off + held],
                     w2=full["w2"][off:off + held])
        part = ref_mod.gated_moe(share, x, cfg)
        out, _ = kl.GatedMoE(config_cls.from_dict(cfg), jnp.float32).apply(
            {"params": share}, u, mutable=["counters"])
        assert rel_gap(out - u, part) <= OUT_RTOL
        total = total + part
    assert rel_gap(total, want) <= OUT_RTOL
    assert float(jnp.abs(want).max()) > 0.1


# ------------------------------------------------------ (c) the selection
def planted(b, s, seed, share=0.5):
    """Scores of which ``share`` are rounded to halves: many equal."""
    sc = jax.random.normal(jax.random.PRNGKey(seed), (b, s, s))
    tie = jax.random.uniform(jax.random.PRNGKey(seed + 1), sc.shape) < share
    return jnp.where(tie, jnp.round(sc * 2) / 2, sc)


@pytest.mark.parametrize("form", ["threshold", "kernel"])
@pytest.mark.parametrize("b,s,topk", [(2, 64, 8), (2, 200, 37), (1, 384, 100),
                                      (1, 1300, 300), (2, 40, 1)])
def test_threshold_forms_keep_top_ks_set(form, b, s, topk):
    scores = planted(b, s, seed=s)
    want = attention_ops.topk_keep(scores, topk)
    got = attention_ops.threshold_keep(scores, topk) if form == "threshold" \
        else pa.flash_topk_keep(scores, topk, interpret=True) != 0
    np.testing.assert_array_equal(got, want)
    # exactly min(t + 1, topk) keys a row, all of them causal
    np.testing.assert_array_equal(
        want.sum(-1), jnp.broadcast_to(attention_ops.keys_wanted(s, topk),
                                       (b, s)))
    assert not bool(jnp.triu(want, 1).any())


@pytest.mark.parametrize("form", ["topk", "threshold", "kernel"])
def test_all_equal_scores_keep_the_first_keys(form):
    scores = jnp.zeros((1, 48, 48))
    keep = {"topk": attention_ops.topk_keep,
            "threshold": attention_ops.threshold_keep,
            "kernel": lambda s, k: pa.flash_topk_keep(
                s, k, interpret=True) != 0}[form](scores, 5)
    pos = jnp.arange(48)
    np.testing.assert_array_equal(
        keep[0], (pos[None, :] <= pos[:, None]) & (pos[None, :] < 5))


@pytest.mark.parametrize("form", ["topk", "threshold", "kernel"])
def test_topk_at_least_the_sequence_keeps_every_causal_key(form):
    scores = planted(2, 24, seed=7)
    keep = {"topk": attention_ops.topk_keep,
            "threshold": attention_ops.threshold_keep,
            "kernel": lambda s, k: pa.flash_topk_keep(
                s, k, interpret=True) != 0}[form](scores, 24)
    np.testing.assert_array_equal(
        keep, jnp.broadcast_to(jnp.tril(jnp.ones((24, 24), bool)),
                               keep.shape))


def test_block_with_every_key_kept_is_causal_attention_bit_for_bit():
    """``topk >= S``: the attention block is ``ops/attention.py::
    causal_attention`` on the same q, k, v, bit for bit in float32."""
    cfg = kl.LMConfig.from_dict(tiny(
        sa_config=dict(kl.PRESETS["tiny"]["sa_config"], topk=64)))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64))
    block = kl.SparseAttention(cfg, jnp.float32)
    params = block.init(jax.random.PRNGKey(3), u)["params"]
    seen = {}
    dense = attention_ops.causal_attention

    def spy(q, k, v, keep=None):
        seen["qkv"] = (q, k, v)
        seen["out"] = dense(q, k, v, keep)
        return seen["out"]

    attention_ops.causal_attention = spy
    try:
        out, sown = block.apply({"params": params}, u,
                                mutable=["losses", "counters"])
    finally:
        attention_ops.causal_attention = dense
    np.testing.assert_array_equal(seen["out"], dense(*seen["qkv"]))
    assert float(sown["counters"][kl.COUNTER_KEPT_SHARE][0]) == 1.0
    want = u + seen["out"].reshape(2, 32, -1) @ params["o_proj"]
    assert rel_gap(out, want) <= OUT_RTOL


def test_counters_read_the_closed_forms(whole):
    cfg, model, params, tokens = whole
    _, sown = model.apply({"params": params}, tokens, train=True,
                          mutable=["losses", "counters"])
    selected, causal = ref.pairs(32, 8)
    assert (selected, causal) == (8 * 9 // 2 + 24 * 8, 32 * 33 // 2)
    for block in ("l00", "l02"):
        c = sown["counters"][block]
        assert abs(float(c[kl.COUNTER_KEPT_SHARE][0])
                   - selected / causal) < 1e-6
        assert float(c[kl.COUNTER_OVER_TOPK][0]) == 0
    assert ref.pairs(8192, 2048) == (14681088, 33558528)


# ------------------------------------- (d) the gradient's separation
def test_alignment_loss_trains_the_indexer_and_nothing_else(whole):
    cfg, model, params, tokens = whole
    grads = {w: jax.grad(lambda p: program_loss(model, p, tokens, w))(params)
             for w in (0.0, 1.0, 3.0)}
    for (name, g0), g1, g3 in zip(leaves_with_names(grads[0.0]),
                                  jax.tree.leaves(grads[1.0]),
                                  jax.tree.leaves(grads[3.0])):
        if is_indexer(name):
            assert float(jnp.abs(g0).max()) == 0, name
            assert float(jnp.abs(g1).max()) > 0, name
            assert rel_gap(g3, 3 * g1) <= 1e-5, name
        else:  # no main parameter's gradient moves with the weight
            np.testing.assert_array_equal(g0, g1, err_msg=name)
            np.testing.assert_array_equal(g0, g3, err_msg=name)


def test_model_states_its_auxiliary_weight(whole):
    cfg, model, *_ = whole
    assert model.aux_loss_weight == 1.0 and model.loss_weights == (1.0,)
    assert build_model("keye_lm", lm_config=tiny(
        index_align_loss_weight=0.25)).aux_loss_weight == 0.25


# ------------------------------------------------------------- (e) M-RoPE
def test_mrope_with_equal_rows_is_nemotrons_rope():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 4, 16))
    ang = kl.rotary_angles(kl.text_positions(2, 24), 8, 10000.0, (2, 3, 3))
    np.testing.assert_array_equal(kl.rotate(x, ang), nh.rope(x, 10000.0))


@pytest.mark.parametrize("section", [(2, 3, 3), (8, 0, 0), (1, 1, 6)])
def test_mrope_with_unequal_rows_is_the_references(section):
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 4, 16))
    pos = jax.random.randint(jax.random.PRNGKey(5), (3, 2, 24), 0, 500)
    got = kl.rotate(x, kl.rotary_angles(pos, 8, 10000.0, section))
    want = ref.rotary(x, pos, 10000.0, section)
    assert rel_gap(got, want) <= 1e-6
    # pair i turns by ITS section's row: moving another row changes nothing
    # of the first section's pairs
    moved = kl.rotate(x, kl.rotary_angles(pos.at[1:].add(7), 8, 10000.0,
                                          section))
    first = np.r_[0:section[0], 8:8 + section[0]]
    np.testing.assert_array_equal(moved[..., first], got[..., first])
    if section[0] < 8:
        assert rel_gap(moved, got) > 1e-2


def test_model_takes_three_position_rows(whole):
    cfg, model, params, tokens = whole
    pos = jax.random.randint(jax.random.PRNGKey(8), (3, 2, 32), 0, 90)
    got = model.apply({"params": params}, tokens, positions=pos)[0]
    want, _ = ref.forward(params, tokens, cfg, positions=pos)
    assert rel_gap(got, want) <= OUT_RTOL
    text = model.apply({"params": params}, tokens)[0]
    np.testing.assert_array_equal(
        text, model.apply({"params": params}, tokens,
                          positions=kl.text_positions(2, 32))[0])
    assert rel_gap(got, text) > 1e-3


# --------------------------------- (f) the kernels, in the interpreter
def heads(s, q_heads, kv_heads, hd=16, b=2, seed=0):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(b, s, h, hd).astype(np.float32))
                 for h in (q_heads, kv_heads, kv_heads))


@pytest.fixture()
def small_tiles(monkeypatch, request):
    schedule = getattr(request, "param", "fused")
    monkeypatch.setattr(pa, "_CAUSAL_TILE", (128, 128))
    monkeypatch.setattr(pa, "_INDEXER_TILE", (128, 128))
    monkeypatch.setattr(pa, "_bwd_plan",
                        lambda n, ck: (128, schedule == "fused"))
    return schedule


@pytest.mark.parametrize("small_tiles", ["fused", "two_sweeps"],
                         indirect=True)
@pytest.mark.parametrize("s,topk", [(64, 9), (256, 40), (300, 77)])
@pytest.mark.parametrize("q_heads,kv_heads", [(2, 2), (4, 1)])
def test_sparse_kernels_match_the_einsum_form(small_tiles, s, topk, q_heads,
                                              kv_heads):
    q, k, v = heads(s, q_heads, kv_heads, seed=s)
    keep = attention_ops.topk_keep(planted(2, s, seed=3), topk)
    keep8 = keep.astype(jnp.int8)

    def flash(q, k, v):
        return pa.flash_sparse_attention(q, k, v, keep8, interpret=True)[0]

    def einsum(q, k, v):
        return attention_ops.causal_attention(q, k, v, keep)

    np.testing.assert_allclose(flash(q, k, v), einsum(q, k, v), rtol=2e-5,
                               atol=2e-6)
    g = jax.random.normal(jax.random.PRNGKey(1), q.shape)
    got = jax.grad(lambda *a: (flash(*a) * g).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (einsum(*a) * g).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert rel_gap(a, b) <= GRAD_RTOL
    _, lse = pa.flash_sparse_attention(q, k, v, keep8, interpret=True)
    p = pa.flash_head_mean_probs(q, k, keep8, lse, interpret=True)
    np.testing.assert_allclose(p, attention_ops.head_mean_probs(q, k, keep),
                               rtol=2e-5, atol=2e-7)
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-5)


def test_a_row_whose_first_tile_holds_no_kept_key(small_tiles):
    """The running max starts below every score: a tile in which a row
    keeps nothing leaves rubbish that its first kept key rescales away."""
    s = 256
    q, k, v = heads(s, 4, 1, seed=9)
    pos = jnp.arange(s)
    late = (pos[None, :] >= 128) | (pos[:, None] < 128)
    keep = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)) & late,
                            (2, s, s))
    got = pa.flash_sparse_attention(q, k, v, keep.astype(jnp.int8),
                                    interpret=True)[0]
    np.testing.assert_allclose(
        got, attention_ops.causal_attention(q, k, v, keep), rtol=2e-5,
        atol=2e-6)


def test_sparse_calls_are_named_and_shared_by_a_sequences_heads(small_tiles):
    q, k, v = heads(300, 4, 1)
    keep = attention_ops.topk_keep(planted(2, 300, 1), 50).astype(jnp.int8)

    def loss(q, k, v):
        out, lse = pa.flash_sparse_attention(q, k, v, keep, interpret=True)
        p = pa.flash_head_mean_probs(q, k, keep, lse, interpret=True)
        return (out ** 2).sum() + p.sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v))
    for name in (scopes.SPARSE_ATTN, scopes.SPARSE_ATTN_BWD_FUSED,
                 scopes.SPARSE_ATTN_PROBS):
        assert f"name={name}\n" in text or f"name={name} " in text, name
    assert "causal_attn" not in text and "pam" not in text


@pytest.mark.parametrize("s", [64, 256, 300])
def test_indexer_kernels_match_the_einsum_form(small_tiles, s):
    r = np.random.RandomState(s)
    qi = jnp.asarray(r.randn(2, s, 4, 8).astype(np.float32))
    ki = jnp.asarray(r.randn(2, s, 8).astype(np.float32))
    w = jnp.asarray(r.randn(2, s, 4).astype(np.float32))
    causal = jnp.tril(jnp.ones((s, s), bool))
    want = attention_ops.indexer_scores(qi, ki, w)
    got = pa.flash_indexer_scores(qi, ki, w, True)
    assert rel_gap(jnp.where(causal, got, 0), jnp.where(causal, want, 0)) \
        <= OUT_RTOL
    g = jnp.where(causal, jnp.asarray(r.randn(2, s, s).astype(np.float32)), 0)
    grads = jax.grad(lambda *a: (pa.flash_indexer_scores(*a, True) * g).sum(),
                     (0, 1, 2))(qi, ki, w)
    wants = jax.grad(lambda *a: (attention_ops.indexer_scores(*a) * g).sum(),
                     (0, 1, 2))(qi, ki, w)
    # dq, dk: bfloat16 products; dw: float32
    for a, b, tol in zip(grads, wants, (BF16_GRAD_RTOL, BF16_GRAD_RTOL,
                                        GRAD_RTOL)):
        assert rel_gap(a, b) <= tol


def test_kernel_path_gives_the_einsum_models_loss_and_gradients(
        whole, sparse_kernels, monkeypatch):
    cfg, model, params, tokens = whole
    calls = []
    for name in ("flash_indexer_scores", "flash_topk_keep",
                 "flash_sparse_attention", "flash_head_mean_probs"):
        fn = getattr(pa, name)
        monkeypatch.setattr(pa, name, lambda *a, _f=fn, _n=name, **kw: (
            calls.append(_n), _f(*a, **kw))[1])
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: program_loss(model, p, tokens))(params)
        want, want_grads = jax.value_and_grad(ref.loss_fn)(params, tokens,
                                                           cfg)
    assert set(calls) == {"flash_indexer_scores", "flash_topk_keep",
                          "flash_sparse_attention", "flash_head_mean_probs"}
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    assert_trees_close(grads, want_grads, GRAD_RTOL,
                       indexer_rtol=BF16_GRAD_RTOL)


# ------------- the alignment loss takes its gradient in the forward pass
ALIGN_FORMS = {
    "einsum": kl._einsum_scores_grads,
    "kernel": functools.partial(pa.flash_indexer_scores_grads,
                                interpret=True)}


def align_case(s, topk, seed=0):
    """The indexer's operands, its scores, a key set and a target that sums
    to one on the set and is zero on a third of it."""
    r = np.random.RandomState(seed)
    qi = jnp.asarray(r.randn(2, s, 4, 8).astype(np.float32))
    ki = jnp.asarray(r.randn(2, s, 8).astype(np.float32))
    wi = jnp.asarray(r.randn(2, s, 4).astype(np.float32))
    scores = attention_ops.indexer_scores(qi, ki, wi)
    keep = attention_ops.topk_keep(scores, topk)
    p = jnp.where(keep & jnp.asarray(r.rand(2, s, s) > 1 / 3)
                  | jnp.eye(s, dtype=bool), jnp.asarray(r.rand(2, s, s)), 0)
    p = jnp.where(keep, p, 0).astype(jnp.float32)
    return qi, ki, wi, scores, p / p.sum(-1, keepdims=True), keep


@pytest.mark.parametrize("form", sorted(ALIGN_FORMS))
@pytest.mark.parametrize("s,topk", [(64, 9), (300, 77)])
def test_alignment_loss_is_the_kl_and_its_gradient(small_tiles, form, s,
                                                   topk):
    """``index_align_loss`` is ``index_align_kl`` of the scores, to the bit,
    and its gradient that of ``index_align_kl ∘ indexer_scores``; the
    cotangent only scales it."""
    qi, ki, wi, scores, p, keep = align_case(s, topk, seed=s)
    grads_of = ALIGN_FORMS[form]

    def loss(qi, ki, wi, weight=1.0):
        return weight * kl.index_align_loss(grads_of, qi, ki, wi, scores, p,
                                            keep)

    def plain(qi, ki, wi):
        return kl.index_align_kl(
            p, attention_ops.indexer_scores(qi, ki, wi), keep)

    want = kl.index_align_kl(p, scores, keep)
    assert float(want) > 0.1
    np.testing.assert_array_equal(loss(qi, ki, wi), want)
    value, got = jax.value_and_grad(loss, (0, 1, 2))(qi, ki, wi)
    np.testing.assert_array_equal(value, want)
    wants = jax.grad(plain, (0, 1, 2))(qi, ki, wi)
    tols = (GRAD_RTOL,) * 3 if form == "einsum" else (
        BF16_GRAD_RTOL, BF16_GRAD_RTOL, GRAD_RTOL)
    for a, b, tol in zip(got, wants, tols):
        assert float(jnp.abs(b).max()) > 0 and rel_gap(a, b) <= tol
    none = jax.grad(loss, (0, 1, 2))(qi, ki, wi, 0.0)
    thrice = jax.grad(loss, (0, 1, 2))(qi, ki, wi, 3.0)
    for g0, g1, g3 in zip(none, got, thrice):
        assert float(jnp.abs(g0).max()) == 0
        assert rel_gap(g3, 3 * g1) <= 1e-5


def calls_named(text, name):
    return text.count(f"name={name}\n") + text.count(f"name={name} ")


@pytest.fixture(params=["einsum", "kernel"])
def remat_block(request, monkeypatch):
    """A rematerialised attention block as ``KeyeLM`` wraps it, its
    parameters and input, a loss over its output and what it sowed, and the
    calls of the index scores' reverse pass that tracing it made."""
    from flax import linen as nn

    if request.param == "kernel":
        request.getfixturevalue("small_tiles")
        request.getfixturevalue("sparse_kernels")
        owner, name = pa, "flash_indexer_scores_grads"
    else:
        owner, name = kl, "_einsum_scores_grads"
    calls, fn = [], getattr(owner, name)
    monkeypatch.setattr(owner, name, lambda *a, **kw: (
        calls.append(name), fn(*a, **kw))[1])
    cfg = kl.LMConfig.from_dict(tiny())
    block = nn.remat(kl.SparseAttention, policy=kl._KEEP_SPARSE_RESIDUALS)(
        cfg, jnp.float32)
    # 48 positions: no width of the preset, so a shape names its array
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 64))
    params = block.init(jax.random.PRNGKey(3), u)["params"]
    del calls[:]

    def loss(params, u):
        out, sown = block.apply({"params": params}, u,
                                mutable=["losses", "counters"])
        return (out ** 2).sum() + sum(
            x.sum() for x in jax.tree.leaves(sown["losses"]))

    return request.param, loss, params, u, calls


def test_replay_recomputes_neither_scores_nor_target(remat_block):
    """The gradient of a rematerialised block holds each Mosaic call once,
    and the replay inside it no index score, no target and no softmax over
    an (S, S) array: what the alignment loss's reverse pass reads was kept."""
    form, loss, params, u, calls = remat_block
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, u)
    assert len(calls) == 1
    replay, = kept_cases.eqns_named(jaxpr.jaxpr, "remat2")
    assert replay.params["differentiated"]
    inside, whole = str(replay.params["jaxpr"]), str(jaxpr)
    if form == "kernel":
        for name in (scopes.INDEXER_SCORES, scopes.SPARSE_ATTN_PROBS,
                     scopes.INDEXER_SCORES_BWD, scopes.TOPK_KEEP,
                     scopes.SPARSE_ATTN, scopes.SPARSE_ATTN_BWD_FUSED):
            assert calls_named(whole, name) == 1, name
            assert calls_named(inside, name) == (
                name == scopes.SPARSE_ATTN_BWD_FUSED), name
        assert "f32[2,48,48]" in whole and "f32[2,48,48]" not in inside
    else:   # the indexer's (B, J, S, S) products, and the selection's sort
        assert "f32[2,4,48,48]" in whole and "top_k" in whole
        assert "f32[2,4,48,48]" not in inside and "top_k" not in inside
    assert "log_softmax" in whole and "log_softmax" not in inside
    for name in kl.INDEX_GRADS_KEPT:
        assert whole.count(f"name[name={name}]") == 1
        assert f"name[name={name}]" not in inside


def test_a_call_that_is_not_differentiated_runs_no_reverse_pass(
        remat_block, whole):
    form, loss, params, u, calls = remat_block
    text = str(jax.make_jaxpr(loss)(params, u))
    assert calls == [] and scopes.INDEXER_SCORES_BWD not in text
    if form == "kernel":
        assert calls_named(text, scopes.INDEXER_SCORES) == 1
    cfg, model, weights, tokens = whole
    text = str(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, tokens, mutable=["losses", "counters"]))(weights))
    assert calls == [] and scopes.INDEXER_SCORES_BWD not in text
    jax.make_jaxpr(jax.grad(lambda p: program_loss(model, p, tokens)))(
        weights)
    assert len(calls) == cfg["num_hidden_layers"]


def test_existing_calls_keep_their_kernels():
    """DANet's and the causal calls trace to the kernels they had: no key
    set among their operands, their names unchanged."""
    q, k, v = heads(300, 4, 1)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: (pa.flash_causal_attention(*a, interpret=True) ** 2).sum(),
        (0, 1, 2)))(q, k, v))
    assert "name=causal_attn" in text and "sparse" not in text
    assert "i8[" not in text


# ------------------------------------------------ scopes and the registry
def test_scope_table_holds_the_new_parts(whole):
    cfg, model, params, tokens = whole
    tx = optax.sgd(1e-2, momentum=0.9)
    state = create_train_state(jax.random.PRNGKey(11), model, tx,
                               tokens.shape, input_dtype=jnp.int32)
    step = make_train_step(model, tx, loss_type=NEXT_TOKEN, donate=False,
                           aux_loss_weight=model.aux_loss_weight)
    table = scopes.scope_table(
        step.lower(state, {"tokens": tokens}).compile().as_text())
    parts = {(s.layer, part, s.phase) for s in table.values()
             for part in s.path.split("/")[1:]}
    for part in (scopes.ATTN_INDEXER, scopes.ATTN_TOPK_SELECT,
                 scopes.ATTN_INDEX_ALIGN):
        assert (scopes.ATTN, part, "fwd") in parts, part
    # the alignment loss's reverse pass scales three kept arrays, inside
    # the fusions of the indexer's: no instruction of its own
    assert (scopes.ATTN, scopes.ATTN_INDEXER, "bwd") in parts
    assert (scopes.ATTN, scopes.ATTN_INDEX_ALIGN, "bwd") not in parts
    for part in (scopes.MOE_ROUTER, scopes.MOE_DISPATCH,
                 scopes.MOE_ROUTED_EXPERTS, scopes.MOE_COMBINE):
        assert (scopes.MOE, part, "fwd") in parts, part
    assert {scopes.EMBED, scopes.ATTN, scopes.MOE, scopes.LM_HEAD,
            scopes.LOSS, scopes.OPTIMIZER} <= {s.layer for s in
                                               table.values()}
    assert not any("KeyeLM" in s.path for s in table.values())
    # blocks alternate: even ones attention, odd ones expert layers
    assert any(s.path.startswith("attn/l02") for s in table.values())
    assert any(s.path.startswith("moe/l03") for s in table.values())
    assert not any(s.path.startswith("moe/l02") for s in table.values())
    # no scope's name is another's path element by accident
    names = [scopes.ATTN_INDEXER, scopes.ATTN_TOPK_SELECT,
             scopes.ATTN_INDEX_ALIGN, scopes.INDEXER_SCORES,
             scopes.INDEXER_SCORES_BWD, scopes.TOPK_KEEP,
             scopes.SPARSE_ATTN, scopes.SPARSE_ATTN_PROBS]
    assert len(set(names)) == len(names)


def test_token_models_are_one_table():
    assert set(TOKEN_MODELS) == {"nemotron_h", "keye_lm", "sdar_lm"}
    assert MODEL_TASKS == {name: ("tokens",) for name in TOKEN_MODELS}
    assert isinstance(build_model("keye_lm"), kl.KeyeLM)
    assert isinstance(build_model("nemotron_h"), nh.NemotronH)
    with pytest.raises(ValueError, match="nemotron_h | keye_lm"):
        build_model("danet", lm_config="tiny")
    with pytest.raises(ValueError, match="keye_lm"):
        build_model("no_such_model")
    with pytest.raises(ValueError, match="neither a preset"):
        build_model("keye_lm", lm_config="no_such_preset")
    with pytest.raises(ValueError, match="mrope_section"):
        build_model("keye_lm", lm_config=tiny(
            rope_scaling={"mrope_section": [2, 2, 2]}))


# ------------------------------------------------------------- the trainer
def _token_cfg(tmp_path, *extra):
    import dataclasses

    from distributedpytorch_tpu.train import Config, apply_overrides

    cfg = apply_overrides(Config(), [
        "task=tokens", "model.name=keye_lm", "data.train_batch=8",
        "data.val_batch=8", "data.seq_len=24", "data.token_samples=16",
        "data.token_val_samples=8", "optim.lr=1e-2", "epochs=1",
        "checkpoint.async_save=false", "log_every_steps=1", *extra])
    return dataclasses.replace(cfg, work_dir=str(tmp_path / "runs"))


def test_trainer_fits_two_steps_and_logs_the_counters(tmp_path):
    from distributedpytorch_tpu.train import Trainer

    tr = Trainer(_token_cfg(tmp_path))
    try:
        assert tr._step_kwargs["aux_loss_weight"] == 1.0
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
    assert flat["train/sparse_attn_keys_over_topk"] == 0
    selected, causal = ref.pairs(24, 8)
    assert abs(flat["train/sparse_attn_kept_share"]
               - selected / causal) < 1e-6


def test_image_models_keep_their_own_auxiliary_rule(tmp_path):
    """The trainer takes the model's ``aux_loss_weight`` only where the
    configuration's capacity MoE is off; a model that states none gets 0."""
    import dataclasses

    from distributedpytorch_tpu.train import Config, Trainer, apply_overrides

    cfg = apply_overrides(Config(), [
        "data.fake=true", "data.train_batch=8", "data.val_batch=2",
        "data.crop_size=[32,32]", "data.relax=10", "data.area_thres=0",
        "model.backbone=resnet18", "model.output_stride=8",
        "checkpoint.async_save=false", "epochs=1"])
    tr = Trainer(dataclasses.replace(cfg, work_dir=str(tmp_path / "runs")))
    try:
        assert tr._step_kwargs["aux_loss_weight"] == 0.0
    finally:
        tr.close()


# ------------------------------------------------- the benchmark's config
def _cell_config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "keye_vl2_30b_a3b_lm_stage_ep8.json")) as f:
        return json.load(f)


def test_benchmark_configuration_keeps_every_published_width():
    cfg = _cell_config()
    widths = {"hidden_size": 2048, "head_dim": 128,
              "num_attention_heads": 32, "num_key_value_heads": 4,
              "moe_intermediate_size": 768, "num_experts_per_tok": 8,
              "intermediate_size": 6144, "rope_theta": 10000000,
              "rms_norm_eps": 1e-06, "norm_topk_prob": True}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "num_local_experts": 128,
                                "vocab_size": 151936}
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["num_experts"] * 8 == 128 and cfg["vocab_size"] * 8 == 151936
    assert 6 <= cfg["num_hidden_layers"] <= 8
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert entry["source"] == cfg["source"]


def test_benchmark_flops_are_the_formulas():
    cfg = _cell_config()
    terms = ref.flops_per_sequence(cfg, 8192)
    assert cfg["model_flops_per_image"] == int(round(terms["total"]))
    layers = cfg["num_hidden_layers"]
    assert terms["attn_scores"] / layers == 7 * 2 * 14681088 * 128 * 32
    assert terms["index_scores"] / layers == 3 * 2 * 33558528 * 64 * 16
    assert 2.0e13 < terms["total"] * 8 / layers < 2.1e13
    for name, per_token in cfg["model_flops_terms_gflop_per_token"].items():
        assert abs(terms[name] / 8192 / 1e9 - per_token) < 1e-4, name


def test_benchmark_configuration_is_the_issues_cut():
    cfg = _cell_config()
    leaves = jax.tree.leaves(ref.param_spec(cfg), is_leaf=ref._is_leaf)
    n = sum(int(np.prod(s)) for s, _ in leaves)
    per_layer = sum(int(np.prod(s)) for block in ("l00", "l01")
                    for s, _ in jax.tree.leaves(ref.param_spec(cfg)[block],
                                                is_leaf=ref._is_leaf))
    assert 96.8e6 < per_layer < 97.0e6
    assert abs(n - (per_layer * cfg["num_hidden_layers"]
                    + 2 * 2048 * 18992 + 2048)) == 0
    assert n * 12 < 10.3e9
    model = kl.build_keye_lm(cfg, dtype=jnp.bfloat16)
    assert model.cfg.experts_total == 128 and model.cfg.experts_held == 16
    assert model.cfg.topk == 2048 and model.cfg.mrope_section == (16, 24, 24)


def test_activation_bytes_follow_the_attention_form_that_runs(monkeypatch):
    from distributedpytorch_tpu.models import danet

    model = kl.build_keye_lm(_cell_config(), dtype=jnp.bfloat16)
    einsum = model.activation_bytes(1, 8192)
    monkeypatch.setattr(danet, "_on_tpu", lambda: True)
    flash = model.activation_bytes(1, 8192)
    # the einsum forms hold 48 heads' float32 (8192, 8192) arrays
    assert einsum > 3 * 48 * 8192 * 8192 * 4 > 8 * flash
    assert 1e9 < flash < 6e9
    # what a block keeps of the alignment loss: the float32 gradients of the
    # indexer's queries, key and head weights, every layer's
    wider = dict(_cell_config())
    wider["sa_config"] = dict(wider["sa_config"], indexer_num_heads=24)
    more = kl.build_keye_lm(wider, dtype=jnp.bfloat16).activation_bytes(
        1, 8192)
    layers = model.cfg.layers
    assert more - flash == layers * 8192 * 8 * (64 + 1) * 4
    assert flash > layers * 8192 * (16 * 64 + 64 + 16) * 4 > 0.25e9


@pytest.mark.parametrize("expected,want", [(8192, 5120), (4096, 5120),
                                           (2816, 4096), (48, 1024),
                                           (20000, 5120), (65536, 5120)])
def test_expert_chunks_hold_the_expected_rows_and_a_quarter_more(expected,
                                                                 want):
    """The trip count of the expert layer's chunk loop does not sit on a
    chunk's edge: the rows expected under even routing, and a quarter more,
    fill a whole number of chunks."""
    from distributedpytorch_tpu.parallel import moe as moe_lib

    rows = kl.expert_chunk_rows(expected)
    assert rows == want and rows % 1024 == 0
    chunks = -(-expected // rows)
    assert chunks <= max(1, -(-expected // moe_lib.CHUNK_ROWS))
    if expected <= 2 * moe_lib.CHUNK_ROWS:  # a few chunks: none of them
        # begins between the expected rows and a quarter more
        assert -(-int(1.25 * expected) // rows) == chunks


# ------------------------------- what a rematerialised expert block keeps
@pytest.mark.parametrize("policy,runs", [("kept", 1), ("bare", 2)])
def test_replay_of_an_expert_block_holds_no_product_or_selection(policy,
                                                                 runs):
    """The gradient of a block that keeps ``EXPERT_KEPT`` runs the router's
    product and the top-k once, under a bare ``nn.remat`` twice; the routed
    sum feeds the block's output alone, so the forward chunk loop is dead
    in the replay either way."""
    cfg = kl.LMConfig.from_dict(tiny())
    got = kept_cases.replay_counts(
        *kept_cases.block_case(
            kl.GatedMoE, cfg,
            kl.KEEP_EXPERT_RESIDUALS if policy == "kept" else None),
        widths=(cfg.experts_total,), k=cfg.experts_per_token)
    # no gather either way: the chosen gates are the top-k's own values
    assert got == {"top_k": runs, "gathers": 0, "products": [runs],
                   "forward_loops": 1}


def test_model_rematerialises_its_expert_blocks_under_the_policy():
    """The whole model's gradient holds one router product a layer (a
    router 12 wide: no other weight of the preset is)."""
    cfg = tiny(published={"num_experts": 12})
    model = build_model("keye_lm", lm_config=cfg)
    tokens = jnp.zeros((2, 20), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            tokens)["params"]
    assert model.remat
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: program_loss(model, p, tokens)))(params).jaxpr
    assert kept_cases.forward_products(
        jaxpr, tokens.size, cfg["hidden_size"], 12) \
        == cfg["num_hidden_layers"]


def test_kept_expert_block_gives_the_bare_blocks_loss_and_gradients():
    kept_cases.assert_kept_block_is_the_bare_blocks(
        kl.GatedMoE, kl.LMConfig.from_dict(tiny()),
        kl.KEEP_EXPERT_RESIDUALS, GRAD_RTOL)


def test_activation_bytes_count_what_an_expert_block_keeps(monkeypatch):
    """The planner is charged every layer's named arrays: float32 logits,
    the top-k's gates and ids, the rows and the group sizes."""
    cfg, batch, length = tiny(), 2, 24
    model = kl.build_keye_lm(cfg)
    kept = kept_cases.kept_bytes(kl.GatedMoE, model.cfg, kl.EXPERT_KEPT,
                                 batch, length, jnp.float32)
    tokens = batch * length
    assert kept == kl.expert_kept_bytes(model.cfg, tokens) \
        == 4 * (tokens * (8 + 2 * 2) + tokens * 2 + 4)

    def acts():
        return [kl.build_keye_lm(cfg, remat=r).activation_bytes(batch, length)
                for r in (True, False)]

    charged = acts()
    monkeypatch.setattr(kl, "expert_kept_bytes", lambda c, t: 0)
    kept_once, plain = (a - b for a, b in zip(charged, acts()))
    assert (kept_once, plain) == (cfg["num_hidden_layers"] * kept, 0)
