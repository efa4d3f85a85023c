"""``sdar_lm`` (models/sdar_lm.py) against its plain reference
(benchmarks/reference/sdar_lm.py); the block-diffusion mask as an einsum
form and as a rule of the flash kernels; the noise as data of the token
task's device stage; the loss as an entry of the step's table; the model
through the step and the trainer.  Tiny widths, seeded weights, on the CPU.

Tolerances, with their reasons: program and reference both compute in
float32 in different forms — the whole row against query blocks, the grouped
product against a masked loop, the kernels' tiles against whole arrays — so
outputs agree to rounding of float32 sums in another order: 2e-5 of the
largest value for outputs, 1e-4 of a leaf's largest gradient for gradients.
Under the bfloat16 policy the program rounds every product's operands to 8
bits of mantissa where the reference holds 24: losses agree to 1e-2, a
leaf's gradient norm to 3e-2 (the reference computed in bfloat16 reads the
same).  A wrong term (the plain triangle in the rule's place, a weight left
out, a block off by one) moves them by 1e-1 or more.
"""

import dataclasses
import functools
import gzip
import itertools
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

from reference import nets  # noqa: E402
from reference import sdar_lm as ref  # noqa: E402

import expert_remat_cases as kept_cases  # noqa: E402
import flash_jaxpr_cases  # noqa: E402
from distributedpytorch_tpu.models import build_model  # noqa: E402
from distributedpytorch_tpu.models import sdar_lm as sl  # noqa: E402
from distributedpytorch_tpu.ops import attention as attention_ops  # noqa: E402
from distributedpytorch_tpu.ops import diffusion  # noqa: E402
from distributedpytorch_tpu.ops import pallas_attention as pa  # noqa: E402
from distributedpytorch_tpu.ops import weighted_token_xent  # noqa: E402
from distributedpytorch_tpu.parallel import (BLOCK_DIFFUSION,  # noqa: E402
                                             NEXT_TOKEN,
                                             create_train_state,
                                             make_eval_step, make_train_step)
from distributedpytorch_tpu.parallel import step as step_lib  # noqa: E402
from distributedpytorch_tpu.telemetry import scopes  # noqa: E402
from distributedpytorch_tpu.train.precision import precision_policy  # noqa: E402

OUT_RTOL, GRAD_RTOL = 2e-5, 1e-4
MASKS = [(16, 4), (64, 4), (96, 8), (40, 4)]


def tiny(**over):
    return dict(sl.PRESETS["tiny"], **over)


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
    model = build_model("sdar_lm", lm_config=cfg)
    params = ref.make_weights(jax.random.PRNGKey(3), cfg)
    batch = ref.make_batch(jax.random.PRNGKey(0), cfg, 2, 32)
    return cfg, model, params, batch


def program_loss(model, params, batch, precision=None):
    """The step's own objective, with what the step hands back beside it."""
    loss, _, counters = step_lib._loss_and_updates(
        model, params, {}, batch, jax.random.PRNGKey(0), model.loss_weights,
        True, BLOCK_DIFFUSION, precision=precision)
    return loss, counters


def brute_force_mask(length, block):
    m = np.zeros((2 * length, 2 * length), bool)
    for t, s in itertools.product(range(2 * length), repeat=2):
        ct, cs = t < length, s < length
        bt, bs = (t % length) // block, (s % length) // block
        m[t, s] = (cs and ct and bs <= bt) or (cs and not ct and bs < bt) \
            or (not cs and not ct and bs == bt)
    return m


# ------------------------------------------------- (a) program = reference
def test_parameter_tree_is_the_references(whole):
    cfg, model, params, batch = whole
    made = model.init(jax.random.PRNGKey(1), batch["tokens"])["params"]
    assert jax.tree.map(lambda x: x.shape, made) == \
        jax.tree.map(lambda x: x.shape, params)
    spec = jax.tree.leaves(ref.param_spec(cfg), is_leaf=ref._is_leaf)
    assert [s for s, _ in spec] == [x.shape for x in jax.tree.leaves(made)]


def test_model_loss_and_gradients_equal_the_reference(whole):
    cfg, model, params, batch = whole
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda p: program_loss(model, p, batch), has_aux=True)(params)
        want, want_grads = jax.value_and_grad(ref.loss_fn)(params, batch,
                                                           cfg)
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    assert_trees_close(grads, want_grads, GRAD_RTOL)
    for name, g in leaves_with_names(want_grads):
        assert float(jnp.abs(g).max()) > 0, name


def test_logits_are_over_the_noised_half_alone(whole):
    cfg, model, params, batch = whole
    (logits,) = model.apply({"params": params}, batch["tokens"],
                            batch["noised"])
    assert logits.shape == (2, 32, cfg["vocab_size"])
    assert logits.dtype == jnp.float32
    # a clean token never sees a noised one: the logits of a position whose
    # block is not masked anywhere still depend on the noised copy only
    # through its own block and the clean past
    other = batch["noised"].at[:, 28:].set(model.mask_id)
    (moved,) = model.apply({"params": params}, batch["tokens"], other)
    np.testing.assert_array_equal(logits[:, :28], moved[:, :28])
    assert rel_gap(moved[:, 28:], logits[:, 28:]) > 1e-3


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_three_steps_follow_the_reference(whole, precision):
    cfg, model, params, batch = whole
    policy = precision_policy(precision)
    if policy is not None:
        model = build_model("sdar_lm", lm_config=cfg,
                            dtype=policy.compute_dtype)
    tx = optax.sgd(1e-2, momentum=0.9)
    opt = {"learning_rate": 1e-2, "momentum": 0.9}
    state = create_train_state(jax.random.PRNGKey(11), model, tx,
                               batch["tokens"].shape, input_dtype=jnp.int32)
    state = state.replace(params=params, opt_state=tx.init(params))
    step = make_train_step(model, tx, loss_type=BLOCK_DIFFUSION,
                           donate=False, loss_weights=model.loss_weights,
                           precision=policy)
    ref_step = jax.jit(functools.partial(ref.train_step, cfg, opt,
                                         remat=False))
    loss_tol, change_tol = (1e-5, 5e-4) if policy is None else (1e-2, None)
    p, trace = params, jax.tree.map(jnp.zeros_like, params)
    with jax.default_matmul_precision("highest"):
        for _ in range(3):
            state, (loss, counters) = step(state, batch)
            p, trace, want = ref_step(p, trace, batch)
            assert abs(float(loss) - float(want)) <= loss_tol * float(want)
    change = jax.tree.map(lambda a, b: a - b, state.params, params)
    want_change = jax.tree.map(lambda a, b: a - b, p, params)
    if change_tol is not None:
        assert_trees_close(change, want_change, change_tol)
    else:  # leaf by leaf, the norm of the change, as the benchmark compares
        for (name, a), b in zip(leaves_with_names(change),
                                jax.tree.leaves(want_change)):
            na, nb = float(jnp.linalg.norm(a)), float(jnp.linalg.norm(b))
            assert abs(na - nb) <= 3e-2 * nb, (name, na, nb)
    assert int(counters["moe_tokens_dropped"]) == 0
    share = float(counters[diffusion.COUNTER_MASKED_SHARE])
    assert share == float((batch["loss_weight"] > 0).mean(-1).max())


@pytest.mark.parametrize("fault", ["rows", "drop_routed", "causal_mask",
                                   "unweighted_loss"])
def test_each_fault_of_the_reference_is_another_step(whole, fault):
    cfg, _, params, batch = whole
    opt = {"learning_rate": 1e-2, "momentum": 0.9}
    zeros = jax.tree.map(jnp.zeros_like, params)
    kw = {"rows": batch["tokens"].size // 2} if fault == "rows" \
        else {fault: True}
    sound = ref.train_step(cfg, opt, params, zeros, batch, remat=False)
    other = ref.train_step(cfg, opt, params, zeros, batch, remat=False, **kw)
    moved = [rel_gap(a, b) for a, b in zip(jax.tree.leaves(other[1]),
                                           jax.tree.leaves(sound[1]))]
    assert max(moved) > 1e-2, fault
    if fault == "unweighted_loss":  # every weight is at least 1
        assert float(other[2]) < float(sound[2])


def test_reference_blocks_change_memory_not_arithmetic(whole, monkeypatch):
    cfg, _, params, batch = whole
    want = jax.value_and_grad(ref.loss_fn)(params, batch, cfg)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "LOSS_BLOCK", 16)
    got = jax.value_and_grad(functools.partial(ref.loss_fn, remat=True))(
        params, batch, cfg)
    assert abs(float(got[0]) - float(want[0])) <= 1e-6 * float(want[0])
    assert_trees_close(got[1], want[1], GRAD_RTOL)


# ------------------------------------------------------------ (b) the mask
@pytest.mark.parametrize("length,block", MASKS)
def test_einsum_form_of_the_rule_is_the_brute_force_mask(length, block):
    want = brute_force_mask(length, block)
    np.testing.assert_array_equal(
        attention_ops.block_diffusion_mask(length, block), want)
    assert want.sum() == length * length + length * block \
        == ref.allowed_pairs(length, block)
    pos = jnp.arange(2 * length)
    np.testing.assert_array_equal(
        ref.allowed(pos[:, None], pos[None, :], length, block), want)
    # every row attends something, its own position included
    assert want.diagonal().all()


@pytest.mark.parametrize("tile", [8, 16, 24, 128])
@pytest.mark.parametrize("length,block", MASKS)
def test_rule_runs_every_tile_that_holds_a_pair(length, block, tile):
    """The tile predicate against the brute-force mask, at tile sizes that
    do and do not divide the length: a tile runs iff it holds an attended
    pair; one that runs whole is all attended; the mask inside a tile is
    the brute-force mask's; the index a stepped-over tile holds is one of
    the grid's, and the tile's own where it runs."""
    m = brute_force_mask(length, block)
    rule = pa.BlockDiffusion(length=length, block=block)
    n = -(-2 * length // tile)
    ran = 0
    for i, j in itertools.product(range(n), repeat=2):
        sub = m[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile]
        (whole, no_mask), (masked, mask) = rule.tiles(
            i, j, tile, tile, n_real=2 * length)
        assert no_mask is None and mask is not None
        runs = bool(whole) or bool(masked)
        assert runs == bool(sub.any()), (i, j)
        assert not (bool(whole) and bool(masked))
        ran += runs
        if bool(whole):
            assert sub.all() and sub.shape[1] == tile
        hold_k = int(rule.hold_key(i, j, tile, tile))
        hold_q = int(rule.hold_query(j, i, tile, tile))
        assert 0 <= hold_k < n and 0 <= hold_q < n
        if runs:
            assert (hold_k, hold_q) == (j, i)
        rows = sub.shape[0]  # the padded queries' rows are cut off
        for reverse in (False, True):
            got = np.asarray(mask((tile, tile)) if not reverse else rule.mask(
                (tile, tile), i * tile, j * tile, reverse=True))
            got = got.T if reverse else got
            want = np.zeros((rows, tile), bool)
            want[:, :sub.shape[1]] = sub
            np.testing.assert_array_equal(got[:rows], want)
    if length % tile == 0 and tile % block == 0:
        # a tile that is one block has no pair on the noised copy's clean
        # diagonal (a block does not see its own clean copy)
        per_side = length // tile
        assert ran == per_side ** 2 + (2 if tile > block else 1) * per_side


def test_tile_count_at_the_cells_shape():
    rule = pa.BlockDiffusion(length=4096, block=4)
    kinds = [tuple(bool(c) for c, _ in rule.tiles(i, j, 1024, 1024,
                                                  n_real=8192))
             for i, j in itertools.product(range(8), repeat=2)]
    assert sum(w or m for w, m in kinds) == 24
    assert sum(w for w, _ in kinds) == 12


def test_rules_are_static_and_refuse_what_they_cannot_tile():
    assert pa.BlockDiffusion(length=64, block=4) == \
        pa.BlockDiffusion(length=64, block=4)
    assert len({pa.NO_MASK, pa.CAUSAL, pa.Causal(),
                pa.BlockDiffusion(length=64, block=4)}) == 3
    with pytest.raises(ValueError, match="do not tile"):
        pa.BlockDiffusion(length=30, block=4)
    with pytest.raises(ValueError, match="do not tile"):
        attention_ops.block_diffusion_mask(30, 4)
    q = jnp.zeros((1, 60, 2, 8))
    with pytest.raises(ValueError, match="noised copy"):
        pa.flash_block_diffusion_attention(q, q, q, 32, 4, interpret=True)


# --------------------------------------------------------- (c) the kernels
def heads(s, q_heads, kv_heads, d=16, seed=0):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(2, s, h, d), jnp.float32)
                 for h in (q_heads, kv_heads, kv_heads))


@pytest.fixture()
def small_tiles(monkeypatch, request):
    forward, schedule = request.param
    monkeypatch.setattr(pa, "_CAUSAL_TILE", forward)
    monkeypatch.setattr(pa, "_bwd_plan",
                        lambda n, ck: (128, schedule == "fused"))
    return schedule


@pytest.mark.parametrize("small_tiles", [((128, 128), "fused"),
                                         ((128, 128), "two_sweeps"),
                                         ((256, 128), "fused")],
                         indirect=True)
@pytest.mark.parametrize("length,block", [(64, 4), (192, 4), (320, 8),
                                          (200, 4)])
def test_kernels_match_the_einsum_form(small_tiles, length, block):
    """Forward and the three gradients, in the pallas interpreter, where the
    tiles divide the length (64 of one tile, 320 of 128s and 256s) and where
    they do not (192: a tile spans the two copies; 200: padding)."""
    q, k, v = heads(2 * length, 4, 2, seed=length)

    def kernel(q, k, v):
        return pa.flash_block_diffusion_attention(q, k, v, length, block,
                                                  interpret=True)

    def einsum(q, k, v):
        return attention_ops.block_diffusion_attention(q, k, v, length,
                                                       block)

    assert rel_gap(kernel(q, k, v), einsum(q, k, v)) <= OUT_RTOL
    got = jax.grad(lambda *a: (kernel(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (einsum(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert rel_gap(a, b) <= GRAD_RTOL


@pytest.mark.parametrize("small_tiles", [((128, 128), "fused"),
                                         ((128, 128), "two_sweeps")],
                         indirect=True)
def test_calls_are_named_and_tiles_that_hold_no_pair_are_not_computed(
        small_tiles):
    length, block = 256, 4
    q, k, v = heads(2 * length, 4, 2, seed=7)

    def loss(q, k, v):
        out = pa.flash_block_diffusion_attention(q, k, v, length, block,
                                                 interpret=True)
        return (out[:, :128] ** 2).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v))
    names = [scopes.BLOCKDIFF_ATTN] + {
        "fused": [scopes.BLOCKDIFF_ATTN_BWD_FUSED],
        "two_sweeps": [scopes.BLOCKDIFF_ATTN_BWD_DKV,
                       scopes.BLOCKDIFF_ATTN_BWD_DQ]}[small_tiles]
    for name in names:
        assert f"name={name}\n" in text or f"name={name} " in text, name
    assert "causal_attn" not in text and "name=pam" not in text
    # a NaN in the noised copy's keys and values poisons a masked product
    # (0 x NaN) but not a tile that never runs: the first clean tile sees
    # clean keys alone
    late = jnp.arange(2 * length)[None, :, None, None] >= length
    k_bad, v_bad = (jnp.where(late, jnp.nan, x) for x in (k, v))
    clean = pa.flash_block_diffusion_attention(q, k_bad, v_bad, length, block,
                                               interpret=True)[:, :length]
    want = pa.flash_block_diffusion_attention(q, k, v, length, block,
                                              interpret=True)[:, :length]
    np.testing.assert_array_equal(clean, want)
    dq_bad = jax.grad(loss)(q, k_bad, v_bad)
    np.testing.assert_array_equal(dq_bad[:, :128], jax.grad(loss)(q, k, v)[
        :, :128])


@pytest.mark.parametrize("name", ["position_300", "causal_1536",
                                  "sparse_1536"])
def test_none_and_causal_rules_trace_to_the_flags_jaxprs(name):
    """With :data:`NO_MASK` and :data:`CAUSAL` (and a key set composed with
    it) the calls trace to what they were when ``causal`` was a flag,
    character for character: kernel bodies, index maps, names, forward and
    reverse (the fixtures were written from the parent commit by
    ``flash_jaxpr_cases.traced``)."""
    with gzip.open(os.path.join(REPO, "tests", "fixtures", "flash_jaxprs",
                                name + ".txt.gz"), "rt") as f:
        want = f.read()
    got = flash_jaxpr_cases.traced()[name]
    assert got == want


def test_kernel_path_gives_the_einsum_models_loss_and_gradients(
        whole, monkeypatch, interpreted_kernels):
    from distributedpytorch_tpu.models import danet

    cfg, model, params, batch = whole
    want = jax.value_and_grad(
        lambda p: program_loss(model, p, batch)[0])(params)
    monkeypatch.setattr(danet, "auto_wants_flash", lambda dtype: True)
    got = jax.value_and_grad(
        lambda p: program_loss(model, p, batch)[0])(params)
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * float(want[0])
    assert_trees_close(got[1], want[1], GRAD_RTOL)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: program_loss(model, p, batch)[0]))(params))
    assert text.count(f"name={scopes.BLOCKDIFF_ATTN}\n") == \
        cfg["num_hidden_layers"], "a replayed block runs no forward call"
    for kept in pa.BlockDiffusion(length=32, block=4).kept:
        assert f"name={kept}]" in text


# ------------------------------------------------------------ (d) the noise
def test_noise_function_and_the_references_batch_agree():
    cfg = tiny()
    key = jax.random.PRNGKey(17)
    batch = ref.make_batch(key, cfg, 3, 64)
    k_ids, k_noise = jax.random.split(key)
    model = build_model("sdar_lm", lm_config=cfg)
    staged = model.device_stage({"tokens": batch["tokens"]}, k_noise)
    assert set(staged) == {"tokens", diffusion.NOISED_KEY,
                           diffusion.LOSS_WEIGHT_KEY}
    np.testing.assert_array_equal(staged["noised"], batch["noised"])
    np.testing.assert_array_equal(staged["loss_weight"], batch["loss_weight"])
    assert int(batch["tokens"].max()) < model.mask_id == cfg["vocab_size"] - 1
    assert model.vocab_size == model.mask_id


def test_noise_is_the_recipes():
    tokens = jnp.zeros((4, 4096), jnp.int32).at[:, ::7].set(255)
    noised, w = diffusion.block_noise(jax.random.PRNGKey(5), tokens, 4, 255)
    masked = np.asarray(w > 0)
    np.testing.assert_array_equal(np.asarray(noised)[masked], 255)
    np.testing.assert_array_equal(np.asarray(noised)[~masked],
                                  np.asarray(tokens)[~masked])
    # the masked set is the weight's, not ``noised == MASK``: a clean token
    # may be the mask's id
    assert (np.asarray(noised) == 255).sum() > masked.sum()
    # one level a block: a block's masked tokens weigh alike, 1 / t with
    # t in [eps, 1)
    blocks = np.asarray(w).reshape(4, 1024, 4)
    level = blocks.max(-1, keepdims=True)
    assert np.all((blocks == 0) | (blocks == level))
    assert blocks.max() <= 1 / diffusion.NOISE_EPS and level[level > 0].min() \
        > 1.0
    # the expected masked share is (1 + eps) / 2; five standard deviations
    # of a 1,024-block draw are the benchmark cell's limit
    share = masked.mean(-1)
    assert np.abs(share - 0.5).max() < 0.06
    assert float(diffusion.masked_share(w)) == pytest.approx(share.max())
    # E[w] = 1: the loss is an unbiased estimate over the levels
    assert abs(float(w.mean()) - 1.0) < 0.25
    with pytest.raises(ValueError, match="do not tile"):
        diffusion.block_noise(jax.random.PRNGKey(0), tokens[:, :30], 4, 255)


# ------------------------------------------------- (e) the loss as an entry
def test_losses_are_one_table():
    assert set(step_lib.LOSSES) == {"multi_sigmoid", "multi_softmax",
                                    NEXT_TOKEN, BLOCK_DIFFUSION}
    assert {k for k, v in step_lib.LOSSES.items() if v.tokens} == {
        NEXT_TOKEN, BLOCK_DIFFUSION}
    assert step_lib.LOSSES[BLOCK_DIFFUSION].inputs == ("tokens", "noised")
    assert step_lib.LOSSES[NEXT_TOKEN].inputs == ("tokens",)
    with pytest.raises(ValueError, match="unknown loss_type"):
        step_lib._compute_loss((), {}, None, "no_such_loss")
    with pytest.raises(ValueError, match="unknown loss_type"):
        make_eval_step(None, loss_type="no_such_loss")


def test_weighted_token_xent_is_the_sum_it_says():
    r = np.random.RandomState(0)
    logits = jnp.asarray(r.randn(2, 8, 11), jnp.float32)
    tokens = jnp.asarray(r.randint(0, 11, (2, 8)))
    w = jnp.asarray(r.rand(2, 8) * (r.rand(2, 8) > 0.5), jnp.float32)
    logp = np.asarray(jax.nn.log_softmax(logits))
    want = sum(-float(w[b, i]) * logp[b, i, int(tokens[b, i])]
               for b in range(2) for i in range(8)) / 16
    assert float(weighted_token_xent(logits, tokens, w)) == pytest.approx(
        want, rel=1e-6)
    assert float(weighted_token_xent(logits, tokens, jnp.zeros_like(w))) == 0


def test_bucketed_reduce_is_refused_for_every_token_loss(whole):
    from distributedpytorch_tpu.parallel.plan import PlanError

    _, model, _, _ = whole
    for loss_type in (NEXT_TOKEN, BLOCK_DIFFUSION):
        with pytest.raises(PlanError, match="tokens task"):
            make_train_step(model, optax.sgd(1e-2), loss_type=loss_type,
                            reduce_buckets=2)


def test_step_draws_the_noise_through_its_stage_and_eval_from_a_fixed_key(
        whole):
    """On the trainer's path the batch is ``{tokens}``: the step's
    ``augment`` hook adds the noise from the step's key (another draw every
    step), evaluation from a fixed one (the same loss twice)."""
    cfg, model, params, batch = whole
    tx = optax.sgd(1e-2, momentum=0.9)
    state = create_train_state(jax.random.PRNGKey(11), model, tx,
                               batch["tokens"].shape, input_dtype=jnp.int32)
    step = make_train_step(model, tx, loss_type=BLOCK_DIFFUSION, donate=False,
                           loss_weights=model.loss_weights,
                           augment=model.device_stage)
    only_ids = {"tokens": batch["tokens"]}
    state1, (loss1, counters1) = step(state, only_ids)
    _, (loss2, counters2) = step(state1.replace(params=state.params,
                                                opt_state=state.opt_state),
                                 only_ids)
    assert np.isfinite(float(loss1)) and float(loss1) != float(loss2)
    assert 0 < float(counters1[diffusion.COUNTER_MASKED_SHARE]) <= 1
    fixed = jax.random.PRNGKey(0)
    ev = make_eval_step(model, loss_type=BLOCK_DIFFUSION,
                        preprocess=lambda b: model.device_stage(b, fixed))
    (), a = ev(state, only_ids)
    (), b = ev(state, only_ids)
    assert float(a) == float(b) and np.isfinite(float(a))
    with pytest.raises(KeyError):  # no stage, no noise: nothing to score
        make_eval_step(model, loss_type=BLOCK_DIFFUSION)(state, only_ids)


# ------------------------------------------------------------- the trainer
def _token_cfg(tmp_path, *extra):
    from distributedpytorch_tpu.train import Config, apply_overrides

    cfg = apply_overrides(Config(), [
        "task=tokens", "model.name=sdar_lm", "data.train_batch=8",
        "data.val_batch=8", "data.seq_len=24", "data.token_samples=24",
        "data.token_val_samples=8", "optim.lr=1e-2", "epochs=1",
        "checkpoint.async_save=false", "log_every_steps=1", *extra])
    return dataclasses.replace(cfg, work_dir=str(tmp_path / "runs"))


def test_token_task_takes_loss_and_stage_from_the_models_configuration(
        tmp_path):
    from distributedpytorch_tpu.train import tasks

    cfg = _token_cfg(tmp_path)
    task = tasks.get("tokens", cfg)
    assert task.loss_type == BLOCK_DIFFUSION and task.name == "tokens"
    stage = task.device_stage(cfg, False, False)
    out = stage({"tokens": jnp.zeros((2, 24), jnp.int32)},
                jax.random.PRNGKey(0))
    assert set(out) == {"tokens", "noised", "loss_weight"}
    other = tasks.get("tokens", _token_cfg(tmp_path, "model.name=keye_lm"))
    assert other.loss_type == NEXT_TOKEN and other.device_stage is None
    assert tasks.get("tokens") is tasks.TOKENS
    assert tasks.get("instance", cfg) is tasks.INSTANCE


def test_trainer_fits_three_steps_under_the_diffusion_loss(tmp_path):
    from distributedpytorch_tpu.train import Trainer

    tr = Trainer(_token_cfg(tmp_path))
    try:
        assert tr.task.loss_type == BLOCK_DIFFUSION
        assert tr._step_kwargs["augment"] is not None
        assert tr._feed_flip_available()[0] is False
        hist = tr.fit()
    finally:
        tr.close()
    assert int(tr.state.step) == 3
    assert np.isfinite(hist["train_loss"][0])
    val = hist["val"][0]
    assert np.isfinite(val["loss"]) and val["perplexity"] > 1.0
    lines = [json.loads(ln) for ln in open(
        os.path.join(tr.run_dir, "metrics.jsonl"))]
    shares = [ln["train/diffusion_masked_share"] for ln in lines
              if "train/diffusion_masked_share" in ln]
    assert len(shares) == 3 and all(0 < s <= 1 for s in shares)
    assert len(set(shares)) > 1, "every step draws its own noise"
    flat = {k: v for ln in lines for k, v in ln.items()}
    assert flat["train/moe_tokens_dropped"] == 0


# ------------------------------------------------- the benchmark's config
def _cell_config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar_30b_a3b_stage_ep8.json")) as f:
        return json.load(f)


def test_benchmark_configuration_keeps_every_published_width():
    cfg = _cell_config()
    catalog = {  # the catalog row's ``config``, key for key
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    reduced = {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(cfg["reduced"]) == set(cfg["published"]) == reduced
    for key, value in catalog.items():
        assert (cfg["published"][key] if key in reduced else cfg[key]) \
            == value, key
    assert cfg["num_experts"] * 8 == 128 and cfg["vocab_size"] * 8 == 151936
    assert 6 <= cfg["num_hidden_layers"] <= 7
    assert cfg["loss"] == BLOCK_DIFFUSION and cfg["block_length"] == 4
    assert {"block_length", "noise_schedule", "layout_and_mask", "no_shift",
            "qk_norm", "mask_token"} <= set(cfg["assumed"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == cfg["source"]


def test_benchmark_flops_are_the_formulas():
    cfg = _cell_config()
    terms = ref.flops_per_sequence(cfg, 4096)
    assert cfg["model_flops_per_image"] == int(round(terms["total"]))
    layers = cfg["num_hidden_layers"]
    assert terms["attn_scores"] / layers == 7 * 2 * 16793600 * 128 * 32
    per_position = (terms["attn_proj"] + terms["moe_router"]
                    + terms["moe_routed"]) / layers / 8192
    assert per_position == pytest.approx(143.1e6, rel=1e-3)
    assert terms["lm_head"] == 6 * 4096 * 2048 * 18992
    assert 1.58e13 < terms["total"] < 1.60e13
    for name, per_token in cfg["model_flops_terms_gflop_per_token"].items():
        assert abs(terms[name] / 4096 / 1e9 - per_token) < 1e-4, name


def test_benchmark_configuration_is_the_issues_cut():
    cfg = _cell_config()
    leaves = jax.tree.leaves(ref.param_spec(cfg), is_leaf=ref._is_leaf)
    n = sum(int(np.prod(s)) for s, _ in leaves)
    assert n == 740_261_632            # 740.3 M
    assert 8.8e9 < n * 12 < 8.9e9      # 8.88 GB at 12 B a parameter
    model = sl.build_sdar_lm(cfg, dtype=jnp.bfloat16)
    assert model.cfg.experts_total == 128 and model.cfg.experts_held == 16
    assert (model.cfg.block_length, model.mask_id) == (4, 18991)
    assert model.loss_type == cfg["loss"]


def test_activation_bytes_follow_the_attention_form_that_runs(monkeypatch):
    from distributedpytorch_tpu.models import danet

    model = sl.build_sdar_lm(_cell_config(), dtype=jnp.bfloat16)
    einsum = model.activation_bytes(1, 4096)
    monkeypatch.setattr(danet, "_on_tpu", lambda: True)
    flash = model.activation_bytes(1, 4096)
    # the einsum form holds 32 heads' float32 (8192, 8192) arrays
    assert einsum > 3 * 32 * 8192 * 8192 * 4 > 8 * flash
    assert 1e9 < flash < 6e9


def test_control_rounding_moves_the_reference(whole):
    """The benchmark's control: the reference in a lower type is another
    step (what the cell's limits have to catch)."""
    cfg, _, params, batch = whole
    opt = {"learning_rate": 1e-2, "momentum": 0.9}
    zeros = jax.tree.map(jnp.zeros_like, params)
    sound = ref.train_step(cfg, opt, params, zeros, batch, remat=False)
    low = ref.train_step(cfg, opt, params, zeros, batch, remat=False,
                         q=nets.Rounding("bfloat16"))
    gaps = [rel_gap(a, b) for a, b in zip(jax.tree.leaves(low[1]),
                                          jax.tree.leaves(sound[1]))]
    assert 1e-4 < max(gaps) < 0.2


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mask_row_is_sharpened_towards_experts_no_seed_chooses(seed):
    """``make_weights``: every other leaf is the draw's; the mask token's
    row is the sum of the seed's router columns of the experts that the
    configuration's own key draws (the same for every seed), at the drawn
    row's norm; the row then routes to those experts with a margin, in
    every layer."""
    cfg = tiny()
    key = jax.random.PRNGKey(seed)
    drawn = ref.make_weights(key, cfg, sharpen_mask_row=False)
    built = ref.make_weights(key, cfg)
    row0, row = drawn["embed"][-1], built["embed"][-1]
    assert_trees_close(
        {**built, "embed": built["embed"][:-1]},
        {**drawn, "embed": drawn["embed"][:-1]}, 0.0)
    assert abs(float(jnp.linalg.norm(row) / jnp.linalg.norm(row0)) - 1) < 1e-5
    k = cfg["num_experts_per_tok"]
    for i in range(cfg["num_hidden_layers"]):
        w = built[ref.layer_name(2 * i + 1)]["router"]
        want = set(np.asarray(ref.mask_experts(cfg, i)).tolist())
        assert len(want) == k and max(want) < 8
        z = np.sort(np.asarray(row @ w))[::-1]
        assert set(np.asarray(jax.lax.top_k(row @ w, k)[1]).tolist()) == want
        # the drawn row's own margin is the order statistics' spacing; the
        # sharpened row's is of the order of a column's length
        z0 = np.sort(np.asarray(row0 @ w))[::-1]
        assert z[k - 1] - z[k] > max(0.5, z0[k - 1] - z0[k])


def test_mask_experts_of_the_cell_are_the_configurations_draw():
    """Seven of the 56 are held here, what eight experts a layer over eight
    ranks expect; none is written down anywhere."""
    cfg = _cell_config()
    held = [int((np.asarray(ref.mask_experts(cfg, i)) < 16).sum())
            for i in range(cfg["num_hidden_layers"])]
    assert held == [1, 0, 2, 2, 0, 2, 0]


def test_expert_chunk_rows_are_the_configurations_where_it_states_them():
    """The cell's deployment states one chunk that takes a layer's rows
    whatever the draw gave the mask token; a configuration that states
    none gets ``keye_lm.expert_chunk_rows``."""
    from distributedpytorch_tpu.parallel import moe as moe_lib

    cfg = _cell_config()
    model = sl.build_sdar_lm(cfg, dtype=jnp.bfloat16)
    assert model.cfg.expert_chunk_rows == cfg["expert_chunk_rows"] == 17408
    # 8,192 rows + four more experts' mask positions (2,130 at the masked
    # share's limit) + three standard deviations of the others' rows
    assert 8192 + 4 * 2130 + 220 <= 17408
    buffer_rows = moe_lib.dropless_buffer_rows(8192, 8, 16)
    assert moe_lib.chunk_rows_of(buffer_rows, 17408) == 17408
    assert sl.build_sdar_lm(tiny()).cfg.expert_chunk_rows is None


# ------------------------------- what a rematerialised expert block keeps
@pytest.mark.parametrize("policy,runs", [("kept", 1), ("bare", 2)])
def test_replay_of_an_expert_block_holds_no_product_or_selection(policy,
                                                                 runs):
    """The Keye model's expert block under this model's configuration: one
    router product and one top-k in the gradient where a bare ``nn.remat``
    runs two."""
    cfg = sl.LMConfig.from_dict(tiny())
    got = kept_cases.replay_counts(
        *kept_cases.block_case(
            sl.GatedMoE, cfg,
            sl.KEEP_EXPERT_RESIDUALS if policy == "kept" else None),
        widths=(cfg.experts_total,), k=cfg.experts_per_token)
    # no gather either way: the chosen gates are the top-k's own values
    assert got == {"top_k": runs, "gathers": 0, "products": [runs],
                   "forward_loops": 1}


def test_model_rematerialises_its_expert_blocks_under_the_policy(whole):
    """The whole model's gradient, over the doubled sequence, holds one
    router product and one top-k a layer."""
    cfg, model, params, batch = whole
    assert model.remat
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: program_loss(model, p, batch)[0]))(params).jaxpr
    assert len(kept_cases.eqns_named(jaxpr, "top_k")) \
        == cfg["num_hidden_layers"]
    assert kept_cases.forward_products(
        jaxpr, 2 * batch["tokens"].size, cfg["hidden_size"],
        cfg["published"]["num_experts"]) == cfg["num_hidden_layers"]


def test_kept_expert_block_gives_the_bare_blocks_loss_and_gradients():
    kept_cases.assert_kept_block_is_the_bare_blocks(
        sl.GatedMoE, sl.LMConfig.from_dict(tiny()),
        sl.KEEP_EXPERT_RESIDUALS, GRAD_RTOL)


def test_activation_bytes_count_what_an_expert_block_keeps(monkeypatch):
    """The planner is charged every layer's named arrays over the doubled
    sequence."""
    from distributedpytorch_tpu.models.keye_lm import EXPERT_KEPT

    cfg, batch, length = tiny(), 2, 24
    model = sl.build_sdar_lm(cfg)
    kept = kept_cases.kept_bytes(sl.GatedMoE, model.cfg, EXPERT_KEPT, batch,
                                 2 * length, jnp.float32)

    def acts():
        return [sl.build_sdar_lm(cfg, remat=r).activation_bytes(batch, length)
                for r in (True, False)]

    charged = acts()
    monkeypatch.setattr(sl, "expert_kept_bytes", lambda c, t: 0)
    kept_once, plain = (a - b for a, b in zip(charged, acts()))
    assert (kept_once, plain) == (cfg["num_hidden_layers"] * kept, 0)
