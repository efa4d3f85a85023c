"""``zaya_lm`` (models/zaya_lm.py) against its plain reference
(benchmarks/reference/zaya_lm.py): compressed convolutional attention, the
router whose state carries down the layers, the skip slot, the scaled
residuals and the tied head; the expert layer's share of a deployment; the
model through the step and the trainer.  Tiny widths, seeded weights, on the
CPU.

Tolerances, with their reasons: program and reference both compute in
float32 here, in different forms — the whole row against query blocks, the
grouped product against a masked loop, two convolution taps as one product
against a sum of two — so outputs agree to rounding of float32 sums in
another order: 2e-5 of the largest value for outputs and logits, 1e-5 of the
loss, 1e-4 of a leaf's largest gradient for gradients.  Under the bfloat16
policy the program rounds every product's operands to 8 bits of mantissa
where the reference holds 24: losses agree to 1e-2, a leaf's change to 3e-2
of its norm.  Each part of the mathematics left out (a convolution, the
value shift, half the rotary, the depth average, the routed experts) moves
the gradients by 1e-2 or more (``test_each_fault_of_the_reference_is_another
_step``), so none of these tolerances passes a program without it.
"""

import dataclasses
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

from reference import zaya_lm as ref  # noqa: E402

import expert_remat_cases as kept_cases  # noqa: E402
from distributedpytorch_tpu.models import build_model  # noqa: E402
from distributedpytorch_tpu.models import nemotron_h as nh  # noqa: E402
from distributedpytorch_tpu.models import zaya_lm as zl  # noqa: E402
from distributedpytorch_tpu.parallel import (NEXT_TOKEN,  # noqa: E402
                                             create_train_state,
                                             make_train_step)
from distributedpytorch_tpu.parallel.step import _loss_and_updates  # noqa: E402
from distributedpytorch_tpu.telemetry import scopes  # noqa: E402
from distributedpytorch_tpu.train.precision import precision_policy  # noqa: E402

OUT_RTOL, LOSS_RTOL, GRAD_RTOL = 2e-5, 1e-5, 1e-4
FAULTS = ("rows", "drop_routed", "no_conv", "no_shift", "full_rotary",
          "no_eda")


def tiny(**over):
    return dict(zl.PRESETS["tiny"], **over)


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
    model = build_model("zaya_lm", lm_config=cfg)
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
    # the head is the embedding; the stage's first expert layer has no depth
    # average, every later one has
    assert "lm_head" not in made
    assert "depth_average" not in made["l01"]
    assert made["l03"]["depth_average"].shape == ()


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
        assert float(jnp.abs(g).max()) > 0, name


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_three_steps_follow_the_reference(whole, precision):
    cfg, model, params, tokens = whole
    policy = precision_policy(precision)
    if policy is not None:
        model = build_model("zaya_lm", lm_config=cfg,
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
    assert 0 < float(counters[zl.COUNTER_SKIP_SHARE]) < 1


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
    if fault == "no_eda":  # the depth averages then get no gradient
        for name, g in leaves_with_names(other[1]):
            if "depth_average" in name:
                assert float(jnp.abs(g).max()) == 0, name


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
    """The temperature and ``sqrt(head_dim)`` folded into q make the flash
    kernels' scores the einsum form's; a rematerialised block runs one
    forward call."""
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


# ------------------------------------------------------ (b) the mechanisms
def _changes_at(fn, x, t):
    """Positions of ``fn``'s output that move when input position ``t``
    does."""
    moved = x.at[:, t].add(1.0)
    diff = jnp.abs(fn(moved) - fn(x)).reshape(x.shape[0], x.shape[1], -1)
    return np.flatnonzero(np.asarray(diff.max(axis=(0, 2))) > 0)


def test_convolutions_and_value_shift_are_causal():
    """Moving token ``t`` moves the output at ``t`` and the ``taps - 1``
    positions after it, and none before, for each convolution (two taps)
    and for the value shift (the previous token only)."""
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(2, 12, 6), jnp.float32)
    w0 = jnp.asarray(r.randn(2, 6), jnp.float32)
    b0 = jnp.asarray(r.randn(6), jnp.float32)
    heads = x.reshape(2, 12, 2, 3)
    w1 = jnp.asarray(r.randn(2, 2, 3, 3), jnp.float32)
    b1 = jnp.asarray(r.randn(2, 3), jnp.float32)
    for t in (0, 5, 11):
        after = [s for s in (t, t + 1) if s < 12]
        np.testing.assert_array_equal(_changes_at(
            lambda z: nh.causal_depthwise_conv(z, w0, b0), x, t), after)
        np.testing.assert_array_equal(_changes_at(
            lambda z: zl.causal_grouped_conv(z, w1, b1, jnp.float32),
            heads, t), after)
        np.testing.assert_array_equal(
            _changes_at(zl.shift_time, x, t), [t + 1] if t < 11 else [])
    # the two forms of each convolution are the reference's sums
    want0 = b0 + w0[0] * ref.earlier(x, 1) + w0[1] * x
    assert rel_gap(nh.causal_depthwise_conv(x, w0, b0), want0) <= OUT_RTOL
    want1 = b1 + sum(jnp.einsum("blgc,gcd->blgd", ref.earlier(heads, 1 - i),
                                w1[i]) for i in range(2))
    assert rel_gap(zl.causal_grouped_conv(heads, w1, b1, jnp.float32),
                   want1) <= OUT_RTOL


def test_mamba_convolution_traces_as_its_inline_loop():
    """``causal_depthwise_conv`` is the loop ``MambaMixer`` held inline,
    equation for equation, in the compute types Nemotron runs it in: the
    Nemotron step's jaxpr does not move by the function's extraction."""
    def inline(xbc, conv_w, conv_b):
        length = xbc.shape[1]
        padded = jnp.pad(xbc, ((0, 0), (conv_w.shape[0] - 1, 0), (0, 0)))
        acc = conv_b.astype(jnp.float32)
        for i in range(conv_w.shape[0]):
            acc = acc + padded[:, i:i + length].astype(jnp.float32) \
                * conv_w[i]
        return acc

    for dtype in (jnp.float32, jnp.bfloat16):
        args = (jnp.zeros((2, 12, 6), dtype), jnp.zeros((4, 6)),
                jnp.zeros((6,)))
        assert str(jax.make_jaxpr(nh.causal_depthwise_conv)(*args)) == \
            str(jax.make_jaxpr(inline)(*args))


def test_attention_block_is_causal(whole):
    """The whole block: moving token ``t + 1`` moves no output at ``t`` or
    before (the convolutions, the value shift and the attention
    together)."""
    cfg, _, params, _ = whole
    block = zl.CCAttention(zl.LMConfig.from_dict(cfg))
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg["hidden_size"]))
    moved = _changes_at(lambda z: block.apply({"params": params["l00"]}, z),
                        u, 9)
    assert moved.min() == 9 and list(moved) == list(range(9, 16))


def test_second_half_of_each_head_stays_unrotated():
    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(1, 6, 3, 16), jnp.float32)
    ang = zl.rotary_angles(zl.text_positions(1, 6), 4, 5e6)
    out = zl.rotate_leading(x, ang, 8)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(out[:, 0], x[:, 0])     # position 0
    assert float(jnp.abs(out[:, 1:, :, :8] - x[:, 1:, :, :8]).max()) > 1e-3
    # a rotation keeps each head's norm, so normalising first is the same
    np.testing.assert_allclose(jnp.linalg.norm(out, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-6)
    want = ref.turn_leading(x, ref.text_positions(1, 6), 5e6, 8)
    assert rel_gap(out, want) <= OUT_RTOL
    c = zl.LMConfig.from_dict(tiny())
    assert (c.rotary_dim, c.rope_theta) == (8, 5e6)


def test_router_state_carries_down_the_layers(whole):
    """``rbar_l = (1 - g) r_l + g rbar_(l-1)``: the block hands on the
    averaged state, the stage's first block its own, and the next layer's
    choice reads the previous layer's state."""
    cfg, _, params, _ = whole
    c = zl.LMConfig.from_dict(cfg)
    block = zl.ZayaExperts(c)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 16, c.hidden_size))
    prev = jax.random.normal(jax.random.PRNGKey(6), (2, 16, c.router_hidden))
    p1, p3 = params["l01"], params["l03"]
    with jax.default_matmul_precision("highest"):
        (_, first), _ = block.apply({"params": p1}, u, mutable=["counters"])
        (_, later), _ = block.apply({"params": p3}, u, prev,
                                    mutable=["counters"])
        x = ref.rms_norm(u, p1["norm"], cfg["rms_norm_eps"]).reshape(-1, 64)
        assert rel_gap(first.reshape(-1, 16),
                       x @ p1["router_down"]) <= OUT_RTOL
        x = ref.rms_norm(u, p3["norm"], cfg["rms_norm_eps"]).reshape(-1, 64)
        g = p3["depth_average"]
        want = (1 - g) * (x @ p3["router_down"]) + g * prev.reshape(-1, 16)
        assert rel_gap(later.reshape(-1, 16), want) <= OUT_RTOL
        _, choice, state = ref.route(p3, x, prev.reshape(-1, 16), cfg)
        _, alone, _ = ref.route(p3, x, None, cfg)
    assert rel_gap(state, want) <= OUT_RTOL
    assert (choice != alone).any(), "the carried state moves no choice"
    # the first layer's block has nothing to average a state with
    with pytest.raises(Exception, match="depth_average"):
        block.apply({"params": p1}, u, prev, mutable=["counters"])


def test_expert_shares_add_up_to_the_uncut_layer():
    """The two shares of the published experts (4 each, the router as wide
    as all of them and the skip slot) give routed parts that, with the
    scaled residual every chip computes alike counted once, add up to the
    uncut reference's layer; each share of the program is the
    reference's."""
    uncut = tiny(num_experts=8)
    full = ref.make_weights(jax.random.PRNGKey(5), uncut)["l03"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 64))
    prev = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 16))
    eps = uncut["rms_norm_eps"]

    def layer(p, cfg):
        y, _ = ref.experts(p, ref.rms_norm(u, p["norm"], eps),
                           prev.reshape(-1, 16), cfg)
        return p["res_scale"] * u + p["out_scale"] * y

    with jax.default_matmul_precision("highest"):
        want = layer(full, uncut)
        shared = full["res_scale"] * u
        total = shared
        for off in (0, 4):
            cfg = tiny(num_experts=4, expert_offset=off)
            share = dict(full, w1=full["w1"][off:off + 4],
                         w2=full["w2"][off:off + 4])
            part = layer(share, cfg) - shared
            (out, _), _ = zl.ZayaExperts(zl.LMConfig.from_dict(cfg)).apply(
                {"params": share}, u, prev, mutable=["counters"])
            assert rel_gap(out - shared, part) <= OUT_RTOL
            total = total + part
    assert rel_gap(total, want) <= OUT_RTOL
    assert float(jnp.abs(want - shared).max()) > 1e-2


def test_skip_share_is_the_hand_count(whole):
    cfg, model, params, tokens = whole
    with jax.default_matmul_precision("highest"):
        _, counters = program_loss(model, params, tokens)
        x = params["embed"][tokens]
        eps, skip = cfg["rms_norm_eps"], cfg["published"]["num_experts"]
        shares, state = [], None
        for i in range(cfg["num_hidden_layers"]):
            a = params[ref.layer_name(2 * i)]
            m = params[ref.layer_name(2 * i + 1)]
            x = a["res_scale"] * x + a["out_scale"] * ref.attention(
                a, ref.rms_norm(x, a["norm"], eps), cfg)
            u = ref.rms_norm(x, m["norm"], eps)
            _, choice, _ = ref.route(m, u.reshape(-1, 64), state, cfg)
            shares.append(int((choice == skip).sum()) / choice.size)
            y, state = ref.experts(m, u, state, cfg)
            x = m["res_scale"] * x + m["out_scale"] * y
    assert max(shares) > 0
    assert float(counters[zl.COUNTER_SKIP_SHARE]) == pytest.approx(
        max(shares), abs=1e-7)


# ----------------------------------------------- scopes and remat policies
def test_scope_table_holds_the_mixing_and_the_router(whole):
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
        assert (scopes.ATTN, scopes.ATTN_CCA_MIX, phase) in parts, phase
        assert (scopes.MOE, scopes.MOE_ROUTER, phase) in parts, phase
    for part in (scopes.MOE_DISPATCH, scopes.MOE_ROUTED_EXPERTS,
                 scopes.MOE_COMBINE):
        assert (scopes.MOE, part, "fwd") in parts, part
    assert {scopes.EMBED, scopes.ATTN, scopes.MOE, scopes.LM_HEAD,
            scopes.LOSS, scopes.OPTIMIZER} <= {s.layer for s in
                                               table.values()}
    assert not any("ZayaLM" in s.path for s in table.values())
    assert any(s.path.startswith("attn/l02/cca_mix") for s in table.values())
    assert any(s.path.startswith("moe/l03/router") for s in table.values())


@pytest.mark.parametrize("policy,runs", [("kept", 1), ("bare", 2)])
def test_replay_of_an_expert_block_holds_no_router_product(policy, runs):
    """An expert block that keeps :data:`EXPERT_KEPT` runs its router's
    down-projection once in the gradient, a bare ``nn.remat`` twice; the
    chunk loop of the forward pass is dead in the replay either way."""
    from flax import linen as nn

    c = zl.LMConfig.from_dict(tiny())
    cls = nn.remat(zl.ZayaExperts, policy=zl.KEEP_EXPERT_RESIDUALS
                   if policy == "kept" else None)
    block = cls(c, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(2), kept_cases.POSITIONS + (64,))
    prev = jax.random.normal(jax.random.PRNGKey(3),
                             kept_cases.POSITIONS + (16,))
    params = block.init(jax.random.PRNGKey(3), u, prev)["params"]

    def loss(params, u):
        (out, state), _ = block.apply({"params": params}, u, prev,
                                      mutable=["counters"])
        return (out ** 2).sum() + (state ** 2).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, u).jaxpr
    assert kept_cases.forward_products(jaxpr, 40, 64, 16) == runs
    assert len(kept_cases.eqns_named(jaxpr, "argmax")) == runs
    if policy == "kept":
        plain = nn.remat(zl.ZayaExperts, policy=None)(c, jnp.float32)

        def bare(params, u):
            (out, state), _ = plain.apply({"params": params}, u, prev,
                                          mutable=["counters"])
            return (out ** 2).sum() + (state ** 2).sum()

        got = jax.grad(loss, (0, 1))(params, u)
        want = jax.grad(bare, (0, 1))(params, u)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- the trainer
def _token_cfg(tmp_path, *extra):
    from distributedpytorch_tpu.train import Config, apply_overrides

    cfg = apply_overrides(Config(), [
        "task=tokens", "model.name=zaya_lm", "data.train_batch=8",
        "data.val_batch=8", "data.seq_len=24", "data.token_samples=16",
        "data.token_val_samples=8", "optim.lr=1e-2", "epochs=1",
        "checkpoint.async_save=false", "log_every_steps=1", *extra])
    return dataclasses.replace(cfg, work_dir=str(tmp_path / "runs"))


def test_trainer_fits_two_steps_and_logs_the_skip_share(tmp_path):
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
    assert 0 <= flat["train/moe_skip_share"] <= 1


def test_configuration_refuses_what_the_model_does_not_build():
    with pytest.raises(ValueError, match="hybrid"):
        build_model("zaya_lm", lm_config=tiny(
            layer_types=["hybrid", "hybrid_sliding"]))
    with pytest.raises(ValueError, match="one slot"):
        build_model("zaya_lm", lm_config=tiny(num_experts_per_tok=2))
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        build_model("zaya_lm", lm_config=tiny(tie_word_embeddings=False))


# ------------------------------------------------- the benchmark's config
def _cell_config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "zaya1_8b_stage_ep2.json")) as f:
        return json.load(f)


def test_benchmark_configuration_keeps_every_published_width():
    cfg = _cell_config()
    catalog = {  # the catalog row's ``config``, key for key
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
        "max_position_embeddings": 131072, "model_type": "zaya",
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"},
        "router_hidden_size": 256, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": 262272}
    reduced = {"num_hidden_layers", "layer_types", "num_experts",
               "vocab_size"}
    assert set(cfg["reduced"]) == set(cfg["published"]) == reduced
    for key, value in catalog.items():
        assert (cfg["published"][key] if key in reduced else cfg[key]) \
            == value, key
    assert cfg["num_experts"] * 2 == 16 and cfg["vocab_size"] * 8 == 262272
    assert cfg["num_hidden_layers"] == 7
    assert cfg["layer_types"] == ["hybrid"] * 7
    assert {"cca_convolutions", "cca_qk_mean", "cca_norm_temperature",
            "cca_value_shift", "router", "skip_slot", "gate",
            "balancing_bias", "residual_scales", "init",
            "optimizer"} <= set(cfg["assumed"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == cfg["source"]


def test_benchmark_configuration_is_the_stated_cut():
    """7 x (6.25 M + 8 x 12.58 M) + 67.1 M = 815.5 M parameters, 9.79 GB at
    12 B a parameter."""
    cfg = _cell_config()
    spec = ref.param_spec(cfg)

    def count(tree):
        return sum(int(np.prod(s)) for s, _ in
                   jax.tree.leaves(tree, is_leaf=ref._is_leaf))

    n = count(spec)
    assert n == cfg["parameters"] == 815_524_158
    assert 9.78e9 < 12 * n < 9.80e9
    assert count(spec["l01"]["w1"]) + count(spec["l01"]["w2"]) \
        == 8 * 3 * 2048 * 2048                      # 8 x 12.58 M
    outside = count(spec["l00"]) + count(spec["l01"]) \
        - 8 * 3 * 2048 * 2048                       # 6.25 M
    assert 6.24e6 < outside < 6.26e6
    assert count(spec["embed"]) == 32784 * 2048     # 67.1 M, tied
    model = zl.build_zaya_lm(cfg, dtype=jnp.bfloat16)
    assert (model.cfg.experts_total, model.cfg.experts_held) == (16, 8)
    assert (model.cfg.rotary_dim, model.cfg.conv_taps) == (64, (2, 2))
    assert model.cfg.rope_theta == 5e6 and model.cfg.router_hidden == 256


def test_benchmark_flops_are_the_formulas():
    cfg = _cell_config()
    terms = ref.flops_per_sequence(cfg, 8192)
    assert cfg["model_flops_per_image"] == int(round(terms["total"]))
    layers = cfg["num_hidden_layers"]
    assert terms["cca_scores"] / layers == 7 * 2 * 8192 ** 2 / 2 * 128 * 8
    assert terms["lm_head"] == 6 * 8192 * 2048 * 32784
    assert 1.09e13 < terms["total"] < 1.10e13
    for name, per_token in cfg["model_flops_terms_gflop_per_token"].items():
        assert abs(terms[name] / 8192 / 1e9 - per_token) < 1e-4, name


def test_activation_bytes_follow_the_attention_form_that_runs(monkeypatch):
    from distributedpytorch_tpu.models import danet

    model = zl.build_zaya_lm(_cell_config(), dtype=jnp.bfloat16)
    einsum = model.activation_bytes(1, 8192)
    monkeypatch.setattr(danet, "_on_tpu", lambda: True)
    flash = model.activation_bytes(1, 8192)
    # the einsum form holds 8 heads' float32 (8192, 8192) arrays
    assert einsum > 3 * 8 * 8192 * 8192 * 4 > flash
    assert 2.15e9 < flash < 6e9          # the logits and their gradient too
