"""The dropless expert dispatch (``parallel/moe.py``) and the LatentMoE layer
that uses it, against the plain reference (``benchmarks/reference/
nemotron_h.py``).  Tiny widths, seeded weights, float32.

Tolerances: both sides compute in float32 on the CPU, so the gaps are
rounding of sums taken in another order (a grouped product over sorted rows
against a masked dense loop): 1e-5 of the output's scale.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from reference import nemotron_h as ref  # noqa: E402

from distributedpytorch_tpu.models.nemotron_h import (  # noqa: E402
    PRESETS,
    LatentMoE,
    LMConfig,
    relu2,
)
from distributedpytorch_tpu.parallel import moe as moe_lib  # noqa: E402

RTOL = 1e-5


def _cfg(held=8, total=8, offset=0, topk=3):
    cfg = dict(PRESETS["tiny"], n_routed_experts=held, expert_offset=offset,
               num_experts_per_tok=topk)
    cfg["published"] = {"n_routed_experts": total}
    return cfg


def _moe_params(cfg, seed=0):
    whole = dict(cfg, hybrid_override_pattern="E",
                 num_nextn_predict_layers=0)
    return ref.make_weights(jax.random.PRNGKey(seed), whole)["l00"]


def _close(got, want, rtol=RTOL):
    scale = float(jnp.abs(want).max()) + 1e-12
    assert float(jnp.abs(got - want).max()) <= rtol * scale


def _random_idx(key, n, total, k):
    scores = jax.random.uniform(key, (n, total))
    return jax.lax.top_k(scores, k)[1].astype(jnp.int32)


@pytest.mark.parametrize("held,offset", [(8, 0), (4, 4), (3, 13), (1, 15)])
def test_dispatch_orders_rows_by_expert_and_drops_none(held, offset):
    n, total, k = 37, 16, 5
    idx = _random_idx(jax.random.PRNGKey(1), n, total, k)
    d = moe_lib.dropless_dispatch(idx, expert_offset=offset, n_held=held)
    assert d.rows.shape == (moe_lib.dropless_buffer_rows(n, k, held),)
    counts = np.array([(np.asarray(idx) == offset + e).sum()
                       for e in range(held)])
    np.testing.assert_array_equal(np.asarray(d.group_sizes), counts)
    assert int(d.dropped) == 0
    rows = np.asarray(d.rows)
    live = rows[:counts.sum()]
    assert (rows[counts.sum():] == n).all()          # empty rows: no token
    # expert e's rows hold exactly the tokens that chose it, in token order
    start = 0
    for e, c in enumerate(counts):
        chose = np.flatnonzero((np.asarray(idx) == offset + e).any(1))
        np.testing.assert_array_equal(live[start:start + c], chose)
        start += c
    # and pos points each kept assignment at its own row
    pos, kept = np.asarray(d.pos), np.asarray(d.kept)
    for t, e in zip(*np.nonzero(kept)):
        assert rows[pos[t, e]] == t


def test_the_dropped_counter_is_live(monkeypatch):
    """``moe_tokens_dropped`` is 0 because the buffer is the worst case's,
    not because nothing counts: with the sizing rule made too small, the
    counter reads exactly what found no row."""
    n, held = 16, 2
    idx = jnp.tile(jnp.array([[0, 1]], jnp.int32), (n, 1))  # all on 0 and 1
    full = moe_lib.dropless_dispatch(idx, expert_offset=0, n_held=held)
    assert int(full.dropped) == 0 and int(full.group_sizes.sum()) == 2 * n
    assert bool(full.kept.all())
    monkeypatch.setattr(moe_lib, "dropless_buffer_rows",
                        lambda n_tokens, k, n_held: 20)
    short = moe_lib.dropless_dispatch(idx, expert_offset=0, n_held=held)
    assert int(short.dropped) == 2 * n - 20
    np.testing.assert_array_equal(np.asarray(short.group_sizes), [16, 4])
    assert int(short.kept.sum()) == 20


def test_grouped_product_equals_the_dense_loop():
    n, total, k, held, d_in, d_h = 29, 8, 3, 8, 12, 20
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    idx = _random_idx(ks[0], n, total, k)
    x = jax.random.normal(ks[1], (n, d_in))
    w1 = jax.random.normal(ks[2], (held, d_in, d_h)) / 3
    w2 = jax.random.normal(ks[3], (held, d_h, d_in)) / 4
    weights = jax.random.uniform(ks[4], (n, held))
    d = moe_lib.dropless_dispatch(idx, expert_offset=0, n_held=held)
    ys = moe_lib.dropless_experts(moe_lib.dropless_gather(x, d), w1, w2, d,
                                  relu2)
    got = moe_lib.dropless_combine(ys, weights, d)
    want = jnp.zeros((n, d_in))
    for e in range(held):
        chosen = (idx == e).any(-1)
        want = want + jnp.where(chosen, weights[:, e], 0.0)[:, None] * (
            relu2(x @ w1[e]) @ w2[e])
    _close(got, want)


def _layer(cfg, params, u, **kw):
    layer = LatentMoE(LMConfig.from_dict(cfg), jnp.float32, **kw)
    out, mut = layer.apply({"params": params}, u, mutable=["counters"])
    counters = {k: v[0] for k, v in mut["counters"].items()}
    return out, counters


def _ref_layer(cfg, params, u, **kw):
    return u + ref.latent_moe(params, ref.rms_norm(u, params["norm"],
                                                   cfg["norm_eps"]), cfg,
                              **kw)


@pytest.mark.parametrize("held,total,offset", [(8, 8, 0), (4, 16, 4),
                                               (2, 16, 14)])
def test_layer_share_equals_the_reference(held, total, offset):
    cfg = _cfg(held, total, offset)
    params = _moe_params(cfg)
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 19, cfg["hidden_size"]))
    out, counters = _layer(cfg, params, u)
    _close(out, _ref_layer(cfg, params, u))
    assert int(counters[moe_lib.COUNTER_DROPPED]) == 0
    assert float(counters[moe_lib.COUNTER_LOAD]) >= 1.0

    def loss(fn):
        return lambda p, v: jnp.sum(jnp.square(fn(cfg, p, v)))

    got = jax.grad(lambda p, v: loss(lambda c, q, w: _layer(c, q, w)[0])(
        p, v), argnums=(0, 1))(params, u)
    want = jax.grad(loss(_ref_layer), argnums=(0, 1))(params, u)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        scale = float(jnp.abs(b).max()) + 1e-9
        assert float(jnp.abs(a - b).max()) <= 2e-5 * scale, \
            jax.tree_util.keystr(path)


def test_dropless_under_skew_every_token_on_one_held_expert():
    """The router is rigged so that every token's first choice is held
    expert 2: its group is the whole batch, nothing is dropped, and the
    result is the reference's."""
    cfg = _cfg(held=4, total=16, offset=4)
    params = dict(_moe_params(cfg))
    bias = jnp.zeros((16,)).at[6].set(10.0)       # expert 6 = held expert 2
    params["router_bias"] = bias
    u = jax.random.normal(jax.random.PRNGKey(4), (3, 16, cfg["hidden_size"]))
    out, counters = _layer(cfg, params, u)
    _close(out, _ref_layer(cfg, params, u))
    assert int(counters[moe_lib.COUNTER_DROPPED]) == 0
    # 48 tokens on one expert against the others' share: far from balanced
    assert float(counters[moe_lib.COUNTER_LOAD]) > 1.5
    idx = ref.route(params, ref.rms_norm(u, params["norm"], 1e-5).reshape(
        -1, cfg["hidden_size"]), cfg)[1]
    assert bool((idx == 6).any(-1).all())


def test_all_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of four experts each, the shared expert counted once
    (zeroed in all shares but the first) and the latent projections being
    the same in every share, add up to the reference's layer over all
    sixteen experts."""
    total = 16
    full_cfg = _cfg(held=total, total=total, offset=0, topk=5)
    full = _moe_params(full_cfg, seed=7)
    u = jax.random.normal(jax.random.PRNGKey(5),
                          (2, 23, full_cfg["hidden_size"]))
    want = _ref_layer(full_cfg, full, u) - u
    got = jnp.zeros_like(u)
    for share in range(4):
        cfg = _cfg(held=4, total=total, offset=4 * share, topk=5)
        p = dict(full, w1=full["w1"][4 * share:4 * share + 4],
                 w2=full["w2"][4 * share:4 * share + 4])
        if share:
            p["shared_down"] = jnp.zeros_like(full["shared_down"])
        out, counters = _layer(cfg, p, u)
        assert int(counters[moe_lib.COUNTER_DROPPED]) == 0
        got = got + (out - u)
    _close(got, want, rtol=2e-5)


def test_routed_experts_left_out_is_another_result():
    cfg = _cfg(4, 16, 4)
    params = _moe_params(cfg)
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 16, cfg["hidden_size"]))
    whole = _ref_layer(cfg, params, u)
    without = _ref_layer(cfg, params, u, drop_routed=True)
    assert float(jnp.abs(whole - without).max()) > 1e-2
