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


def _dense_loop(idx, x, weights, w1, w2, offset=0):
    """Each held expert over every token, masked to the tokens that chose
    it: what the chunked grouped products must add up to."""
    want = jnp.zeros(x.shape, jnp.float32)
    for e in range(w1.shape[0]):
        chosen = (idx == offset + e).any(-1)
        want = want + jnp.where(chosen, weights[:, e], 0.0)[:, None] * (
            relu2(x @ w1[e]) @ w2[e])
    return want


def _operands(key, n, held, d_in=12, d_h=20):
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (n, d_in)),
            jax.random.uniform(ks[1], (n, held)),
            jax.random.normal(ks[2], (held, d_in, d_h)) / 3,
            jax.random.normal(ks[3], (held, d_h, d_in)) / 4)


def test_grouped_product_equals_the_dense_loop():
    n, total, k, held = 29, 8, 3, 8
    idx = _random_idx(jax.random.PRNGKey(2), n, total, k)
    x, weights, w1, w2 = _operands(jax.random.PRNGKey(12), n, held)
    d = moe_lib.dropless_dispatch(idx, expert_offset=0, n_held=held)
    got, _ = moe_lib.dropless_routed(x, weights, w1, w2, d, relu2)
    _close(got, _dense_loop(idx, x, weights, w1, w2))


def _check_chunked(idx, held, offset, chunk_rows, seed=0):
    """Result, gradients (x, weights, w1, w2) and the chunks counter of the
    chunked path against the dense loop; returns (live rows, chunks, share,
    result, gradients) for the caller's own assertions."""
    n = idx.shape[0]
    x, weights, w1, w2 = _operands(jax.random.PRNGKey(20 + seed), n, held)
    d = moe_lib.dropless_dispatch(idx, expert_offset=offset, n_held=held)
    assert int(d.dropped) == 0

    def chunked(*a):
        return moe_lib.dropless_routed(*a, d, relu2, chunk_rows=chunk_rows)

    def dense(*a):
        return _dense_loop(idx, *a, offset=offset)

    got, share = chunked(x, weights, w1, w2)
    _close(got, dense(x, weights, w1, w2))
    probe = jax.random.normal(jax.random.PRNGKey(30 + seed), x.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * probe)

    g_got = jax.grad(loss(lambda *a: chunked(*a)[0]),
                     argnums=(0, 1, 2, 3))(x, weights, w1, w2)
    g_want = jax.grad(loss(dense), argnums=(0, 1, 2, 3))(x, weights, w1, w2)
    for name, a, b in zip(("x", "weights", "w1", "w2"), g_got, g_want):
        assert bool(jnp.isfinite(a).all()), name
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= 2e-5 * scale + 1e-12, name
    live = int(d.group_sizes.sum())
    m = d.rows.shape[0]
    r = moe_lib.chunk_rows_of(m, chunk_rows)
    chunks = -(-m // r)
    # (d) the counter: chunks that hold a token over chunks in all
    assert float(share) == pytest.approx(-(-live // r) / chunks)
    return live, chunks, float(share), got, g_got


def _idx_with_counts(n, counts, k, total):
    """(N, k) choices that put exactly ``counts[e]`` tokens on held expert
    ``e`` (numbered from 0): token ``t`` picks held experts by a fixed
    stride, its other choices fall past the held ones."""
    held = len(counts)
    assert total >= held + k
    idx = np.tile(np.arange(held, held + k, dtype=np.int32), (n, 1))
    slot = np.zeros(n, dtype=np.int64)
    for e, c in enumerate(counts):
        tokens = (np.arange(c) * 5 + e) % n
        assert len(set(tokens.tolist())) == c
        for t in tokens:
            idx[t, slot[t]] = e
            slot[t] += 1
    assert slot.max() <= k
    return jnp.asarray(idx)


#: 24 tokens, top-4 of 16, 4 held: a 96-row buffer.  (counts per held
#: expert, chunk rows) -> what the case is about
_CHUNK_CASES = {
    "one_chunk": ([5, 3, 7, 2], None),                # 17 live, 1 chunk of 96
    "several_chunks": ([5, 3, 7, 2], 8),              # 3 of 12 chunks run
    "ends_on_a_boundary": ([5, 3, 6, 2], 8),          # 16 live = 2 chunks
    "one_past_a_boundary": ([5, 3, 7, 2], 16),        # 17 live: 2 of 6 chunks
    "group_straddles_a_boundary": ([5, 9, 1, 0], 8),  # expert 1: rows 5-13
    "chunk_does_not_divide_the_buffer": ([5, 3, 7, 2], 7),   # 96 = 13*7 + 5
}


@pytest.mark.parametrize("case", sorted(_CHUNK_CASES))
def test_chunked_result_and_gradients_equal_the_dense_loop(case):
    counts, chunk_rows = _CHUNK_CASES[case]
    idx = _idx_with_counts(24, counts, k=4, total=16)
    live, chunks, share, *_ = _check_chunked(idx, len(counts), 0,
                                             chunk_rows)
    assert live == sum(counts)
    if case == "one_chunk":
        assert (chunks, share) == (1, 1.0)
    if case == "ends_on_a_boundary":
        assert share == pytest.approx(2 / 12)
    if case == "one_past_a_boundary":
        assert share == pytest.approx(2 / 6)
    if case == "several_chunks":
        assert share == pytest.approx(3 / 12)


@pytest.mark.parametrize("chunk_rows", [None, 16, 10])
def test_worst_case_routing_runs_every_chunk(chunk_rows):
    """Every token on every held expert: the whole buffer is live, every
    chunk runs, nothing is dropped, and the result is the loop's."""
    n, held = 20, 4
    idx = jnp.tile(jnp.arange(held, dtype=jnp.int32)[None, :], (n, 1))
    live, _, share, *_ = _check_chunked(idx, held, 0, chunk_rows, seed=1)
    assert live == moe_lib.dropless_buffer_rows(n, held, held) == n * held
    assert share == 1.0


@pytest.mark.parametrize("chunk_rows", [None, 16])
def test_nothing_routed_here_runs_no_chunk(chunk_rows):
    """No token chooses a held expert: no chunk runs, the result and every
    gradient are zeros, and nothing is NaN."""
    n, held, offset = 20, 4, 4
    idx = jnp.tile(jnp.array([[0, 1, 2, 9]], jnp.int32), (n, 1))
    live, _, share, out, grads = _check_chunked(idx, held, offset,
                                                chunk_rows, seed=2)
    assert (live, share) == (0, 0.0)
    assert not bool(out.any())
    assert not any(bool(g.any()) for g in grads)


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
