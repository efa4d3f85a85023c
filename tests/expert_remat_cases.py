"""What a rematerialised expert block keeps (``EXPERT_KEPT`` of
models/nemotron_h.py and models/keye_lm.py): the counts and comparisons that
tests/test_nemotron_h.py, test_keye_lm.py and test_sdar_lm.py make, each on
its own model's block.  A policy saves the result of the ``name`` primitive,
and a derivative whose residual is another variable still replays its
producers: so what is counted here is products, selections and loops in the
differentiated jaxpr, not names.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

#: 40 tokens: no width of a preset, so an operand's shape names its product
POSITIONS = (2, 20)


def eqns_named(jaxpr, primitive):
    """Every equation of ``primitive`` in ``jaxpr``, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += eqns_named(sub, primitive)
    return found


def forward_products(jaxpr, tokens: int, hidden: int, width: int) -> int:
    """The products ``(tokens, hidden) @ (hidden, width)`` in ``jaxpr``: a
    forward product of the block's normed input (its two reverse products
    have other operands)."""
    return [tuple(v.aval.shape for v in e.invars)
            for e in eqns_named(jaxpr, "dot_general")].count(
                ((tokens, hidden), (hidden, width)))


def block_case(cls, cfg, policy="none"):
    """``(loss, params, u)``: a loss over the output of the expert block
    ``cls(cfg)`` in float32 — rematerialised under ``policy`` (``None``: a
    bare ``nn.remat``) or, by default, not at all — with seeded parameters
    and input."""
    wrapped = cls if policy == "none" else nn.remat(cls, policy=policy)
    block = wrapped(cfg, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(2),
                          POSITIONS + (cfg.hidden_size,))
    params = block.init(jax.random.PRNGKey(3), u)["params"]

    def loss(params, u):
        out, _ = block.apply({"params": params}, u, mutable=["counters"])
        return (out ** 2).sum()

    return loss, params, u


def replay_counts(loss, params, u, widths, k: int) -> dict:
    """In the jaxpr of ``loss``'s gradient: the top-k's, the gathers of
    ``k`` chosen scores a token, the forward products of the block's input
    with a weight ``widths`` wide (one entry each), and the chunk loops of
    the forward pass (the loop that runs the two grouped products alone;
    the reverse pass's runs six)."""
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, u).jaxpr
    tokens, hidden = math.prod(u.shape[:-1]), u.shape[-1]
    return {
        "top_k": len(eqns_named(jaxpr, "top_k")),
        "gathers": [e.outvars[0].aval.shape
                    for e in eqns_named(jaxpr, "gather")].count((tokens, k)),
        "products": [forward_products(jaxpr, tokens, hidden, w)
                     for w in widths],
        "forward_loops": sum(
            len(eqns_named(e.params["body_jaxpr"].jaxpr,
                           "ragged_dot_general")) == 2
            for e in eqns_named(jaxpr, "while")),
    }


def assert_kept_block_is_the_bare_blocks(cls, cfg, policy, rtol):
    """Loss and every gradient of the block under ``policy`` equal the
    bare ``nn.remat``'s to the bit (the kept values are the values the
    replay computes; op by op, so no fusion stands between them), and the
    block's that recomputes nothing within ``rtol`` of a leaf's largest."""
    def value_and_grads(policy):
        loss, params, u = block_case(cls, cfg, policy)
        return jax.value_and_grad(loss, (0, 1))(params, u)

    kept, bare, plain = map(value_and_grads, (policy, None, "none"))
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(bare)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(kept),
                            jax.tree.leaves(plain)):
        gap = float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-12)
        assert gap <= rtol, (jax.tree_util.keystr(path), gap)


def kept_bytes(cls, cfg, names, batch: int, seq_len: int, dtype) -> int:
    """Bytes of the arrays that the block names, one entry of ``names``
    each, at ``batch`` sequences of ``seq_len`` positions in ``dtype``."""
    block = cls(cfg, dtype)
    u = jax.ShapeDtypeStruct((batch, seq_len, cfg.hidden_size), dtype)
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0), u)["params"]

    def apply(p, u):
        return block.apply({"params": p}, u, mutable=["counters"])[0]

    # differentiated: a rule of the block's own may name what it keeps
    jaxpr = jax.make_jaxpr(lambda p, u: jax.jvp(apply, (p, u), (p, u)))(
        params, u).jaxpr
    named = {e.params["name"]: e.outvars[0].aval
             for e in eqns_named(jaxpr, "name")}
    assert sorted(named) == sorted(names)
    return sum(a.size * a.dtype.itemsize for a in named.values())
