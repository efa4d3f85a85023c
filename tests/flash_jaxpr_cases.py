"""The flash kernels' existing calls, traced: what
``tests/test_sdar_lm.py::test_none_and_causal_rules_trace_to_the_flags_jaxprs``
compares with ``tests/fixtures/flash_jaxprs/*.txt.gz``, which the same
function wrote from the commit before the mask rule (PR 37's parent), where
``causal`` was a flag.  One shape each: DANet's position attention (no
rule), causal grouped-query attention, and the causal call given a key set;
forward and reverse, kernel bodies and index maps."""

import jax
import jax.numpy as jnp
import numpy as np

from distributedpytorch_tpu.ops import pallas_attention as pa


def _calls(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _calls(sub)
    return found


def _text(fn, *args) -> str:
    jaxpr = jax.make_jaxpr(fn)(*args)
    text = str(jaxpr)
    for eqn in _calls(jaxpr.jaxpr):  # the printed equation leaves them out
        for bm in eqn.params["grid_mapping"].block_mappings:
            text += "\nINDEX_MAP " + str(bm.index_map_jaxpr)
    return text


def _heads(s, qh, kvh, d=16):
    r = np.random.RandomState(0)
    return tuple(jnp.asarray(r.randn(2, s, h, d), jnp.float32)
                 for h in (qh, kvh, kvh))


def traced() -> dict:
    r = np.random.RandomState(1)
    q, k = (jnp.asarray(r.randn(2, 300, 16), jnp.float32) for _ in range(2))
    v = jnp.asarray(r.randn(2, 300, 32), jnp.float32)
    out = {"position_300": _text(jax.grad(
        lambda *a: (pa.flash_position_attention(*a) ** 2).sum(), (0, 1, 2)),
        q, k, v)}
    q, k, v = _heads(1536, 4, 1)
    out["causal_1536"] = _text(jax.grad(
        lambda *a: (pa.flash_causal_attention(*a) ** 2).sum(), (0, 1, 2)),
        q, k, v)
    keep = jnp.tril(jnp.ones((2, 1536, 1536), jnp.int8))

    def sparse(q, k, v):
        o, lse = pa.flash_sparse_attention(q, k, v, keep)
        return (o ** 2).sum() + pa.flash_head_mean_probs(q, k, keep,
                                                         lse).sum()

    out["sparse_1536"] = _text(jax.grad(sparse, (0, 1, 2)), q, k, v)
    return out
