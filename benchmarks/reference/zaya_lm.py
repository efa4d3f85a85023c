"""Plain reference of a ``zaya_lm`` stage on the training path (the ZAYA1
family, ``model_type`` ``zaya``: compressed convolutional attention and a
top-1 expert layer with an MLP router whose state carries across depth):
forward, loss, gradients and the SGD-momentum step in ``jax.numpy`` and
float32, with every matrix product at ``highest`` precision.  It follows the
description below as written and imports nothing of the program (a few
helpers come from the other plain references).

Per layer ``l``, on ``h`` (B, S, d), two blocks ``l<2l>``, ``l<2l+1>``, each
``h <- a * h + b * f(RMSNorm(h))`` with ``a`` (``res_scale``) and ``b``
(``out_scale``) per channel:

* attention (CCA), on ``u = RMSNorm(h)``, ``r = Hq / Hkv`` query heads a
  key/value head, ``hd`` channels a head:
  ``q0 = u Wq`` (S, Hq hd), ``k0 = u Wk`` (S, Hkv hd), ``v = u Wv`` with the
  second half of each key/value head's channels taken from token ``t - 1``
  (zero at ``t = 0``);
  ``c1[t] = b0 + sum_i w0[i] * [q0 | k0][t - K0 + 1 + i]`` (depthwise,
  causal, ``K0 = cca_time0`` taps); per head ``g`` of the ``Hq + Hkv`` heads
  of ``c1``: ``c2[t, g] = b1[g] + sum_i c1[t - K1 + 1 + i, g] @ w1[i, g]``
  (``K1 = cca_time1``); ``m_j = (mean of q0 over query group j + k0_j) / 2``;
  ``q_h = c2_h + m_j(h)``, ``k_j = c2_j + m_j``; ``q_h <- tau_h q_h / |q_h|``,
  ``k_j <- k_j / |k_j|``; the rotary embedding over the first
  ``partial_rotary_factor * hd`` channels of every head (rotate-half within
  them, ``rope_theta`` of ``rope_parameters.hybrid``); ``o[t, h] =
  softmax_{s <= t}(q[t, h] . k[s, j(h)]) v[s, j(h)]``, no other scale; ``f =
  o Wo``.  Computed in query blocks of :data:`QUERY_BLOCK`, so that 8,192
  positions fit.
* experts, on ``u = RMSNorm(h)``: ``r_l = u W_down``; ``rbar_l = (1 - g_l)
  r_l + g_l rbar_(l-1)`` where there is a previous layer in the stage, else
  ``rbar_l = r_l``; ``logits = GELU(GELU(rbar W1 + c1) W2 + c2) W3`` (exact
  GELU), one logit for every published expert and one for the skip slot
  (the last); ``e(t) = argmax logits[t]``; ``p = softmax(logits)``; ``f[t] =
  p[t, e(t)] (silu(u Wgate_e) * (u Wup_e)) Wdown_e`` if ``e(t)`` is an expert
  HELD here, else 0 (the skip slot, an expert held elsewhere), as a plain
  loop over the held experts with a mask (``w1 = [Wgate | Wup]``).  The
  router, its state and the softmax in float32 in every variant.

then a final RMSNorm, the tied head ``logits = RMSNorm(h) E^T`` (``E`` the
embedding) and the next-token cross-entropy.

Departures from the published description, each also under ``assumed`` in
the configuration's file: the order of the two convolutions and their
biases, the q-k mean over a query group, the temperature and its start, the
value shift's halves (the CCA paper, arXiv:2510.04476, which gives the
mechanisms and not the config's keys for them); the depth averaging's form,
the router MLP's depth, GELU and biases, the skip slot, the gate (the ZAYA1
report and the catalog's ``described_as``: "ZAYA router MLP (hidden 256)",
"residual-scaled MoD"); the per-channel residual scales; the balancing bias
held at nought (the token step has no non-gradient state).

The configuration is the benchmark's JSON: ``num_experts`` and
``vocab_size`` are what is *held here*; ``published.num_experts`` is the
router's width less the skip slot.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# what the plain token references compute alike: a product under a rounding,
# RMSNorm, the blocked next-token loss, the spec's leaf test, the
# rotate-half rotary embedding
from reference.keye_lm import rotary, text_positions  # noqa: E402
from reference.nemotron_h import (LOSS_BLOCK, _is_leaf, _mm, _r,  # noqa: E402,F401
                                  blocked_xent, layer_name, rms_norm,
                                  shifted_xent)

F32 = jnp.float32
#: query rows of a block of the reference's attention (memory only, not
#: arithmetic)
QUERY_BLOCK = 512
L2_EPS = 1e-12


# ------------------------------------------------------------------ shapes
def dims(cfg: dict) -> dict:
    rope = cfg["rope_parameters"]["hybrid"]
    hd = cfg["head_dim"]
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "qh": cfg["num_attention_heads"], "kvh": cfg["num_key_value_heads"],
        "hd": hd, "k0": cfg["cca_time0"], "k1": cfg["cca_time1"],
        "rot": int(hd * rope["partial_rotary_factor"]),
        "theta": float(rope["rope_theta"]),
        "rh": cfg["router_hidden_size"],
        "e_all": cfg.get("published", {}).get("num_experts",
                                              cfg["num_experts"]),
        "e_held": cfg["num_experts"], "e_off": cfg.get("expert_offset", 0),
        "ei": cfg["moe_intermediate_size"],
        "eps": cfg["rms_norm_eps"],
    }


def param_spec(cfg: dict) -> dict:
    """``{path: (shape, kind)}`` as a tree: the program's parameter tree,
    leaf for leaf (block ``2l`` is layer ``l``'s attention, ``2l + 1`` its
    expert layer; the stage's first expert layer has no depth average)."""
    s = dims(cfg)
    d, v, hd, rh = s["d"], s["v"], s["hd"], s["rh"]
    heads = s["qh"] + s["kvh"]
    scales = {"norm": ((d,), "scale"), "res_scale": ((d,), "scale"),
              "out_scale": ((d,), "scale")}
    spec = {"embed": ((v, d), "embed"), "final_norm": ((d,), "head_scale")}
    for i in range(s["layers"]):
        spec[layer_name(2 * i)] = {
            **scales,
            "q_proj": ((d, s["qh"] * hd), "dense"),
            "k_proj": ((d, s["kvh"] * hd), "dense"),
            "v_proj": ((d, s["kvh"] * hd), "dense"),
            "o_proj": ((s["qh"] * hd, d), "residual_out"),
            "conv0_w": ((s["k0"], heads * hd), "dense"),
            "conv0_b": ((heads * hd,), "bias"),
            "conv1_w": ((s["k1"], heads, hd, hd), "grouped_conv"),
            "conv1_b": ((heads, hd), "bias"),
            "temperature": ((s["qh"],), "temperature")}
        spec[layer_name(2 * i + 1)] = {
            **scales,
            "router_down": ((d, rh), "dense"),
            "router_w1": ((rh, rh), "gelu_in"), "router_b1": ((rh,), "bias"),
            "router_w2": ((rh, rh), "gelu_both"),
            "router_b2": ((rh,), "bias"),
            "router_w3": ((rh, s["e_all"] + 1), "gelu_out"),
            "w1": ((s["e_held"], d, 2 * s["ei"]), "dense"),
            "w2": ((s["e_held"], s["ei"], d), "residual_out")}
        if i:
            spec[layer_name(2 * i + 1)]["depth_average"] = ((), "half")
    return spec


def make_weights(key, cfg: dict) -> dict:
    """Every leaf but the router's from ``key``.  Products are LeCun-normal
    (the grouped convolution's over both its taps and a head's channels); the
    two that write into the residual stream (``o_proj``, ``w2``) are scaled
    by ``(2 x published layers)^-1/2``, as ``reference/keye_lm.py::
    make_weights`` says why; scales near one, biases near nought, the temperature near
    ``sqrt(hd)`` and the depth averages near one half.

    The embedding is also the head.  It is drawn at unit spread, as the
    other token models' embeddings, and the final norm's scale starts near
    ``d^-1/2``, so that the tied head's logits on the normed state start at
    unit spread too.  Drawn at ``d^-1/2`` instead, the residual stream
    starts 45 times smaller than the blocks' outputs expect: by the third
    layer the mean of the values that a random attention hands every query
    outgrows the tokens' own states, every position hands the router the
    same state (mean cosine 0.57 at the third layer, 0.86 at the seventh),
    and one slot takes 86% of a layer's tokens (the reference at the
    published widths with 64-wide experts and 4,096 rows, 2,048 positions,
    on the CPU); at unit spread the mean cosine stays at 0.001 and no slot
    takes more than 9.4% in any layer.  On the chip the held experts' fullest
    read 7.98 times their mean over three seeds (a TPU v5e, 8,192 tokens).

    The router's MLP is drawn so that every slot starts with an even share
    of the tokens, as a router that a balancing bias keeps balanced has:
    the products that feed a GELU at ``(2 / fan-in)^1/2`` (a GELU halves
    the variance it is given), and the two that read a GELU's output with
    each output's weights centred over the inputs, so that the GELUs'
    common positive mean hands no hidden unit, and then no slot, a head
    start.  Over four seeds and seven layers of 8,192 normal inputs each (the
    router alone at the published widths, on the CPU): drawn LeCun-normal
    and uncentred, a slot took 0.02% to 42% of a layer's tokens, the skip
    slot 0.5% to 20%, the held experts 24% to 70%; drawn so, 3.2% to 9.5%,
    3.7% to 8.1% and 43% to 52%, and the tokens whose two largest logits
    lie within 0.01 halve (2.5-5.0% of them, from 4.2-10.8%).

    The router's leaves (``router_*``) are drawn from a key of the
    configuration's own (``PRNGKey(0)`` folded with the leaf's place) and
    **not from the run's seed**, as ``reference/sdar_lm.py::mask_experts``
    draws what decides its expert layers' rows: how many tokens a layer
    hands the experts held here is what the grouped products work on, and
    with the seed's own router it moved the rate by 0.5% between seeds
    (PERF.md section 6, PR 41).  Over twelve seeds of the stage at 2,048
    positions (the reference on the CPU, one expert held), the held share
    of a stage's tokens spreads by 1.13% (standard deviation, of the
    tokens) drawn from the seed and by 0.60% so, against 0.42% that
    independent tokens alone would give.  Every other leaf, and the ids,
    follow the seed."""
    depth = cfg.get("published", {}).get("num_hidden_layers",
                                         cfg["num_hidden_layers"])
    hd = cfg["head_dim"]
    spec = param_spec(cfg)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)
    names = [str(path[-1].key) for path, _ in
             jax.tree_util.tree_flatten_with_path(spec, is_leaf=_is_leaf)[0]]
    keys = jax.random.split(key, len(leaves))
    keys = [jax.random.fold_in(jax.random.PRNGKey(0), i)
            if name.startswith("router_") else k
            for i, (name, k) in enumerate(zip(names, keys))]
    out = []
    for k, (shape, kind) in zip(keys, leaves):
        z = jax.random.normal(k, shape, F32)
        if kind in ("dense", "residual_out"):
            z = z / math.sqrt(shape[-2])
            if kind == "residual_out":
                z = z / math.sqrt(2 * depth)
        elif kind in ("gelu_in", "gelu_both"):
            z = z * math.sqrt(2.0 / shape[-2])
            if kind == "gelu_both":
                z = z - z.mean(axis=0)
        elif kind == "gelu_out":
            z = z / math.sqrt(shape[-2])
            z = z - z.mean(axis=0)
        elif kind == "grouped_conv":
            z = z / math.sqrt(shape[0] * shape[-2])
        elif kind == "embed":
            pass
        elif kind == "head_scale":
            z = (1.0 + 0.1 * z) / math.sqrt(shape[-1])
        elif kind == "bias":
            z = 0.01 * z
        elif kind == "scale":
            z = 1.0 + 0.1 * z
        elif kind == "temperature":
            z = math.sqrt(hd) * (1.0 + 0.1 * z)
        elif kind == "half":
            z = 0.5 + 0.05 * z
        else:
            raise ValueError(kind)
        out.append(z)
    return jax.tree.unflatten(treedef, out)


# -------------------------------------------------------------- arithmetic
def earlier(x, by: int):
    """``x[:, t - by]``, zero before the sequence."""
    return jnp.pad(x, ((0, 0), (by, 0)) + ((0, 0),) * (x.ndim - 2))[
        :, :x.shape[1]]


def unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def turn_leading(x, positions, theta, dims_: int):
    return jnp.concatenate(
        [rotary(x[..., :dims_], positions, theta), x[..., dims_:]], -1)


def attention(p, u, cfg, q=None, no_conv=False, no_shift=False,
              full_rotary=False):
    """``o Wo`` of a CCA block.  Faults: ``no_conv`` leaves both
    convolutions out (``c2 = [q0 | k0]``), ``no_shift`` the value shift,
    ``full_rotary`` turns every channel of a head."""
    s = dims(cfg)
    b, length, _ = u.shape
    qh, kvh, hd = s["qh"], s["kvh"], s["hd"]
    heads, r = qh + kvh, qh // kvh
    q0 = _mm(q, u, p["q_proj"])
    k0 = _mm(q, u, p["k_proj"])
    vv = _mm(q, u, p["v_proj"]).reshape(b, length, kvh, hd)
    if not no_shift:
        vv = jnp.concatenate([vv[..., :hd // 2],
                              earlier(vv, 1)[..., hd // 2:]], -1)
    z = jnp.concatenate([q0, k0], -1)
    if no_conv:
        c2 = z.reshape(b, length, heads, hd)
    else:
        k0_, k1_ = s["k0"], s["k1"]
        c1 = p["conv0_b"] + sum(p["conv0_w"][i] * earlier(z, k0_ - 1 - i)
                                for i in range(k0_))
        c1 = c1.reshape(b, length, heads, hd)
        c2 = p["conv1_b"] + sum(
            jnp.einsum("blgc,gcd->blgd", _r(q, earlier(c1, k1_ - 1 - i)),
                       p["conv1_w"][i] if q is None
                       else q.weight(p["conv1_w"][i]))
            for i in range(k1_))
    mean = 0.5 * (q0.reshape(b, length, kvh, r, hd).mean(3)
                  + k0.reshape(b, length, kvh, hd))
    qq = (c2[:, :, :qh].reshape(b, length, kvh, r, hd)
          + mean[:, :, :, None]).reshape(b, length, qh, hd)
    kk = c2[:, :, qh:] + mean
    positions = text_positions(b, length)
    rot = hd if full_rotary else s["rot"]
    qq = turn_leading(unit(qq), positions, s["theta"], rot) \
        * p["temperature"][:, None]
    kk = turn_leading(unit(kk), positions, s["theta"], rot)
    kk = jnp.repeat(kk, r, axis=2)
    vv = jnp.repeat(vv, r, axis=2)
    qq, kk, vv = _r(q, qq), _r(q, kk), _r(q, vv)
    pos = jnp.arange(length)

    @jax.checkpoint
    def rows(q_blk, q_pos):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_blk, kk)
        sc = jnp.where(q_pos[None, None, :, None] >= pos[None, None, None, :],
                       sc, -jnp.inf)
        w = _r(q, jax.nn.softmax(sc, axis=-1))
        return jnp.einsum("bhqk,bkhd->bqhd", w, vv)

    if length > QUERY_BLOCK and length % QUERY_BLOCK == 0:
        nb = length // QUERY_BLOCK
        qb = jnp.moveaxis(qq.reshape(b, nb, QUERY_BLOCK, qh, hd), 1, 0)
        out = lax.map(lambda a: rows(*a), (qb, pos.reshape(nb, QUERY_BLOCK)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, length, qh * hd)
    else:
        out = rows(qq, pos).reshape(b, length, qh * hd)
    return _mm(q, _r(q, out), p["o_proj"])


def route(p, x, state, cfg, no_eda=False):
    """``(logits, choice, router state)`` in float32: the state is the
    depth average of this layer's down-projection and ``state``, the
    previous layer's (``None``, or ``no_eda`` as a fault: this layer's
    alone)."""
    r = x.astype(F32) @ p["router_down"]
    if state is not None and not no_eda:
        r = (1 - p["depth_average"]) * r + p["depth_average"] * state
    h = jax.nn.gelu(r @ p["router_w1"] + p["router_b1"], approximate=False)
    h = jax.nn.gelu(h @ p["router_w2"] + p["router_b2"], approximate=False)
    logits = h @ p["router_w3"]
    return logits, jnp.argmax(logits, -1), r


def experts(p, u, state, cfg, q=None, drop_routed=False, no_eda=False):
    """``(f, router state)``: the chip's share of the expert layer;
    ``drop_routed`` leaves the routed experts out (a fault)."""
    s = dims(cfg)
    x = u.reshape(-1, s["d"])
    logits, choice, r = route(p, x, state, cfg, no_eda)
    gates = jax.nn.softmax(logits, axis=-1)
    off = s["e_off"]

    @jax.checkpoint
    def one_expert(routed, xs):
        w1, w2, e = xs
        w = jnp.where(choice == off + e, jnp.take(gates, off + e, axis=1), 0.0)
        gate, up = jnp.split(_mm(q, x, w1), 2, axis=-1)
        hidden = _r(q, jax.nn.silu(gate) * up)
        return routed + w[:, None] * _mm(q, hidden, w2), None

    routed = jnp.zeros_like(x)
    if not drop_routed:
        routed, _ = lax.scan(one_expert, routed,
                             (p["w1"], p["w2"], jnp.arange(s["e_held"])))
    return routed.reshape(u.shape), r


def forward(params, tokens, cfg, q=None, remat=False, drop_routed=False,
            no_conv=False, no_shift=False, full_rotary=False, no_eda=False,
            logits=True):
    """The tied head's logits, or the normed state it is applied to."""
    s = dims(cfg)
    eps = s["eps"]

    def scaled(p, x, y):
        return _r(q, p["res_scale"] * x + p["out_scale"] * y)

    def attn_block(p, x):
        return scaled(p, x, attention(p, _r(q, rms_norm(x, p["norm"], eps)),
                                      cfg, q, no_conv, no_shift, full_rotary))

    def moe_block(p, x, state):
        y, r = experts(p, _r(q, rms_norm(x, p["norm"], eps)), state, cfg, q,
                       drop_routed, no_eda)
        return scaled(p, x, y), r

    if remat:
        attn_block, moe_block = map(jax.checkpoint, (attn_block, moe_block))
    x = params["embed"][tokens]
    state = None
    for i in range(s["layers"]):
        x = attn_block(params[layer_name(2 * i)], x)
        x, state = moe_block(params[layer_name(2 * i + 1)], x, state)
    h = _r(q, rms_norm(x, params["final_norm"], eps))
    return _mm(q, h, params["embed"].T) if logits else h


def loss_fn(params, tokens, cfg, q=None, remat=False, **faults):
    """The next-token cross-entropy over the tied head."""
    length = tokens.shape[1]
    blocked = length > LOSS_BLOCK and length % LOSS_BLOCK == 0
    out = forward(params, tokens, cfg, q, remat, logits=not blocked,
                  **faults)
    return blocked_xent(out, params["embed"].T, tokens, 1, q) if blocked \
        else shifted_xent(out, tokens, 1)


def train_step(cfg, opt, params, trace, batch, q=None, remat=True, rows=None,
               **faults):
    """One SGD-momentum step as the program's trainer takes it.  Faults:
    ``rows`` keeps only the first ``rows`` tokens of the step, over its
    sequences; ``drop_routed``, ``no_conv``, ``no_shift``, ``full_rotary``,
    ``no_eda`` as :func:`forward`'s."""
    tokens = batch["tokens"]
    if rows is not None:
        tokens = tokens[:, :max(3, rows // tokens.shape[0])]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg, q,
                                                  remat, **faults)
    trace = jax.tree.map(lambda g, t: g + opt["momentum"] * t, grads, trace)
    params = jax.tree.map(lambda p, t: p - opt["learning_rate"] * t, params,
                          trace)
    return params, trace, loss


# ---------------------------------------------------------- operation count
def flops_per_sequence(cfg: dict, seq_len: int) -> dict:
    """Model FLOPs of one training step over one sequence, term by term:
    6 x (matmul parameters a token passes through) x tokens for every
    product with a weight (2 forward, 4 backward) — the attention block's
    four projections and its grouped convolution (``cca_proj``), the router's
    down-projection and MLP (``router``), the routed experts at the tokens
    routed to *held* experts under uniform routing over the published
    experts (``held / published`` of a token a layer: the skip slot's share
    is counted as work), the tied head; the attention's seven products
    (forward S and P v; reverse S again, dP, dV, dK, dQ) over the causal
    half of the pairs, ``S^2 / 2`` (``cca_scores``).  A multiply-add counts
    2; recomputation, the depthwise convolution, norms, activations, the
    softmax and the gathers are not counted."""
    s = dims(cfg)
    d, v, t, hd = s["d"], s["v"], seq_len, s["hd"]
    heads = s["qh"] + s["kvh"]
    layer = {
        "cca_proj": 6 * t * (d * hd * (2 * s["qh"] + 2 * s["kvh"])
                             + s["k1"] * heads * hd * hd),
        "cca_scores": 7 * 2 * (t * t / 2) * hd * s["qh"],
        "router": 6 * t * (d * s["rh"] + 2 * s["rh"] * s["rh"]
                           + s["rh"] * (s["e_all"] + 1)),
        "moe_routed": 6 * t * s["e_held"] / s["e_all"] * 3 * d * s["ei"],
    }
    terms = {k: float(f * s["layers"]) for k, f in layer.items()}
    terms["lm_head"] = float(6 * t * d * v)
    terms["total"] = sum(terms.values())
    return terms
