"""Plain reference of a ``nemotron_h`` stage on the training path: forward,
loss, gradients and the SGD-momentum step in ``jax.numpy`` and float32, with
every matrix product at ``highest`` precision.  It follows the published
equations as written and imports nothing of the program:

* ``M``  Mamba-2 mixer, the *recurrence* over time (a ``lax.scan``; the
  program computes the chunked SSD form);
* ``*``  causal grouped-query attention with rotary embedding over the whole
  head, softmax over the full causal row, computed in query blocks so that
  8,192 positions fit;
* ``E``  LatentMoE as the chip's share: the router is as wide as the
  published expert count, the top-k and the normalisation are over all of
  them, and the routed sum runs as a plain loop over the experts held here
  with a mask (what the absent experts would add is left out, here and in
  the program alike);
* the multi-token-prediction module (DeepSeek-V3 wiring), sharing the
  embedding and the head with the trunk.

The configuration is the benchmark's JSON (``configs/<name>.json``): counts
of heads, groups, experts and vocabulary rows are what is *held here*;
``published.n_routed_experts`` is the router's width.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
#: blocks of the reference's own computation (memory only, not arithmetic)
TIME_BLOCK = 128
QUERY_BLOCK = 512
LOSS_BLOCK = 1024


# ------------------------------------------------------------------ shapes
def dims(cfg: dict) -> dict:
    """The sizes the equations use, from the configuration's keys."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"],
        "mh": h, "mp": p, "mg": g, "mn": n, "inner": h * p,
        "conv_dim": h * p + 2 * g * n, "k": cfg["conv_kernel"],
        "qh": cfg["num_attention_heads"], "kvh": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"],
        "e_all": cfg.get("published", {}).get("n_routed_experts",
                                              cfg["n_routed_experts"]),
        "e_held": cfg["n_routed_experts"],
        "e_off": cfg.get("expert_offset", 0),
        "topk": cfg["num_experts_per_tok"], "lat": cfg["moe_latent_size"],
        "ei": cfg["moe_intermediate_size"],
        "si": cfg["moe_shared_expert_intermediate_size"],
    }


def _block_spec(kind: str, s: dict) -> dict:
    d = s["d"]
    if kind == "M":
        return {"norm": ((d,), "scale"),
                "in_proj": ((d, 2 * s["inner"] + 2 * s["mg"] * s["mn"]
                             + s["mh"]), "dense"),
                "conv_w": ((s["k"], s["conv_dim"]), "conv"),
                "conv_b": ((s["conv_dim"],), "bias"),
                "dt_bias": ((s["mh"],), "dt_bias"),
                "A_log": ((s["mh"],), "a_log"),
                "D": ((s["mh"],), "scale"),
                "gnorm": ((s["inner"],), "scale"),
                "out_proj": ((s["inner"], d), "dense")}
    if kind == "*":
        return {"norm": ((d,), "scale"),
                "q_proj": ((d, s["qh"] * s["hd"]), "dense"),
                "k_proj": ((d, s["kvh"] * s["hd"]), "dense"),
                "v_proj": ((d, s["kvh"] * s["hd"]), "dense"),
                "o_proj": ((s["qh"] * s["hd"], d), "dense")}
    if kind == "E":
        return {"norm": ((d,), "scale"),
                "router": ((d, s["e_all"]), "router"),
                "router_bias": ((s["e_all"],), "router_bias"),
                "latent_down": ((d, s["lat"]), "dense"),
                "latent_up": ((s["lat"], d), "dense"),
                "w1": ((s["e_held"], s["lat"], s["ei"]), "expert"),
                "w2": ((s["e_held"], s["ei"], s["lat"]), "after_relu2"),
                "shared_up": ((d, s["si"]), "dense"),
                "shared_down": ((s["si"], d), "after_relu2")}
    raise ValueError(f"unknown layer kind {kind!r} (M | * | E)")


def layer_name(i: int) -> str:
    return f"l{i:02d}"


def param_spec(cfg: dict) -> dict:
    """``{path: (shape, kind)}`` as a tree: the program's parameter tree,
    leaf for leaf."""
    s = dims(cfg)
    d, v = s["d"], s["v"]
    spec = {"embed": ((v, d), "embed")}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        spec[layer_name(i)] = _block_spec(kind, s)
    spec["final_norm"] = ((d,), "scale")
    spec["lm_head"] = ((d, v), "dense")
    if cfg.get("num_nextn_predict_layers", 0):
        mtp = {"hnorm": ((d,), "scale"), "enorm": ((d,), "scale"),
               "proj": ((2 * d, d), "dense")}
        for i, kind in enumerate(cfg["mtp_hybrid_override_pattern"]):
            mtp[layer_name(i)] = _block_spec(kind, s)
        mtp["final_norm"] = ((d,), "scale")
        spec["mtp"] = mtp
    return spec


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def make_weights(key, cfg: dict) -> dict:
    """Every leaf from one key.  Products are LeCun-normal; the scales start
    near one; ``dt_bias`` and ``A_log`` are the family's initialisation
    (``dt`` log-uniform in [time_step_min, time_step_max] through the inverse
    softplus, ``A`` uniform in [1, 16]); the router is sized so that the
    sigmoid scores spread over (0.1, 0.9) and the score-correction bias is
    small against that spread.  A product that follows the squared ReLU is
    centred over its inputs: relu(z)^2 has a positive mean, and through an
    uncentred matrix that mean puts one common vector into every token's
    state, which (not the tokens) then decides which experts are popular —
    at random weights the fullest held expert read 3 to 4.6 times the mean
    and the routed work moved by a fifth from seed to seed; a trained
    model's score-correction bias keeps the load even."""
    spec = param_spec(cfg)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    lo = math.log(cfg.get("time_step_min", 0.001))
    hi = math.log(cfg.get("time_step_max", 0.1))
    out = []
    for k, (shape, kind) in zip(keys, leaves):
        if kind in ("dense", "expert", "after_relu2"):
            z = jax.random.normal(k, shape, F32) / math.sqrt(shape[-2])
            if kind == "after_relu2":
                z = z - jnp.mean(z, axis=-2, keepdims=True)
        elif kind == "router":
            z = jax.random.normal(k, shape, F32) * (1.5 / math.sqrt(shape[0]))
        elif kind == "router_bias":
            z = 0.01 * jax.random.normal(k, shape, F32)
        elif kind == "embed":
            z = jax.random.normal(k, shape, F32)
        elif kind == "conv":
            z = jax.random.normal(k, shape, F32) / math.sqrt(shape[0])
        elif kind == "bias":
            z = 0.01 * jax.random.normal(k, shape, F32)
        elif kind == "scale":
            z = 1.0 + 0.1 * jax.random.normal(k, shape, F32)
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, F32, lo, hi))
            dt = jnp.maximum(dt, cfg.get("time_step_floor", 1e-4))
            z = dt + jnp.log(-jnp.expm1(-dt))   # softplus(z) == dt
        elif kind == "a_log":
            z = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
        else:
            raise ValueError(kind)
        out.append(z)
    return jax.tree.unflatten(treedef, out)


# -------------------------------------------------------------- arithmetic
def _mm(q, x, w):
    """``x @ w``; under a rounding ``q`` the operands and the result are
    held in the lower type (the weight's gradient is not)."""
    if q is None:
        return x @ w
    return q(q(x) @ q.weight(w))


def _r(q, x):
    return x if q is None else q(x)


def rms_norm(x, w, eps):
    return w * x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _scan_time(step, carry, xs, length: int):
    """``lax.scan`` over time; for a long sequence in blocks of
    ``TIME_BLOCK`` steps whose insides the reverse pass recomputes, so that
    it keeps one state per block and not one per step."""
    if length <= TIME_BLOCK or length % TIME_BLOCK:
        return lax.scan(step, carry, xs)
    nb = length // TIME_BLOCK
    blocked = jax.tree.map(
        lambda a: a.reshape((nb, TIME_BLOCK) + a.shape[1:]), xs)

    @jax.checkpoint
    def block(c, xb):
        return lax.scan(step, c, xb)

    carry, ys = lax.scan(block, carry, blocked)
    return carry, jax.tree.map(
        lambda a: a.reshape((length,) + a.shape[2:]), ys)


def mamba_mixer(p, u, cfg, q=None):
    """Mamba-2 as the recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x)
    B_t``, ``y_t = C_t . h_t + D x_t``, gated and group-normed."""
    s = dims(cfg)
    b, length, _ = u.shape
    h, pd, g, n, inner = s["mh"], s["mp"], s["mg"], s["mn"], s["inner"]
    zxbcdt = _mm(q, u, p["in_proj"])
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:inner + s["conv_dim"]]
    dt = zxbcdt[..., inner + s["conv_dim"]:]
    padded = jnp.pad(xbc, ((0, 0), (s["k"] - 1, 0), (0, 0)))
    xbc = sum(padded[:, i:i + length] * p["conv_w"][i]
              for i in range(s["k"])) + p["conv_b"]
    xbc = _r(q, jax.nn.silu(xbc))
    x = xbc[..., :inner].reshape(b, length, h, pd)
    bm = xbc[..., inner:inner + g * n].reshape(b, length, g, n)
    cm = xbc[..., inner + g * n:].reshape(b, length, g, n)
    bm = jnp.repeat(bm, h // g, axis=2)     # a head reads its group's B, C
    cm = jnp.repeat(cm, h // g, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])             # (b, l, h)
    a = -jnp.exp(p["A_log"])                            # (h,)

    def step(state, xs):
        x_t, b_t, c_t, dt_t = xs                        # (b,h,p) (b,h,n) ..
        decay = jnp.exp(dt_t * a)[..., None, None]
        state = decay * state + (dt_t[..., None] * x_t)[..., None] \
            * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    t_first = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
    _, y = _scan_time(step, jnp.zeros((b, h, pd, n), F32),
                      (t_first(x), t_first(bm), t_first(cm), t_first(dt)),
                      length)
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * x
    y = _r(q, y.reshape(b, length, inner) * jax.nn.silu(z))
    yg = y.reshape(b, length, g, inner // g)
    yg = yg * lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                        + cfg["norm_eps"])
    y = _r(q, yg.reshape(b, length, inner) * p["gnorm"])
    return _mm(q, y, p["out_proj"])


def rope(x, theta: float):
    """Rotary embedding over the whole head (rotate-half form): ``x`` is
    (b, l, heads, head_dim), position ``t`` turns pair ``(i, i + hd/2)`` by
    ``t * theta^(-2i/hd)``."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(p, u, cfg, q=None):
    s = dims(cfg)
    b, length, _ = u.shape
    qh, kvh, hd = s["qh"], s["kvh"], s["hd"]
    qq = rope(_mm(q, u, p["q_proj"]).reshape(b, length, qh, hd),
              cfg["rope_theta"])
    kk = rope(_mm(q, u, p["k_proj"]).reshape(b, length, kvh, hd),
              cfg["rope_theta"])
    vv = _mm(q, u, p["v_proj"]).reshape(b, length, kvh, hd)
    kk = jnp.repeat(kk, qh // kvh, axis=2)
    vv = jnp.repeat(vv, qh // kvh, axis=2)
    qq, kk, vv = _r(q, qq), _r(q, kk), _r(q, vv)
    scale = 1.0 / math.sqrt(hd)
    pos = jnp.arange(length)

    @jax.checkpoint
    def rows(q_blk, q_pos):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_blk, kk) * scale
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


def route(p, x, cfg):
    """``(scores, indices, denominator)``: sigmoid scores over every
    published expert in float32, the ``topk`` experts by score plus the
    correction bias, and the sum of the selected scores."""
    scores = jax.nn.sigmoid(x.astype(F32) @ p["router"])
    _, idx = lax.top_k(scores + p["router_bias"], dims(cfg)["topk"])
    denom = jnp.take_along_axis(scores, idx, axis=-1).sum(-1)
    return scores, idx, denom


def latent_moe(p, u, cfg, q=None, drop_routed=False):
    """The chip's share of a LatentMoE layer; ``drop_routed`` leaves the
    routed experts out (a fault for the comparison's own tests)."""
    s = dims(cfg)
    x = u.reshape(-1, s["d"])
    scores, idx, denom = route(p, x, cfg)
    if cfg.get("norm_topk_prob", True):
        scores = scores / (denom[:, None] + 1e-20)
    scores = scores * cfg["routed_scaling_factor"]
    lat = _mm(q, x, p["latent_down"])

    # a plain loop over the experts held here (a scan, so that the program
    # text holds the expert once; its body is recomputed in the reverse
    # pass): every token through every held expert, masked to its choices
    @jax.checkpoint
    def one_expert(routed, xs):
        w1, w2, e = xs
        chosen = (idx == s["e_off"] + e).any(-1)
        w = jnp.where(chosen, jnp.take(scores, s["e_off"] + e, axis=1), 0.0)
        hidden = _r(q, relu2(_mm(q, lat, w1)))
        return routed + w[:, None] * _mm(q, hidden, w2), None

    routed = jnp.zeros_like(lat)
    if not drop_routed:
        routed, _ = lax.scan(one_expert, routed,
                             (p["w1"], p["w2"], jnp.arange(s["e_held"])))
    shared = _mm(q, _r(q, relu2(_mm(q, x, p["shared_up"]))),
                 p["shared_down"])
    out = _mm(q, _r(q, routed), p["latent_up"]) + shared
    return out.reshape(u.shape)


_MIXERS = {"M": mamba_mixer, "*": attention, "E": latent_moe}


def _blocks(params, pattern, x, cfg, q, remat, drop_routed):
    for i, kind in enumerate(pattern):
        fn = _MIXERS[kind]
        if kind == "E":
            fn = functools.partial(fn, drop_routed=drop_routed)

        def block(p, x, fn=fn):
            return _r(q, x + fn(p, _r(q, rms_norm(x, p["norm"],
                                                   cfg["norm_eps"])), cfg,
                                q))

        x = (jax.checkpoint(block) if remat else block)(
            params[layer_name(i)], x)
    return x


def forward(params, tokens, cfg, q=None, remat=False, drop_routed=False,
            logits=True):
    """``(logits, mtp_logits or None)``, both (b, l, vocabulary held) in
    float32.  ``mtp_logits[:, t]`` predicts token ``t + 2`` from the trunk's
    state at ``t`` and the embedding of token ``t + 1``.  With
    ``logits=False`` the two normed states that the head would be applied
    to."""
    eps = cfg["norm_eps"]

    def head(y):
        return _mm(q, y, params["lm_head"]) if logits else y

    x = params["embed"][tokens]
    h = _blocks(params, cfg["hybrid_override_pattern"], x, cfg, q, remat,
                drop_routed)
    logits_out = head(_r(q, rms_norm(h, params["final_norm"], eps)))
    if "mtp" not in params:
        return logits_out, None
    m = params["mtp"]
    nxt = params["embed"][jnp.roll(tokens, -1, axis=1)]
    joined = jnp.concatenate([rms_norm(h, m["hnorm"], eps),
                              rms_norm(nxt, m["enorm"], eps)], -1)
    h2 = _blocks(m, cfg["mtp_hybrid_override_pattern"],
                 _mm(q, _r(q, joined), m["proj"]), cfg, q, remat,
                 drop_routed)
    return logits_out, head(_r(q, rms_norm(h2, m["final_norm"], eps)))


def shifted_xent(logits, tokens, shift: int):
    """Mean over the positions that have a target of the cross-entropy of
    ``logits[:, t]`` against ``tokens[:, t + shift]``."""
    length = tokens.shape[1]
    logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
    target = jnp.roll(tokens, -shift, axis=1)
    nll = -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
    valid = (jnp.arange(length) < length - shift)[None, :]
    return jnp.sum(jnp.where(valid, nll, 0.0)) / (
        tokens.shape[0] * (length - shift))


def blocked_xent(hidden, head, tokens, shift: int, q=None):
    """:func:`shifted_xent` of ``hidden @ head`` without the whole
    (b, l, vocabulary) logits in memory: ``LOSS_BLOCK`` positions at a time,
    each block's logits recomputed in the reverse pass."""
    b, length, d = hidden.shape
    nb = length // LOSS_BLOCK
    target = jnp.roll(tokens, -shift, axis=1)
    valid = jnp.broadcast_to(jnp.arange(length) < length - shift, (b, length))

    def blocks(a):
        return jnp.moveaxis(a.reshape((b, nb, LOSS_BLOCK) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one(args):
        h, t, ok = args
        logp = jax.nn.log_softmax(_mm(q, h, head).astype(F32), axis=-1)
        nll = -jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(ok, nll, 0.0))

    sums = lax.map(one, (blocks(hidden), blocks(target), blocks(valid)))
    return jnp.sum(sums) / (b * (length - shift))


def loss_fn(params, tokens, cfg, q=None, remat=False, drop_routed=False):
    """``CE_next + lambda CE_mtp``.  A long sequence goes through
    :func:`blocked_xent`, a short one through :func:`forward`'s logits: the
    same sums either way."""
    length = tokens.shape[1]
    if length <= LOSS_BLOCK or length % LOSS_BLOCK:
        logits, mtp_logits = forward(params, tokens, cfg, q, remat,
                                     drop_routed)
        loss = shifted_xent(logits, tokens, 1)
        if mtp_logits is not None:
            loss = loss + cfg["mtp_loss_weight"] * shifted_xent(
                mtp_logits, tokens, 2)
        return loss
    hidden, mtp_hidden = forward(params, tokens, cfg, q, remat, drop_routed,
                                 logits=False)
    loss = blocked_xent(hidden, params["lm_head"], tokens, 1, q)
    if mtp_hidden is not None:
        loss = loss + cfg["mtp_loss_weight"] * blocked_xent(
            mtp_hidden, params["lm_head"], tokens, 2, q)
    return loss


def train_step(cfg, opt, params, trace, batch, q=None, remat=True, rows=None,
               drop_routed=False):
    """One SGD-momentum step as the program's trainer takes it.  ``rows`` (a
    fault): only the first ``rows`` tokens of the step, over its sequences;
    ``drop_routed`` (a fault): the routed experts left out."""
    tokens = batch["tokens"]
    if rows is not None:
        tokens = tokens[:, :max(3, rows // tokens.shape[0])]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, cfg, q, remat, drop_routed)
    trace = jax.tree.map(lambda g, t: g + opt["momentum"] * t, grads, trace)
    params = jax.tree.map(lambda p, t: p - opt["learning_rate"] * t, params,
                          trace)
    return params, trace, loss


# ---------------------------------------------------------- operation count
def flops_per_sequence(cfg: dict, seq_len: int) -> dict:
    """Model FLOPs of one training step over one sequence, term by term:
    6 x (matmul parameters a token passes through) x tokens for every
    product with a weight (2 forward, 4 backward), the routed experts at the
    tokens routed to *held* experts under uniform routing
    (``topk * held / published`` experts a token a layer), causal attention
    as 3 x (2 products x 2 FLOPs x S^2/2 x head_dim x heads), and the
    chunked scan's products (scores C B^T and scores.x within a chunk under
    the causal half, chunk states and their read-out).  A multiply-add
    counts 2; recomputation, norms, activations, softmax, the router's
    top-k and the gathers are not counted."""
    s = dims(cfg)
    d, v, t = s["d"], s["v"], seq_len
    c = cfg["chunk_size"]
    held_per_token = s["topk"] * s["e_held"] / s["e_all"]
    per_block = {
        "M": {"mamba_proj": 6 * t * (
                  d * (2 * s["inner"] + 2 * s["mg"] * s["mn"] + s["mh"])
                  + s["inner"] * d),
              "mamba_scan": 3 * t * (
                  # within a chunk, causal half: C B^T per group and the
                  # decayed scores times x per head
                  2 * (c / 2) * s["mn"] * s["mg"]
                  + 2 * (c / 2) * s["mp"] * s["mh"]
                  # chunk states (B^T x) and their read-out (C h)
                  + 2 * 2 * s["mn"] * s["mp"] * s["mh"])},
        "*": {"attn_proj": 6 * t * d * s["hd"] * (2 * s["qh"] + 2 * s["kvh"]),
              "attn_scores": 3 * (4 * t * t / 2 * s["hd"] * s["qh"])},
        "E": {"moe_router": 6 * t * d * s["e_all"],
              "moe_latent": 6 * t * 2 * d * s["lat"],
              "moe_shared": 6 * t * 2 * d * s["si"],
              "moe_routed": 6 * t * held_per_token * 2 * s["lat"] * s["ei"]},
    }
    terms: dict = {}

    def add(pattern, prefix=""):
        for kind in pattern:
            for name, f in per_block[kind].items():
                terms[prefix + name] = terms.get(prefix + name, 0.0) + f

    add(cfg["hybrid_override_pattern"])
    terms["lm_head"] = 6 * t * d * v
    if cfg.get("num_nextn_predict_layers", 0):
        add(cfg["mtp_hybrid_override_pattern"], "mtp_")
        terms["mtp_proj"] = 6 * t * 2 * d * d
        terms["mtp_lm_head"] = 6 * t * d * v
    terms["total"] = sum(terms.values())
    return terms
