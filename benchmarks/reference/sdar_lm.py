"""Plain reference of an ``sdar_lm`` stage on the training path (the SDAR
family, ``model_type`` ``sdar_moe``: a token model trained by a
block-diffusion objective): the noise, forward, loss, gradients and the
SGD-momentum step in ``jax.numpy`` and float32, with every matrix product at
``highest`` precision.  It follows the description below as written and
imports nothing of the program (a few helpers come from the other plain
references).

With ``L`` the batch's sequence length, ``b`` the block length
(``block_length``), ``V`` the vocabulary rows held, ``MASK = V - 1``:

* noise (data: :func:`make_batch` makes it with the ids, from the run's
  seed).  Per block ``j < L/b``: ``t_j = eps + (1 - eps) u_j``, ``u_j ~
  U[0, 1)``; per token ``m_i = [u'_i < t_(i // b)]``; ``xt_i = MASK if m_i
  else x0_i``; ``w_i = m_i / t_(i // b)``.  The masked set is ``m`` (``w >
  0``), never ``xt == MASK``.
* input: ids ``[x0 || xt]`` (2L) through one embedding table; positions
  ``[0..L-1 || 0..L-1]``.
* mask: query ``t``, key ``s`` in ``[0, 2L)``, ``c(i) = i < L``, ``B(i) =
  (i mod L) // b``; allowed iff ``c(s) & c(t) & B(s) <= B(t)`` or ``c(s) &
  ~c(t) & B(s) < B(t)`` or ``~c(s) & ~c(t) & B(s) = B(t)``.  A dense boolean,
  built for one block of queries at a time so that 8,192 positions fit.
* per layer, on ``h`` (B, 2L, d), two blocks ``l<2i>``, ``l<2i+1>``:
  attention — ``x = RMSNorm(h)``; ``q, k, v = x Wq, x Wk, x Wv`` (no bias);
  RMSNorm over each head of q and k; rotary over the whole head
  (rotate-half, ``rope_theta``, no scaling) by the repeated positions;
  ``o[t, h] = softmax_{s allowed}(q[t, h] . k[s] / sqrt(hd)) v``; ``h <- h +
  o Wo``.  Experts — ``x = RMSNorm(h)``; ``g = softmax(x Wr)`` over every
  published expert; top-k; weights ``g_e / sum_topk g``; the routed sum as a
  plain loop over the experts HELD here with a mask: ``y[t] = sum_{e in
  topk(t), held} w_e (silu(x Wgate_e) * (x Wup_e)) Wdown_e`` (``w1 = [Wgate
  | Wup]``); ``h <- h + y``; no shared expert.
* head and loss: ``logits = Head(RMSNorm(h[L:]))``, the noised half only;
  ``loss = (1 / (B L)) sum_i w_i (-log softmax(logits_i)[x0_i])``, no shift.

Departures from the published description, each also under ``assumed`` in
the configuration's file (the catalog's ``not_given``: block length, noise
schedule): block length 4; the linear schedule with the weight ``1/t``, one
``t`` a block, ``eps = 1e-3``; the clean || noised layout and its three-part
mask (the block-diffusion recipe of BD3-LMs, arXiv:2503.09573, which the SDAR
report, arXiv:2510.06303, adapts an autoregressive checkpoint to); no shift;
the per-head RMSNorm of q and k (the Qwen3 lineage's; the config has no key
for it); ``MASK`` = the last held row; no balancing loss.

The configuration is the benchmark's JSON: ``num_experts`` and
``vocab_size`` are what is *held here*; ``published.num_experts`` is the
router's width.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# what the plain token references compute alike: a product under a rounding,
# RMSNorm, the spec's leaf test, the rotate-half rotary embedding
from reference.keye_lm import rotary  # noqa: E402
from reference.nemotron_h import (LOSS_BLOCK, _is_leaf, _mm, _r,  # noqa: E402,F401
                                  layer_name, rms_norm)

F32 = jnp.float32
#: query rows of a block of the reference's attention (memory only, not
#: arithmetic)
QUERY_BLOCK = 256
NOISE_EPS = 1e-3


# ------------------------------------------------------------------ shapes
def dims(cfg: dict) -> dict:
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "qh": cfg["num_attention_heads"], "kvh": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "block": cfg["block_length"],
        "e_all": cfg.get("published", {}).get("num_experts",
                                              cfg["num_experts"]),
        "e_held": cfg["num_experts"], "e_off": cfg.get("expert_offset", 0),
        "per_tok": cfg["num_experts_per_tok"],
        "ei": cfg["moe_intermediate_size"],
    }


def param_spec(cfg: dict) -> dict:
    """``{path: (shape, kind)}`` as a tree: the program's parameter tree,
    leaf for leaf (block ``2i`` is layer ``i``'s attention, ``2i + 1`` its
    expert layer)."""
    s = dims(cfg)
    d, v, hd = s["d"], s["v"], s["hd"]
    spec = {"embed": ((v, d), "embed")}
    for i in range(s["layers"]):
        spec[layer_name(2 * i)] = {
            "norm": ((d,), "scale"),
            "q_proj": ((d, s["qh"] * hd), "dense"),
            "k_proj": ((d, s["kvh"] * hd), "dense"),
            "v_proj": ((d, s["kvh"] * hd), "dense"),
            "o_proj": ((s["qh"] * hd, d), "residual_out"),
            "q_norm": ((hd,), "scale"), "k_norm": ((hd,), "scale")}
        spec[layer_name(2 * i + 1)] = {
            "norm": ((d,), "scale"),
            "router": ((d, s["e_all"]), "dense"),
            "w1": ((s["e_held"], d, 2 * s["ei"]), "dense"),
            "w2": ((s["e_held"], s["ei"], d), "residual_out")}
    spec["final_norm"] = ((d,), "scale")
    spec["lm_head"] = ((d, v), "dense")
    return spec


def mask_experts(cfg: dict, layer: int):
    """The experts that the mask token prefers in layer ``layer``: as many
    as a token is routed to, drawn without replacement from every published
    expert by a key of the configuration's own (``PRNGKey(0)`` folded with
    the layer) and **not of the run's seed**: which of them are held here
    (1, 0, 2, 2, 0, 2, 0 of eight in the cell's seven layers: 7, what eight
    experts a layer over eight ranks expect) decides how many rows the
    expert layers work on, and with the seed's own draw the rate moved with
    it, by 1.2% between seeds holding 4 and 11 (PERF.md section 6, PR 37)."""
    s = dims(cfg)
    return jax.random.choice(
        jax.random.fold_in(jax.random.PRNGKey(0), layer), s["e_all"],
        (s["per_tok"],), replace=False)


def make_weights(key, cfg: dict, sharpen_mask_row: bool = True) -> dict:
    """Every leaf from one key: LeCun-normal products, scales near one, and
    the two products that write into the residual stream (``o_proj``,
    ``w2``) scaled by ``(2 x published layers)^-1/2`` as GPT-2 and Megatron
    initialise them — ``reference/keye_lm.py::make_weights`` says what the
    router does without that (PERF.md section 6, PR 35).

    The **mask token's row** of the embedding is drawn like the others and
    then sharpened: replaced by the sum, over the layers, of the seed's own
    router columns of :func:`mask_experts`, at the drawn row's norm.  A
    quarter of a step's positions hold that one token, and a random stack
    hands each of them nearly the same context (the mean of thousands of
    values), so the router sees 2,048 near-copies of one state: drawn, that
    state's 8th and 9th logits lie 0.012-0.02 apart in three or four of the
    seven layers, the copies spread over 0.01 and bfloat16 moves a logit by
    0.003-0.006, so 60 to 830 of the copies take another expert in the
    program than in the reference, and the reference in bfloat16 than in
    float32 (``tools/mask_routing_sdar.py --drawn`` counts them; PERF.md
    section 2b).  Sharpened, the logits on the preferred experts are 6
    against the others' unit spread (what the sum of 56 unit columns at a
    row's norm gives: no strength is chosen), the margin is 1.7-3.8, and no
    copy differs."""
    depth = cfg.get("published", {}).get("num_hidden_layers",
                                         cfg["num_hidden_layers"])
    leaves, treedef = jax.tree.flatten(param_spec(cfg), is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, kind) in zip(keys, leaves):
        if kind in ("dense", "residual_out"):
            z = jax.random.normal(k, shape, F32) / math.sqrt(shape[-2])
            if kind == "residual_out":
                z = z / math.sqrt(2 * depth)
        elif kind == "embed":
            z = jax.random.normal(k, shape, F32)
        elif kind == "scale":
            z = 1.0 + 0.1 * jax.random.normal(k, shape, F32)
        else:
            raise ValueError(kind)
        out.append(z)
    params = jax.tree.unflatten(treedef, out)
    if sharpen_mask_row:
        row = sum(jnp.take(params[layer_name(2 * i + 1)]["router"],
                           mask_experts(cfg, i), axis=1).sum(axis=1)
                  for i in range(cfg["num_hidden_layers"]))
        drawn = params["embed"][-1]
        params["embed"] = params["embed"].at[-1].set(
            row * (jnp.linalg.norm(drawn) / jnp.linalg.norm(row)))
    return params


def make_batch(key, cfg: dict, sequences: int, seq_len: int) -> dict:
    """The step's batch from one key: ``tokens`` uniform over the held rows
    but the last (which stands for the mask), and one draw of the noise over
    them — ``noised`` and ``loss_weight`` — by the recipe at the top."""
    v, block = cfg["vocab_size"], cfg["block_length"]
    k_ids, k_noise = jax.random.split(key)
    tokens = jax.random.randint(k_ids, (sequences, seq_len), 0, v - 1,
                                dtype="int32")
    k_level, k_token = jax.random.split(k_noise)
    level = NOISE_EPS + (1.0 - NOISE_EPS) * jax.random.uniform(
        k_level, (sequences, seq_len // block), F32)
    level = jnp.repeat(level, block, axis=1)
    masked = jax.random.uniform(k_token, (sequences, seq_len), F32) < level
    return {"tokens": tokens,
            "noised": jnp.where(masked, jnp.int32(v - 1), tokens),
            "loss_weight": jnp.where(masked, 1.0 / level, 0.0)}


# -------------------------------------------------------------- arithmetic
def allowed(t, s, length: int, block: int):
    """The mask: query positions ``t`` (n, 1) against key positions ``s``
    (1, m) of the doubled sequence."""
    ct, cs = t < length, s < length
    bt, bs = (t % length) // block, (s % length) // block
    return (cs & ct & (bs <= bt)) | (cs & ~ct & (bs < bt)) \
        | (~cs & ~ct & (bs == bt))


def attention(p, u, cfg, q=None, causal_mask=False):
    """``o Wo`` over the doubled sequence.  ``causal_mask`` (a fault): the
    plain triangle over the 2L positions in the rule's place."""
    s = dims(cfg)
    b, doubled, _ = u.shape
    length = doubled // 2
    qh, kvh, hd = s["qh"], s["kvh"], s["hd"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    positions = jnp.broadcast_to(jnp.tile(jnp.arange(length), 2),
                                 (1, b, doubled))
    qq = _mm(q, u, p["q_proj"]).reshape(b, doubled, qh, hd)
    kk = _mm(q, u, p["k_proj"]).reshape(b, doubled, kvh, hd)
    vv = _mm(q, u, p["v_proj"]).reshape(b, doubled, kvh, hd)
    qq = rotary(rms_norm(qq, p["q_norm"], eps), positions, theta)
    kk = rotary(rms_norm(kk, p["k_norm"], eps), positions, theta)
    kk = jnp.repeat(kk, qh // kvh, axis=2)
    vv = jnp.repeat(vv, qh // kvh, axis=2)
    qq, kk, vv = _r(q, qq), _r(q, kk), _r(q, vv)
    pos = jnp.arange(doubled)

    @jax.checkpoint
    def rows(q_blk, q_pos):
        if causal_mask:
            seen = q_pos[:, None] >= pos[None, :]
        else:
            seen = allowed(q_pos[:, None], pos[None, :], length, s["block"])
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_blk, kk) / math.sqrt(hd)
        w = _r(q, jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1))
        return jnp.einsum("bhqk,bkhd->bqhd", w, vv)

    if doubled > QUERY_BLOCK and doubled % QUERY_BLOCK == 0:
        nb = doubled // QUERY_BLOCK
        qb = jnp.moveaxis(qq.reshape(b, nb, QUERY_BLOCK, qh, hd), 1, 0)
        out = lax.map(lambda a: rows(*a), (qb, pos.reshape(nb, QUERY_BLOCK)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, doubled, qh * hd)
    else:
        out = rows(qq, pos).reshape(b, doubled, qh * hd)
    return _mm(q, _r(q, out), p["o_proj"])


def gated_moe(p, u, cfg, q=None, drop_routed=False):
    """The chip's share of the expert layer; ``drop_routed`` leaves the
    routed experts out (a fault)."""
    s = dims(cfg)
    x = u.reshape(-1, s["d"])
    gates = jax.nn.softmax(x.astype(F32) @ p["router"], axis=-1)
    top, idx = lax.top_k(gates, s["per_tok"])
    if cfg.get("norm_topk_prob", True):
        gates = gates / top.sum(-1)[:, None]
    off = s["e_off"]

    @jax.checkpoint
    def one_expert(routed, xs):
        w1, w2, e = xs
        chosen = (idx == off + e).any(-1)
        w = jnp.where(chosen, jnp.take(gates, off + e, axis=1), 0.0)
        gate, up = jnp.split(_mm(q, x, w1), 2, axis=-1)
        hidden = _r(q, jax.nn.silu(gate) * up)
        return routed + w[:, None] * _mm(q, hidden, w2), None

    routed = jnp.zeros_like(x)
    if not drop_routed:
        routed, _ = lax.scan(one_expert, routed,
                             (p["w1"], p["w2"], jnp.arange(s["e_held"])))
    return routed.reshape(u.shape)


def forward(params, tokens, noised, cfg, q=None, remat=False,
            drop_routed=False, causal_mask=False):
    """The normed states the head is applied to: (B, L, d), the noised
    half's."""
    s = dims(cfg)
    eps = cfg["rms_norm_eps"]
    length = tokens.shape[1]

    def attn_block(p, x):
        return _r(q, x + attention(p, _r(q, rms_norm(x, p["norm"], eps)),
                                   cfg, q, causal_mask))

    def moe_block(p, x):
        return _r(q, x + gated_moe(p, _r(q, rms_norm(x, p["norm"], eps)),
                                   cfg, q, drop_routed))

    if remat:
        attn_block, moe_block = map(jax.checkpoint, (attn_block, moe_block))
    x = params["embed"][jnp.concatenate([tokens, noised], axis=1)]
    for i in range(s["layers"]):
        x = attn_block(params[layer_name(2 * i)], x)
        x = moe_block(params[layer_name(2 * i + 1)], x)
    return _r(q, rms_norm(x[:, length:], params["final_norm"], eps))


def weighted_xent(hidden, head, tokens, weights, q=None):
    """``(1 / (B L)) sum_i w_i (-log softmax(hidden_i @ head)[tokens_i])``;
    a long sequence ``LOSS_BLOCK`` positions at a time, each block's logits
    recomputed in the reverse pass: the same sum either way."""
    b, length, _ = hidden.shape

    def one(args):
        h, t, w = args
        logp = jax.nn.log_softmax(_mm(q, h, head).astype(F32), axis=-1)
        nll = -jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0]
        return jnp.sum(w * nll)

    if length <= LOSS_BLOCK or length % LOSS_BLOCK:
        return one((hidden, tokens, weights)) / (b * length)
    nb = length // LOSS_BLOCK

    def blocks(a):
        return jnp.moveaxis(a.reshape((b, nb, LOSS_BLOCK) + a.shape[2:]), 1, 0)

    sums = lax.map(jax.checkpoint(one),
                   (blocks(hidden), blocks(tokens), blocks(weights)))
    return jnp.sum(sums) / (b * length)


def loss_fn(params, batch, cfg, q=None, remat=False, drop_routed=False,
            causal_mask=False, unweighted_loss=False):
    """The masked-diffusion loss.  ``unweighted_loss`` (a fault): ``w = m``,
    the inverse of the noise level left out."""
    weights = batch["loss_weight"]
    if unweighted_loss:
        weights = (weights > 0).astype(F32)
    hidden = forward(params, batch["tokens"], batch["noised"], cfg, q, remat,
                     drop_routed, causal_mask)
    return weighted_xent(hidden, params["lm_head"], batch["tokens"], weights,
                         q)


def train_step(cfg, opt, params, trace, batch, q=None, remat=True, rows=None,
               drop_routed=False, causal_mask=False, unweighted_loss=False):
    """One SGD-momentum step as the program's trainer takes it.  Faults:
    ``rows`` keeps only the first ``rows`` tokens of the step, over its
    sequences (whole blocks of them); ``drop_routed`` leaves the routed
    experts out; ``causal_mask`` puts the plain triangle in the rule's
    place; ``unweighted_loss`` leaves ``1/t`` out of the loss."""
    if rows is not None:
        block = cfg["block_length"]
        keep = max(block, rows // batch["tokens"].shape[0] // block * block)
        batch = {k: v[:, :keep] for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, batch, cfg, q, remat, drop_routed, causal_mask,
            unweighted_loss)
    trace = jax.tree.map(lambda g, t: g + opt["momentum"] * t, grads, trace)
    params = jax.tree.map(lambda p, t: p - opt["learning_rate"] * t, params,
                          trace)
    return params, trace, loss


# ---------------------------------------------------------- operation count
def allowed_pairs(seq_len: int, block: int) -> int:
    """Pairs a head attends over the doubled sequence: the clean copy's
    block-causal ``L (L + b) / 2``, a noised block's clean past ``L (L - b)
    / 2``, and its own block ``L b``: ``L^2 + L b`` of ``4 L^2``."""
    return seq_len * seq_len + seq_len * block


def flops_per_sequence(cfg: dict, seq_len: int) -> dict:
    """Model FLOPs of one training step over one sequence of ``seq_len``
    tokens, term by term: 6 x (matmul parameters a position passes through)
    x positions for every product with a weight (2 forward, 4 backward) —
    the layers over the **2 x seq_len** positions of the doubled sequence,
    the head over the ``seq_len`` of the noised copy; the routed experts at
    the positions routed to *held* experts under uniform routing (``per_tok
    * held / published`` experts a position a layer); the attention's seven
    products (forward S and P v; reverse S again, dP, dV, dK, dQ) over the
    **allowed** pairs.  A multiply-add counts 2; recomputation, norms,
    activations, softmax, the router's top-k and the gathers are not
    counted."""
    s = dims(cfg)
    d, v, hd = s["d"], s["v"], s["hd"]
    positions = 2 * seq_len
    held_per_position = s["per_tok"] * s["e_held"] / s["e_all"]
    layer = {
        "attn_proj": 6 * positions * d * hd * (2 * s["qh"] + 2 * s["kvh"]),
        "attn_scores": 7 * 2 * allowed_pairs(seq_len, s["block"]) * hd
        * s["qh"],
        "moe_router": 6 * positions * d * s["e_all"],
        "moe_routed": 6 * positions * held_per_position * 3 * d * s["ei"],
    }
    terms = {k: float(f * s["layers"]) for k, f in layer.items()}
    terms["lm_head"] = float(6 * seq_len * d * v)
    terms["total"] = sum(terms.values())
    return terms
