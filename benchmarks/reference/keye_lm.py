"""Plain reference of a ``keye_lm`` stage on the training path (the language
model of Keye-VL-2.0: learned sparse attention, M-RoPE, a softmax-routed
gated expert layer): forward, loss, gradients and the SGD-momentum step in
``jax.numpy`` and float32, with every matrix product at ``highest``
precision.  It follows the description below as written and imports nothing
of the program (a few helpers come from the other plain reference).  Per layer, on ``h`` (B, S, d), two blocks ``l<2i>``,
``l<2i+1>``:

* attention.  ``x = RMSNorm(h)``; ``q, k, v = x Wq, x Wk, x Wv``; RMSNorm
  over each head of q and k; M-RoPE (rotate-half pairs; of a head's pairs the
  first ``mrope_section[0]`` turn by the temporal position, the next by the
  height's, the last by the width's; text: the three equal).  The indexer, on
  ``stop_gradient(x)``: ``qI = x WqI`` (J heads), ``kI = LayerNorm(x WkI)``
  (one head), ``w = x Ww``, rotary over the indexer's head by the temporal
  position, ``I[t, s] = (J Di)^-1/2 sum_j w[t, j] ReLU(qI[t, j] . kI[s])``.
  ``S_t`` = the ``min(t + 1, topk)`` causal keys of largest ``I[t, .]`` by
  ``jax.lax.top_k`` (equal scores to the lower index).  ``o[t, h] =
  softmax_{s in S_t}(q[t, h] . k[s] / sqrt(hd)) v``; ``h <- h + o Wo``.
  Alignment loss of the layer: ``mean_t KL(p[t, .] || softmax_{s in S_t}
  I[t, s])`` with ``p`` the mean over the heads of the attention's
  probabilities (each head's sum to one over ``S_t``, so the L1
  normalisation of their sum is a division by the head count), a constant.
  Computed over the whole causal row in query blocks, so that 8,192
  positions fit.
* experts.  ``x = RMSNorm(h)``; ``g = softmax(x Wr)`` over every published
  expert; top-k; weights ``g_e / sum_topk g``; the routed sum as a plain loop
  over the experts HELD here with a mask: ``y[t] = sum_{e in topk(t), held}
  w_e (silu(x Wgate_e) * (x Wup_e)) Wdown_e`` (``w1 = [Wgate | Wup]``);
  ``h <- h + y``.  What the absent experts would add is left out, here and
  in the program alike.

then a final RMSNorm, the untied head and the next-token cross-entropy; the
objective is that plus ``index_align_loss_weight`` x the mean over the layers
of the alignment losses.

Departures from the published description (each also under ``assumed`` in
the configuration's file): the per-head RMSNorm of q and k (the Qwen3
lineage's; the config has no key for it); the indexer's LayerNorm on kI, its
rotary by the temporal position, ReLU and the ``(J Di)^-1/2`` scale (the
public DeepSeek-Sparse-Attention code that the catalog's ``described_as``
names); indexer products and selection in float32 (the public implementation
runs them in FP8); the alignment loss in the sparse stage's form of
DeepSeek-V3.2's recipe, weight 1; no load-balancing loss (the config has no
coefficient); text positions.  ``q_chunk_size`` / ``kv_chunk_size`` tile this
computation and change no result: the query block here is 256 (memory).

The configuration is the benchmark's JSON: ``num_experts`` and
``vocab_size`` are what is *held here*; ``published.num_experts`` is the
router's width.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# what the two plain token references compute alike: a product under a
# rounding, RMSNorm, the blocked next-token loss, the spec's leaf test
from reference.nemotron_h import (LOSS_BLOCK, _is_leaf, _mm, _r,  # noqa: E402,F401
                                  blocked_xent, layer_name, rms_norm,
                                  shifted_xent)

F32 = jnp.float32
#: query rows of a block of the reference's attention (memory only, not
#: arithmetic)
QUERY_BLOCK = 256


# ------------------------------------------------------------------ shapes
def dims(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "qh": cfg["num_attention_heads"], "kvh": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"],
        "section": tuple(cfg["rope_scaling"]["mrope_section"]),
        "ih": sa["indexer_num_heads"], "ihd": sa["indexer_head_dim"],
        "topk": sa["topk"],
        "e_all": cfg.get("published", {}).get("num_experts",
                                              cfg["num_experts"]),
        "e_held": cfg["num_experts"], "e_off": cfg.get("expert_offset", 0),
        "per_tok": cfg["num_experts_per_tok"],
        "ei": cfg["moe_intermediate_size"],
        "align": float(cfg.get("index_align_loss_weight", 1.0)),
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
            "q_norm": ((hd,), "scale"), "k_norm": ((hd,), "scale"),
            "index_q": ((d, s["ih"] * s["ihd"]), "dense"),
            "index_k": ((d, s["ihd"]), "dense"),
            "index_w": ((d, s["ih"]), "dense"),
            "index_k_scale": ((s["ihd"],), "scale"),
            "index_k_bias": ((s["ihd"],), "bias")}
        spec[layer_name(2 * i + 1)] = {
            "norm": ((d,), "scale"),
            "router": ((d, s["e_all"]), "dense"),
            "w1": ((s["e_held"], d, 2 * s["ei"]), "dense"),
            "w2": ((s["e_held"], s["ei"], d), "residual_out")}
    spec["final_norm"] = ((d,), "scale")
    spec["lm_head"] = ((d, v), "dense")
    return spec


def make_weights(key, cfg: dict) -> dict:
    """Every leaf from one key.  Products are LeCun-normal, which on normed
    inputs of unit scale gives router logits, attention scores and index
    scores of about unit spread: the softmax router's top experts carry most
    of a token's weight without one taking all, and a query's index scores
    are far enough apart that float32 tells the 2,048th from the 2,049th;
    the scales start near one, the biases near nought.  The two products
    that write into the residual stream (``o_proj``, ``w2``) are scaled by
    ``(2 x published layers)^-1/2``, as GPT-2 and Megatron initialise them:
    at LeCun scale a random attention block hands the mean of its values,
    the same vector for every query, on to the next layer with a gain of
    about one, that vector grows by a factor of root two a layer, and by the
    eighth layer it decides which experts are popular whatever the tokens
    are — the fullest held expert read 4.7 to 7.4 times the mean on the chip
    and the held experts three to four times their share of the rows (my
    chip runs, PR 35); a trained model's attention is not uniform and its
    router is balanced."""
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
        elif kind == "bias":
            z = 0.01 * jax.random.normal(k, shape, F32)
        elif kind == "scale":
            z = 1.0 + 0.1 * jax.random.normal(k, shape, F32)
        else:
            raise ValueError(kind)
        out.append(z)
    return jax.tree.unflatten(treedef, out)


# -------------------------------------------------------------- arithmetic
def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def rotary(x, positions, theta: float, section=None):
    """Rotate-half rotary embedding.  ``x``: (b, l, heads, hd);
    ``positions``: (3, b, l), the temporal, height and width position of
    every token.  Pair ``(i, i + hd/2)`` turns by ``p * theta^(-2i/hd)`` with
    ``p`` the row that ``section`` gives pair ``i`` (M-RoPE: the first
    ``section[0]`` pairs the temporal row, and so on); without ``section``
    every pair takes the temporal row."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    row = np.repeat(np.arange(3), section) if section is not None \
        else np.zeros(hd // 2, int)
    ang = jnp.moveaxis(positions.astype(F32)[row], 0, -1) * inv   # (b,l,hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(p, u, cfg, positions, q=None, dense=False):
    """``(o Wo, the layer's alignment loss)``.  ``dense`` (a fault): the
    selection left out, every causal key attended."""
    s = dims(cfg)
    b, length, _ = u.shape
    qh, kvh, hd, ih, ihd = s["qh"], s["kvh"], s["hd"], s["ih"], s["ihd"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    qq = _mm(q, u, p["q_proj"]).reshape(b, length, qh, hd)
    kk = _mm(q, u, p["k_proj"]).reshape(b, length, kvh, hd)
    vv = _mm(q, u, p["v_proj"]).reshape(b, length, kvh, hd)
    qq = rotary(rms_norm(qq, p["q_norm"], eps), positions, theta,
                s["section"])
    kk = rotary(rms_norm(kk, p["k_norm"], eps), positions, theta,
                s["section"])
    kk = jnp.repeat(kk, qh // kvh, axis=2)
    vv = jnp.repeat(vv, qh // kvh, axis=2)
    qq, kk, vv = _r(q, qq), _r(q, kk), _r(q, vv)
    # the indexer: float32 whatever ``q`` is, on a constant copy of the input
    ui = lax.stop_gradient(u)
    qi = rotary((ui @ p["index_q"]).reshape(b, length, ih, ihd), positions,
                theta)
    ki = layer_norm(ui @ p["index_k"], p["index_k_scale"], p["index_k_bias"],
                    eps)
    ki = rotary(ki[:, :, None, :], positions, theta)[:, :, 0]
    wi = ui @ p["index_w"]
    pos = jnp.arange(length)
    topk = length if dense else min(s["topk"], length)

    @jax.checkpoint
    def rows(q_blk, qi_blk, wi_blk, q_pos):
        causal = q_pos[:, None] >= pos[None, :]                  # (n, l)
        index = jnp.einsum("bqjd,bkd->bjqk", qi_blk, ki)
        index = (jnp.moveaxis(wi_blk, -1, 1)[..., None]
                 * jnp.maximum(index, 0.0)).sum(1) / math.sqrt(ih * ihd)
        if topk < length:
            _, idx = lax.top_k(jnp.where(causal, index, -jnp.inf), topk)
            n = q_pos.shape[0]
            keep = jnp.zeros((b * n, length), bool).at[
                jnp.arange(b * n)[:, None], idx.reshape(b * n, topk)
            ].set(True).reshape(b, n, length) & causal
        else:
            keep = jnp.broadcast_to(causal, index.shape)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_blk, kk) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(keep[:, None], sc, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", _r(q, w), vv)
        target = lax.stop_gradient(w.sum(1) / qh)                # (b, n, l)
        logq = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), axis=-1)
        live = keep & (target > 0)
        kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                       - jnp.where(live, logq, 0.0)), 0.0)
        return out, kl.sum()

    if length > QUERY_BLOCK and length % QUERY_BLOCK == 0:
        nb = length // QUERY_BLOCK

        def blocks(a):
            return jnp.moveaxis(
                a.reshape((b, nb, QUERY_BLOCK) + a.shape[2:]), 1, 0)

        out, kl = lax.map(lambda a: rows(*a),
                          (blocks(qq), blocks(qi), blocks(wi),
                           pos.reshape(nb, QUERY_BLOCK)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, length, qh * hd)
    else:
        out, kl = rows(qq, qi, wi, pos)
        out = out.reshape(b, length, qh * hd)
    return _mm(q, _r(q, out), p["o_proj"]), kl.sum() / (b * length)


def route(p, x, cfg):
    """``(gates, indices, sum of the chosen gates)``: the softmax over every
    published expert in float32 and its ``num_experts_per_tok`` largest."""
    gates = jax.nn.softmax(x.astype(F32) @ p["router"], axis=-1)
    top, idx = lax.top_k(gates, dims(cfg)["per_tok"])
    return gates, idx, top.sum(-1)


def gated_moe(p, u, cfg, q=None, drop_routed=False):
    """The chip's share of the expert layer; ``drop_routed`` leaves the
    routed experts out (a fault)."""
    s = dims(cfg)
    x = u.reshape(-1, s["d"])
    gates, idx, denom = route(p, x, cfg)
    if cfg.get("norm_topk_prob", True):
        gates = gates / denom[:, None]
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


def text_positions(batch: int, length: int):
    return jnp.broadcast_to(jnp.arange(length), (3, batch, length))


def forward(params, tokens, cfg, positions=None, q=None, remat=False,
            drop_routed=False, dense_attention=False, logits=True):
    """``(logits or the normed state the head is applied to, the mean over
    the layers of the alignment losses)``."""
    s = dims(cfg)
    eps = cfg["rms_norm_eps"]
    if positions is None:
        positions = text_positions(*tokens.shape)

    def attn_block(p, x):
        y, kl = attention(p, _r(q, rms_norm(x, p["norm"], eps)), cfg,
                          positions, q, dense_attention)
        return _r(q, x + y), kl

    def moe_block(p, x):
        return _r(q, x + gated_moe(p, _r(q, rms_norm(x, p["norm"], eps)),
                                   cfg, q, drop_routed))

    if remat:
        attn_block, moe_block = map(jax.checkpoint, (attn_block, moe_block))
    x = params["embed"][tokens]
    align = 0.0
    for i in range(s["layers"]):
        x, kl = attn_block(params[layer_name(2 * i)], x)
        align = align + kl / s["layers"]
        x = moe_block(params[layer_name(2 * i + 1)], x)
    h = _r(q, rms_norm(x, params["final_norm"], eps))
    return (_mm(q, h, params["lm_head"]) if logits else h), align


def loss_fn(params, tokens, cfg, q=None, remat=False, drop_routed=False,
            dense_attention=False, drop_align=False, positions=None):
    """``CE_next + index_align_loss_weight * mean over layers of the
    alignment losses``.  ``drop_align`` (a fault): the second term left
    out."""
    length = tokens.shape[1]
    blocked = length > LOSS_BLOCK and length % LOSS_BLOCK == 0
    out, align = forward(params, tokens, cfg, positions, q, remat,
                         drop_routed, dense_attention, logits=not blocked)
    ce = blocked_xent(out, params["lm_head"], tokens, 1, q) if blocked \
        else shifted_xent(out, tokens, 1)
    return ce if drop_align else ce + dims(cfg)["align"] * align


def train_step(cfg, opt, params, trace, batch, q=None, remat=True, rows=None,
               drop_routed=False, dense_attention=False, drop_align=False):
    """One SGD-momentum step as the program's trainer takes it.  Faults:
    ``rows`` keeps only the first ``rows`` tokens of the step, over its
    sequences; ``drop_routed`` leaves the routed experts out;
    ``dense_attention`` the selection; ``drop_align`` the alignment loss."""
    tokens = batch["tokens"]
    if rows is not None:
        tokens = tokens[:, :max(3, rows // tokens.shape[0])]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, cfg, q, remat, drop_routed, dense_attention,
            drop_align)
    trace = jax.tree.map(lambda g, t: g + opt["momentum"] * t, grads, trace)
    params = jax.tree.map(lambda p, t: p - opt["learning_rate"] * t, params,
                          trace)
    return params, trace, loss


# ---------------------------------------------------------- operation count
def pairs(seq_len: int, topk: int) -> tuple[int, int]:
    """``(selected, causal)`` token pairs of one sequence: ``sum_t min(t + 1,
    topk)`` and ``S (S + 1) / 2``."""
    k = min(topk, seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k, seq_len * (seq_len + 1) // 2


def flops_per_sequence(cfg: dict, seq_len: int) -> dict:
    """Model FLOPs of one training step over one sequence, term by term:
    6 x (matmul parameters a token passes through) x tokens for every
    product with a weight (2 forward, 4 backward), the routed experts at the
    tokens routed to *held* experts under uniform routing (``per_tok * held
    / published`` experts a token a layer); the main attention's seven
    products (forward S and P v; reverse S again, dP, dV, dK, dQ) over the
    **selected** pairs; the indexer's three (forward q k^T; reverse dq, dk)
    over all causal pairs.  A multiply-add counts 2; recomputation, the
    alignment target's second pass over the scores, norms, activations,
    softmax, the top-k and the gathers are not counted."""
    s = dims(cfg)
    d, v, t, hd = s["d"], s["v"], seq_len, s["hd"]
    selected, causal = pairs(t, s["topk"])
    held_per_token = s["per_tok"] * s["e_held"] / s["e_all"]
    layer = {
        "attn_proj": 6 * t * d * hd * (2 * s["qh"] + 2 * s["kvh"]),
        "attn_scores": 7 * 2 * selected * hd * s["qh"],
        "index_proj": 6 * t * d * (s["ih"] * s["ihd"] + s["ihd"] + s["ih"]),
        "index_scores": 3 * 2 * causal * s["ihd"] * s["ih"],
        "moe_router": 6 * t * d * s["e_all"],
        "moe_routed": 6 * t * held_per_token * 3 * d * s["ei"],
    }
    terms = {k: float(f * s["layers"]) for k, f in layer.items()}
    terms["lm_head"] = float(6 * t * d * v)
    terms["total"] = sum(terms.values())
    return terms
