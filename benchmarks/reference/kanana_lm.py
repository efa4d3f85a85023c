"""Plain reference of a ``kanana_lm`` stage on the training path (the
Kanana-2 family, ``model_type`` ``deepseek_v3``: multi-head latent attention
with a decoupled rotary part, a leading dense layer, then expert layers with
a sigmoid router, a score-correction bias and shared experts): forward,
loss, gradients and the SGD-momentum step in ``jax.numpy`` and float32, with
every matrix product at ``highest`` precision.  It follows the description
below as written and imports nothing of the program (a few helpers come from
the other plain references).

Per layer ``l``, on ``h`` (B, S, d), two blocks ``l<2l>``, ``l<2l+1>``, each
``h <- h + f(RMSNorm(h))``:

* attention (MLA, no q LoRA), on ``u = RMSNorm(h)``, ``H`` heads:
  ``q = u Wq`` split per head into ``q_nope`` (``qk_nope_head_dim``) and
  ``q_pe`` (``qk_rope_head_dim``); ``[c, k_pe] = u Wkva``; ``c =
  RMSNorm(c)`` (``kv_a_layernorm``); ``[k_nope, v] = c Wkvb`` split per
  head; ``q_pe`` and ``k_pe`` turn by the rotary embedding in the published
  form of ``rope_interleave``: the channels are de-interleaved (the even
  ones, then the odd ones) and turned rotate-half, ``rope_theta``, plain
  positions; ``k_pe`` is one head, repeated for every query head; ``q = [q_nope
  ‖ q_pe]``, ``k = [k_nope ‖ k_pe]``; ``o[t, h] = softmax_{s <= t}(q[t, h] .
  k[s, h] / sqrt(qk_head_dim)) v[s, h]``; ``f = o Wo``.  The keys and
  values are expanded from the latent for every head, and the scores are
  computed over the whole causal row in query blocks of :data:`QUERY_BLOCK`,
  so that 8,192 positions fit.
* the first ``first_k_dense_replace`` layers' second block: ``f = (silu(u
  Wgate) * (u Wup)) Wdown``, ``intermediate_size`` wide (``w1 = [Wgate |
  Wup]``).
* every later layer's: ``s = sigmoid(u Wr)`` over every published expert,
  float32; the top ``num_experts_per_tok`` of ``s + b`` (``b`` the
  score-correction bias; ``n_group`` = ``topk_group``, so the group limit
  chooses every group); ``w_e = s_e / sum of the chosen s x
  routed_scaling_factor`` (``norm_topk_prob``; ``s`` without ``b``);
  ``f[t] = sum over the chosen experts e HELD here of w_e (silu(u Wgate_e) *
  (u Wup_e)) Wdown_e``, as a plain loop over the held experts with a mask,
  plus the shared experts for every token, one SwiGLU of
  ``n_shared_experts x moe_intermediate_size``.  The router in float32 in
  every variant.

then a final RMSNorm, the untied head and the next-token cross-entropy.

Departures from the published description, each also under ``assumed`` in
the configuration's file: the score-correction bias is held at nought (its
trained values are not in the catalog, and the token step updates no state
outside the gradient's); the shared experts as one SwiGLU of their summed
width (two SwiGLUs whose outputs add are one SwiGLU of both widths).

The configuration is the benchmark's JSON: ``n_routed_experts`` and
``vocab_size`` are what is *held here*; ``published.n_routed_experts`` is
the router's width.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# what the plain token references compute alike: a product under a
# rounding, RMSNorm, the blocked next-token loss, the spec's leaf test, the
# rotate-half rotary embedding
from reference.keye_lm import rotary, text_positions  # noqa: E402
from reference.nemotron_h import (LOSS_BLOCK, _is_leaf, _mm, _r,  # noqa: E402,F401
                                  blocked_xent, layer_name, rms_norm,
                                  shifted_xent)

F32 = jnp.float32
#: query rows of a block of the reference's attention (memory only, not
#: arithmetic)
QUERY_BLOCK = 256


# ------------------------------------------------------------------ shapes
def dims(cfg: dict) -> dict:
    held = cfg["n_routed_experts"]
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"],
        "di": cfg["intermediate_size"], "h": cfg["num_attention_heads"],
        "r": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
        "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
        "theta": float(cfg["rope_theta"]),
        "e_all": cfg.get("published", {}).get("n_routed_experts", held),
        "e_held": held, "e_off": cfg.get("expert_offset", 0),
        "per_tok": cfg["num_experts_per_tok"],
        "ei": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "scale": float(cfg["routed_scaling_factor"]),
        "eps": cfg["rms_norm_eps"],
    }


def param_spec(cfg: dict) -> dict:
    """``{path: (shape, kind)}`` as a tree: the program's parameter tree,
    leaf for leaf (block ``2l`` is layer ``l``'s attention, ``2l + 1`` its
    dense or expert block)."""
    s = dims(cfg)
    d, h, r = s["d"], s["h"], s["r"]
    qk = s["dn"] + s["dr"]
    spec = {"embed": ((s["v"], d), "embed")}
    for i in range(s["layers"]):
        spec[layer_name(2 * i)] = {
            "norm": ((d,), "scale"),
            "q_proj": ((d, h * qk), "dense"),
            "kv_a_proj": ((d, r + s["dr"]), "dense"),
            "kv_a_norm": ((r,), "scale"),
            "kv_b_proj": ((r, h * (s["dn"] + s["dv"])), "dense"),
            "o_proj": ((h * s["dv"], d), "residual_out")}
        if i < s["dense"]:
            spec[layer_name(2 * i + 1)] = {
                "norm": ((d,), "scale"),
                "w1": ((d, 2 * s["di"]), "dense"),
                "w2": ((s["di"], d), "residual_out")}
        else:
            spec[layer_name(2 * i + 1)] = {
                "norm": ((d,), "scale"),
                "router": ((d, s["e_all"]), "router"),
                "e_score_correction_bias": ((s["e_all"],), "zero"),
                "w1": ((s["e_held"], d, 2 * s["ei"]), "dense"),
                "w2": ((s["e_held"], s["ei"], d), "residual_out"),
                "shared_w1": ((d, 2 * s["shared"]), "dense"),
                "shared_w2": ((s["shared"], d), "residual_out")}
    spec["final_norm"] = ((d,), "scale")
    spec["lm_head"] = ((d, s["v"]), "dense")
    return spec


def make_weights(key, cfg: dict) -> dict:
    """Every leaf but the routers' from ``key``.  Products are LeCun-normal;
    the four that write into the residual stream (``o_proj``, the dense
    layer's and the experts' ``w2``, ``shared_w2``) are scaled by ``(2 x
    published layers)^-1/2``, as ``reference/keye_lm.py::make_weights`` says
    why; the embedding at unit spread, scales near one, the score-correction
    bias at nought (assumed: its trained values are not published with the
    configuration).

    The routers (``router``) are LeCun-normal from a key of the
    configuration's own (``PRNGKey(0)`` folded with the leaf's place) and
    **not from the run's seed**, as ``reference/zaya_lm.py::make_weights``
    draws them: how many rows a layer hands the experts held here is what
    the grouped products work on, and the seed would move it with every
    run.  Every other leaf, and the ids, follow the seed."""
    depth = cfg.get("published", {}).get("num_hidden_layers",
                                         cfg["num_hidden_layers"])
    leaves, treedef = jax.tree.flatten(param_spec(cfg), is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for i, (k, (shape, kind)) in enumerate(zip(keys, leaves)):
        if kind == "router":
            k = jax.random.fold_in(jax.random.PRNGKey(0), i)
        z = jax.random.normal(k, shape, F32)
        if kind in ("dense", "residual_out", "router"):
            z = z / math.sqrt(shape[-2])
            if kind == "residual_out":
                z = z / math.sqrt(2 * depth)
        elif kind == "scale":
            z = 1.0 + 0.1 * z
        elif kind == "zero":
            z = jnp.zeros(shape, F32)
        elif kind != "embed":
            raise ValueError(kind)
        out.append(z)
    return jax.tree.unflatten(treedef, out)


# -------------------------------------------------------------- arithmetic
def deinterleave(x):
    """Channels ``(0, 2, 4, …, 1, 3, 5, …)``: the published form of
    ``rope_interleave`` turns them rotate-half from there."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)


def attention(p, u, cfg, q=None, no_kv_norm=False, whole_head_rope=False):
    """``o Wo`` of an MLA block.  Faults: ``no_kv_norm`` leaves the latent's
    RMSNorm out; ``whole_head_rope`` turns all ``qk_head_dim`` channels of
    q and of k, the repeated ``k_pe`` among them, as attention with the
    rotary over the whole head would, in place of the decoupled part alone."""
    s = dims(cfg)
    b, length, _ = u.shape
    h, r, dn, dr, dv = s["h"], s["r"], s["dn"], s["dr"], s["dv"]
    qq = _mm(q, u, p["q_proj"]).reshape(b, length, h, dn + dr)
    kva = _mm(q, u, p["kv_a_proj"])
    c = kva[..., :r]
    if not no_kv_norm:
        c = rms_norm(c, p["kv_a_norm"], s["eps"])
    kv = _mm(q, _r(q, c), p["kv_b_proj"]).reshape(b, length, h, dn + dv)
    k_pe = jnp.repeat(kva[:, :, None, r:], h, axis=2)
    positions = text_positions(b, length)
    if whole_head_rope:
        kk = rotary(deinterleave(jnp.concatenate([kv[..., :dn], k_pe], -1)),
                    positions, s["theta"])
        qq = rotary(deinterleave(qq), positions, s["theta"])
    else:
        kk = jnp.concatenate(
            [kv[..., :dn], rotary(deinterleave(k_pe), positions, s["theta"])],
            -1)
        qq = jnp.concatenate(
            [qq[..., :dn],
             rotary(deinterleave(qq[..., dn:]), positions, s["theta"])], -1)
    vv = kv[..., dn:]
    qq, kk, vv = _r(q, qq), _r(q, kk), _r(q, vv)
    pos = jnp.arange(length)

    @jax.checkpoint
    def rows(q_blk, q_pos):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_blk, kk) / math.sqrt(dn + dr)
        sc = jnp.where(q_pos[None, None, :, None] >= pos[None, None, None, :],
                       sc, -jnp.inf)
        w = _r(q, jax.nn.softmax(sc, axis=-1))
        return jnp.einsum("bhqk,bkhd->bqhd", w, vv)

    if length > QUERY_BLOCK and length % QUERY_BLOCK == 0:
        nb = length // QUERY_BLOCK
        qb = jnp.moveaxis(qq.reshape(b, nb, QUERY_BLOCK, h, dn + dr), 1, 0)
        out = lax.map(lambda a: rows(*a), (qb, pos.reshape(nb, QUERY_BLOCK)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, length, h * dv)
    else:
        out = rows(qq, pos).reshape(b, length, h * dv)
    return _mm(q, _r(q, out), p["o_proj"])


def swiglu(x, w1, w2, q=None):
    gate, up = jnp.split(_mm(q, x, w1), 2, axis=-1)
    return _mm(q, _r(q, jax.nn.silu(gate) * up), w2)


def route(p, x, cfg):
    """``(scores, indices, denominator)``: sigmoid scores over every
    published expert in float32, the ``num_experts_per_tok`` experts by
    score plus the correction bias, and the sum of the chosen scores."""
    scores = jax.nn.sigmoid(x.astype(F32) @ p["router"])
    _, idx = lax.top_k(scores + p["e_score_correction_bias"],
                       dims(cfg)["per_tok"])
    denom = jnp.take_along_axis(scores, idx, axis=-1).sum(-1)
    return scores, idx, denom


def sigmoid_moe(p, u, cfg, q=None, drop_routed=False, no_shared=False):
    """The chip's share of an expert layer and the shared experts.  Faults:
    ``drop_routed`` leaves the routed experts out, ``no_shared`` the shared
    ones."""
    s = dims(cfg)
    x = u.reshape(-1, s["d"])
    scores, idx, denom = route(p, x, cfg)
    if cfg.get("norm_topk_prob", True):
        scores = scores / (denom[:, None] + 1e-20)
    scores = scores * s["scale"]
    off = s["e_off"]

    @jax.checkpoint
    def one_expert(routed, xs):
        w1, w2, e = xs
        chosen = (idx == off + e).any(-1)
        w = jnp.where(chosen, jnp.take(scores, off + e, axis=1), 0.0)
        return routed + w[:, None] * swiglu(x, w1, w2, q), None

    out = jnp.zeros_like(x)
    if not drop_routed:
        out, _ = lax.scan(one_expert, out,
                          (p["w1"], p["w2"], jnp.arange(s["e_held"])))
    if not no_shared:
        out = out + swiglu(x, p["shared_w1"], p["shared_w2"], q)
    return out.reshape(u.shape)


def forward(params, tokens, cfg, q=None, remat=False, drop_routed=False,
            no_shared=False, no_kv_norm=False, whole_head_rope=False,
            logits=True):
    """The head's logits, or the normed state it is applied to."""
    s = dims(cfg)
    eps = s["eps"]

    def block(fn):
        def run(p, x):
            return _r(q, x + fn(p, _r(q, rms_norm(x, p["norm"], eps))))
        return jax.checkpoint(run) if remat else run

    attn = block(lambda p, u: attention(p, u, cfg, q, no_kv_norm,
                                        whole_head_rope))
    dense = block(lambda p, u: swiglu(u, p["w1"], p["w2"], q))
    moe = block(lambda p, u: sigmoid_moe(p, u, cfg, q, drop_routed,
                                         no_shared))
    x = params["embed"][tokens]
    for i in range(s["layers"]):
        x = attn(params[layer_name(2 * i)], x)
        x = (dense if i < s["dense"] else moe)(params[layer_name(2 * i + 1)],
                                               x)
    h = _r(q, rms_norm(x, params["final_norm"], eps))
    return _mm(q, h, params["lm_head"]) if logits else h


def loss_fn(params, tokens, cfg, q=None, remat=False, **faults):
    """The next-token cross-entropy over the head."""
    length = tokens.shape[1]
    blocked = length > LOSS_BLOCK and length % LOSS_BLOCK == 0
    out = forward(params, tokens, cfg, q, remat, logits=not blocked,
                  **faults)
    return blocked_xent(out, params["lm_head"], tokens, 1, q) if blocked \
        else shifted_xent(out, tokens, 1)


def train_step(cfg, opt, params, trace, batch, q=None, remat=True, rows=None,
               **faults):
    """One SGD-momentum step as the program's trainer takes it.  Faults:
    ``rows`` keeps only the first ``rows`` tokens of the step, over its
    sequences; ``drop_routed``, ``no_shared``, ``no_kv_norm``,
    ``whole_head_rope`` as :func:`forward`'s."""
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
    four projections (``mla_proj``), the dense layers' SwiGLU (``mlp``), the
    router (``router``), the shared experts (``shared_expert``), the routed
    experts at the tokens routed to *held* experts under uniform routing over
    the published experts (``k x held / published`` of a token a layer,
    ``moe_routed``), the head; the attention's seven products over the
    causal half of the pairs, ``S^2 / 2`` (``mla_scores``): forward S and
    P v, reverse S again, dP, dV, dK and dQ, the three that read q or k at
    ``qk_head_dim``, the four that read v or the output at ``v_head_dim``.
    A multiply-add counts 2; recomputation, norms, activations, the rotary
    embedding, the softmax and the gathers are not counted."""
    s = dims(cfg)
    d, t, h = s["d"], seq_len, s["h"]
    qk = s["dn"] + s["dr"]
    moe_layers = s["layers"] - s["dense"]
    proj = d * h * qk + d * (s["r"] + s["dr"]) \
        + s["r"] * h * (s["dn"] + s["dv"]) + h * s["dv"] * d
    terms = {
        "mla_proj": 6.0 * t * proj * s["layers"],
        "mla_scores": 2.0 * (t * t / 2) * h * (4 * qk + 3 * s["dv"])
        * s["layers"],
        "mlp": 6.0 * t * 3 * d * s["di"] * s["dense"],
        "router": 6.0 * t * d * s["e_all"] * moe_layers,
        "shared_expert": 6.0 * t * 3 * d * s["shared"] * moe_layers,
        "moe_routed": 6.0 * t * s["per_tok"] * s["e_held"] / s["e_all"]
        * 3 * d * s["ei"] * moe_layers,
        "lm_head": 6.0 * t * d * s["v"],
    }
    terms["total"] = sum(terms.values())
    return terms
