"""``keye_lm``: a token model whose attention learns which keys to read, on
the training path (the language model of the Kwai Keye-VL-2.0 family,
``model_type`` ``KeyeVL2``: 48 identical layers in the published 30B-A3B).

Every layer is two blocks, each ``x <- x + f(RMSNorm(x))``:

* :class:`SparseAttention` — grouped-query attention with RMSNorm over each
  head of q and k and **M-RoPE** (the rotary pairs split into three sections
  that turn by the temporal, the height and the width position), in which a
  query attends only to a **selected key set**: a small *indexer* (its own
  query heads over one key head, ``I[t, s] = Σ_j w[t, j] · ReLU(qI[t, j] ·
  kI[s])``, float32) scores every causal pair, and query ``t`` keeps the
  ``min(t + 1, topk)`` keys of largest score, exactly, equal scores to the
  lower index.  No gradient passes through the selection; the indexer learns
  from an **alignment loss**, the KL divergence from the main attention's
  head-averaged probabilities over the set to the softmax of its own scores
  there, sown into the ``losses`` collection.  The indexer reads
  ``stop_gradient`` of the block's normed input and the target is a
  constant, so the indexer's parameters get their whole gradient from that
  loss and no other parameter gets any.
* :class:`GatedMoE` — a softmax router over ALL published experts, top-k
  renormalised, gated (SiLU) experts, no shared expert; the layer is told
  which experts it holds and computes their part of the result through the
  dropless grouped product of ``parallel/moe.py``, as ``nemotron_h``'s does;
  a rematerialised block keeps the router's logits, the top-k's results and
  the dispatch's rows (:data:`EXPERT_KEPT`), so its reverse pass replays no
  product, selection or sort.

then a final RMSNorm and the untied head.

In bfloat16 on a TPU (``models/danet.py::auto_wants_flash``, the rule every
kernel of this package is chosen by) the block runs as the Mosaic calls of
``ops/pallas_attention.py``: index scores forward and reverse, the exact
selection, the causal flash kernels masked by the key set, and the
head-averaged probabilities — no array of (heads, S, S) reaches HBM.
Otherwise the einsum / ``top_k`` forms of ``ops/attention.py`` run.  A
rematerialised block keeps the forward call's output and log-sum-exp and
the key set, so its reverse pass runs no second forward call or selection;
and it keeps the alignment loss's gradient with respect to the indexer's
three operands (:func:`index_align_loss` takes it in the forward pass,
where the scores and the target are alive: 36 MB a layer at 8,192
positions), so its reverse pass recomputes neither of those (S, S) arrays.

The counts of experts and vocabulary rows in the configuration are what
this chip HOLDS of a stated deployment; widths are never cut.  Parameters
are float32 masters; the indexer, the router and every softmax are float32
whatever the compute dtype.

Scopes: blocks ``l00, l01, …`` alternate ``attn`` / ``moe`` (block ``2i`` is
layer ``i``'s attention); inside ``attn/l<k>/``: ``indexer`` (the index
scores' reverse call too, which runs in the forward phase),
``topk_select``, ``index_align``; inside ``moe/l<k>/``: ``router``,
``dispatch``, ``routed_experts``, ``combine``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..ops import attention as attention_ops
from ..ops import pallas_attention
from ..parallel import moe as moe_lib
from ..telemetry import counters, scopes
from . import danet
from .nemotron_h import (_dense_init, _dot, _ones, layer_name, load_preset,
                         rms_norm)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

#: pairs attended over causal pairs, the fullest layer (``topk`` >= the
#: sequence: 1)
COUNTER_KEPT_SHARE = counters.declare("sparse_attn_kept_share", "max")
#: keys kept beyond ``min(t + 1, topk)``, over queries and layers: the
#: selection is exact, so anything but 0 is a fault
COUNTER_OVER_TOPK = counters.declare("sparse_attn_keys_over_topk", "sum")
#: the alignment loss's name in the ``losses`` collection
INDEX_ALIGN_LOSS = "index_align"

#: a preset small enough for the CPU tests and the README's command line
#: (every mechanism present: rows that select, experts not held, unequal
#: rotary sections); the benchmark's configuration file has the published
#: widths
PRESETS: dict[str, dict] = {
    "tiny": {
        "hidden_size": 64, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "rope_theta": 10000,
        "rope_scaling": {"mrope_section": [2, 3, 3]},
        "sa_config": {"indexer_num_heads": 4, "indexer_head_dim": 8,
                      "indexer_num_kv_heads": 1, "topk": 8},
        "num_experts": 4, "published": {"num_experts": 8},
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "norm_topk_prob": True,
    },
}


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The sizes the layers use (hashable: a Flax module field)."""

    hidden_size: int
    vocab_size: int
    layers: int
    norm_eps: float
    q_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    mrope_section: tuple   # rotary pairs turned by (temporal, height, width)
    index_heads: int
    index_head_dim: int
    topk: int
    experts_total: int      # the router's width: every published expert
    experts_held: int       # experts whose weights live here
    expert_offset: int      # ... numbered from this one on
    experts_per_token: int
    expert_hidden: int
    norm_topk: bool
    index_align_weight: float

    @classmethod
    def from_dict(cls, c: dict) -> "LMConfig":
        sa = c["sa_config"]
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("the indexer has one key head "
                             "(sa_config.indexer_num_kv_heads)")
        section = tuple(c["rope_scaling"]["mrope_section"])
        if 2 * sum(section) != c["head_dim"]:
            raise ValueError(f"mrope_section {section} does not cover the "
                             f"{c['head_dim'] // 2} rotary pairs of a head")
        held = c["num_experts"]
        return cls(
            hidden_size=c["hidden_size"], vocab_size=c["vocab_size"],
            layers=c["num_hidden_layers"], norm_eps=c["rms_norm_eps"],
            q_heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            rope_theta=float(c["rope_theta"]), mrope_section=section,
            index_heads=sa["indexer_num_heads"],
            index_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
            experts_total=c.get("published", {}).get("num_experts", held),
            experts_held=held, expert_offset=c.get("expert_offset", 0),
            experts_per_token=c["num_experts_per_tok"],
            expert_hidden=c["moe_intermediate_size"],
            norm_topk=bool(c.get("norm_topk_prob", True)),
            index_align_weight=float(c.get("index_align_loss_weight", 1.0)))


# ------------------------------------------------------------------ pieces
def text_positions(batch: int, length: int):
    """The three M-RoPE position rows of plain text: (3, B, S), all equal."""
    return jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32),
                            (3, batch, length))


def rotary_angles(positions, pairs: int, theta: float,
                  section: tuple | None = None):
    """(B, S, pairs) float32: pair ``i`` turns by ``position · theta^(-i /
    pairs)``, its position taken from the row of ``positions`` (3, B, S)
    that ``section`` gives it — the first ``section[0]`` pairs the temporal
    row, the next the height's, the last the width's (M-RoPE); with no
    ``section`` every pair the temporal row."""
    inv = theta ** (-jnp.arange(0, 2 * pairs, 2, dtype=F32) / (2 * pairs))
    row = np.repeat(np.arange(3), section) if section else np.zeros(pairs, int)
    pos = jnp.moveaxis(positions.astype(F32)[row], 0, -1)    # (B, S, pairs)
    return pos * inv


def rotate(x, ang):
    """Rotate-half rotary embedding of ``x`` (B, S, heads, 2·pairs) by
    ``ang`` (B, S, pairs), computed in float32."""
    half = x.shape[-1] // 2
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    x32 = x.astype(F32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return (x32 * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


def swiglu(gate_up):
    """``silu(a) * b`` of a fused ``[a | b]`` product."""
    a, b = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(a) * b


def expert_chunk_rows(expected_rows: float) -> int:
    """Rows of one chunk of the expert layer's row buffer
    (``parallel/moe.py::dropless_routed`` runs ``ceil(live / rows)`` chunks),
    sized so that the rows expected under even routing **and a quarter more**
    fill a whole number of chunks.  At :data:`moe_lib.CHUNK_ROWS` = 4,096 the
    8,192 rows that a step of 8,192 tokens expects (top-8, 16 of 128 held)
    lie on a chunk's edge: each layer runs two chunks or three by the toss of
    its routing, and the step's time moves by 1.8% from seed to seed (PERF.md
    section 6, PR 35); 5,120 rows make it two."""
    chunks = max(1, math.ceil(expected_rows / moe_lib.CHUNK_ROWS))
    return 1024 * math.ceil(1.25 * expected_rows / chunks / 1024)


def _dot32(x, w):
    """``x @ w`` in float32 at full precision (the indexer, the router)."""
    return jnp.dot(x.astype(F32), w, precision=HIGHEST)


def _log_softmax_over(scores, keep):
    return jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)


def index_align_kl(p, scores, keep):
    """Mean over the queries of ``KL(p[t, ·] ‖ softmax over the key set of
    scores[t, ·])``; ``p`` is zero outside the set and sums to one on it."""
    return _kl_to(p, _log_softmax_over(scores, keep), keep)


def _kl_to(p, logq, keep):
    live = keep & (p > 0)
    terms = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                 - jnp.where(live, logq, 0.0)), 0.0)
    return terms.sum(-1).mean()


#: ``checkpoint_name``s of the alignment loss's gradient with respect to the
#: indexer's operands (float32: S x 16 x 64, S x 64 and S x 16 a sequence).
#: They are all that the loss's reverse pass reads, so a block that keeps
#: them recomputes neither the index scores nor the target
INDEX_GRADS_KEPT = ("index_align_dq", "index_align_dk", "index_align_dw")


def _einsum_scores_grads(qi, ki, wi, g):
    """``ops/attention.py::indexer_scores``'s reverse pass at cotangent
    ``g``: what ``flash_indexer_scores_grads`` is to the kernel."""
    return jax.vjp(attention_ops.indexer_scores, qi, ki, wi)[1](g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def index_align_loss(scores_grads, qi, ki, wi, scores, p, keep):
    """:func:`index_align_kl` of the indexer's ``scores`` of ``qi, ki, wi``,
    differentiable in those three alone: ``scores`` (computed for the
    selection), the target ``p`` and the key set ``keep`` are constants.

    A loss is a scalar, so its gradient is known, up to the scalar that the
    reverse pass brings, when the loss is: differentiated, this takes the
    gradient through the scores in the **forward** pass (``scores_grads(qi,
    ki, wi, g) -> (dqi, dki, dwi)``, the scores' reverse pass: ``ops/
    pallas_attention.py::flash_indexer_scores_grads`` or the einsum form's)
    and keeps that, named :data:`INDEX_GRADS_KEPT`; the reverse pass scales
    three small arrays.  Not differentiated, it computes the loss only."""
    with jax.named_scope(scopes.ATTN_INDEX_ALIGN):
        return index_align_kl(p, scores, keep)


def _index_align_fwd(scores_grads, qi, ki, wi, scores, p, keep):
    with jax.named_scope(scopes.ATTN_INDEX_ALIGN):
        logq = _log_softmax_over(scores, keep)
        loss = _kl_to(p, logq, keep)
        # d(sum of the rows' KL) / d(scores): the mean's 1 / rows waits for
        # the reverse pass, so that the reverse call's bfloat16 products see
        # values of a probability's size
        d = jnp.where(keep, jnp.exp(logq) - p, 0.0)
    with jax.named_scope(scopes.ATTN_INDEXER):
        grads = scores_grads(qi, ki, wi, d)
    return loss, tuple(map(checkpoint_name, grads, INDEX_GRADS_KEPT))


def _index_align_bwd(scores_grads, grads, g):
    g = g / math.prod(grads[2].shape[:2])   # the mean over B x S queries
    return (*(g * x for x in grads), None, None, None)


index_align_loss.defvjp(_index_align_fwd, _index_align_bwd)


# ------------------------------------------------------------------ blocks
class SparseAttention(nn.Module):
    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u, positions=None):
        c = self.cfg
        d, qh, kvh, hd = c.hidden_size, c.q_heads, c.kv_heads, c.head_dim
        ih, ihd = c.index_heads, c.index_head_dim
        b, length, _ = u.shape
        norm = self.param("norm", _ones, (d,))
        q_proj = self.param("q_proj", _dense_init, (d, qh * hd))
        k_proj = self.param("k_proj", _dense_init, (d, kvh * hd))
        v_proj = self.param("v_proj", _dense_init, (d, kvh * hd))
        o_proj = self.param("o_proj", _dense_init, (qh * hd, d))
        q_norm = self.param("q_norm", _ones, (hd,))
        k_norm = self.param("k_norm", _ones, (hd,))
        index_q = self.param("index_q", _dense_init, (d, ih * ihd))
        index_k = self.param("index_k", _dense_init, (d, ihd))
        index_w = self.param("index_w", _dense_init, (d, ih))
        index_k_scale = self.param("index_k_scale", _ones, (ihd,))
        index_k_bias = self.param("index_k_bias", nn.initializers.zeros,
                                  (ihd,))
        if positions is None:
            positions = text_positions(b, length)
        flash = danet.auto_wants_flash(self.dtype)

        x = rms_norm(u, norm, c.norm_eps)
        q = _dot(x, q_proj, self.dtype).reshape(b, length, qh, hd)
        k = _dot(x, k_proj, self.dtype).reshape(b, length, kvh, hd)
        v = _dot(x, v_proj, self.dtype).reshape(b, length, kvh, hd)
        ang = rotary_angles(positions, hd // 2, c.rope_theta, c.mrope_section)
        q = rotate(rms_norm(q, q_norm, c.norm_eps), ang)
        k = rotate(rms_norm(k, k_norm, c.norm_eps), ang)

        with jax.named_scope(scopes.ATTN_INDEXER):
            xi = jax.lax.stop_gradient(x)
            ang_i = rotary_angles(positions, ihd // 2, c.rope_theta)
            qi = rotate(_dot32(xi, index_q).reshape(b, length, ih, ihd),
                        ang_i)
            ki = _dot32(xi, index_k)
            ki = ki - ki.mean(-1, keepdims=True)
            ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True)
                                    + c.norm_eps)
            ki = rotate((ki * index_k_scale + index_k_bias)[:, :, None, :],
                        ang_i)[:, :, 0]
            wi = _dot32(xi, index_w)
            # a constant: no gradient passes the selection, and the
            # alignment loss takes its own through the scores
            scores = jax.lax.stop_gradient(
                (pallas_attention.flash_indexer_scores if flash
                 else attention_ops.indexer_scores)(qi, ki, wi))
        with jax.named_scope(scopes.ATTN_TOPK_SELECT):
            keep = (pallas_attention.flash_topk_keep(scores, c.topk) if flash
                    else attention_ops.topk_keep(scores, c.topk))
            keep = checkpoint_name(
                keep, pallas_attention.SPARSE_KEPT_BY_REVERSE[2])
        if flash:
            out, lse = pallas_attention.flash_sparse_attention(q, k, v, keep)
        else:
            out = attention_ops.causal_attention(q, k, v, keep)
        with jax.named_scope(scopes.ATTN_INDEX_ALIGN):
            if flash:
                p = pallas_attention.flash_head_mean_probs(q, k, keep, lse)
                keep = keep != 0
            else:
                p = jax.lax.stop_gradient(
                    attention_ops.head_mean_probs(q, k, keep))
            kept = keep.sum(-1, dtype=jnp.int32)              # (B, S)
            want = attention_ops.keys_wanted(length, c.topk)
            self.sow(counters.COLLECTION, COUNTER_KEPT_SHARE,
                     kept.sum().astype(F32)
                     / (b * length * (length + 1) / 2))
            self.sow(counters.COLLECTION, COUNTER_OVER_TOPK,
                     jnp.maximum(kept - want, 0).sum().astype(F32))
        # names its own parts: index_align, and indexer around the scores'
        # reverse call
        align = index_align_loss(
            pallas_attention.flash_indexer_scores_grads if flash
            else _einsum_scores_grads, qi, ki, wi, scores, p, keep)
        # the mean over layers: each layer sows its share
        self.sow("losses", INDEX_ALIGN_LOSS, align / c.layers)
        out = out.reshape(b, length, qh * hd)
        return u + _dot(out, o_proj, self.dtype, out=u.dtype)


#: ``checkpoint_name``s of what an expert block's reverse pass reads of its
#: forward pass past a product or a selection: the router's logits (before
#: the softmax, whose rule reads its own result), both results of the top-k
#: (the normalisation reads the values) and the two arrays of the dispatch
#: that the routed experts' residuals hold.  A block that keeps them replays
#: no product, no top-k and no sort (the routed sum feeds the block's output
#: alone, so the replay holds no forward chunk loop either way)
EXPERT_KEPT = (_LOGITS, _TOP, _IDX, _ROWS, _GROUP_SIZES) = (
    "moe_router_logits", "moe_topk_gates", "moe_topk_idx",
    "moe_dispatch_rows", "moe_group_sizes")


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def chosen_gates(gates, k: int):
    """``jax.lax.top_k(gates, k)``, differentiated through its *named*
    results: ``top_k``'s own rule reads the ids it made itself, which no
    policy keeps, so a rematerialised block would select again; and the
    values gathered by kept ids (``take_along_axis``) cost the cell six
    times the selection (PERF.md section 6, PR 38)."""
    return tuple(jax.lax.top_k(gates, k))


@chosen_gates.defjvp
def _chosen_gates_jvp(k, primals, tangents):
    top, idx = map(checkpoint_name, jax.lax.top_k(primals[0], k),
                   (_TOP, _IDX))
    return (top, idx), (jnp.take_along_axis(tangents[0], idx, axis=-1),
                        np.zeros(idx.shape, jax.dtypes.float0))


class GatedMoE(nn.Module):
    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        d, held, off = c.hidden_size, c.experts_held, c.expert_offset

        norm = self.param("norm", _ones, (d,))
        router = self.param("router", _dense_init, (d, c.experts_total))
        w1 = self.param("w1", _dense_init, (held, d, 2 * c.expert_hidden))
        w2 = self.param("w2", _dense_init, (held, c.expert_hidden, d))

        x = rms_norm(u, norm, c.norm_eps).reshape(-1, d)
        with jax.named_scope(scopes.MOE_ROUTER):
            gates = jax.nn.softmax(
                checkpoint_name(_dot32(x, router), _LOGITS), axis=-1)
            top, idx = chosen_gates(gates, c.experts_per_token)
            weights = gates[:, off:off + held]
            if c.norm_topk:
                weights = weights / top.sum(-1, keepdims=True)
        with jax.named_scope(scopes.MOE_DISPATCH):
            disp = moe_lib.dropless_dispatch(
                idx, expert_offset=off, n_held=held)
            disp = disp._replace(
                rows=checkpoint_name(disp.rows, _ROWS),
                group_sizes=checkpoint_name(disp.group_sizes,
                                            _GROUP_SIZES))
        # names its own parts: dispatch / routed_experts / combine
        routed, chunks_run = moe_lib.dropless_routed(
            x, weights, w1, w2, disp, swiglu,
            chunk_rows=getattr(c, "expert_chunk_rows", None)
            or expert_chunk_rows(
                x.shape[0] * c.experts_per_token * held / c.experts_total))
        self.sow(counters.COLLECTION, moe_lib.COUNTER_DROPPED, disp.dropped)
        self.sow(counters.COLLECTION, moe_lib.COUNTER_LOAD,
                 moe_lib.expert_load_max_over_mean(disp))
        self.sow(counters.COLLECTION, moe_lib.COUNTER_CHUNKS, chunks_run)
        return u + routed.astype(u.dtype).reshape(u.shape)


def expert_kept_bytes(c, tokens: int) -> int:
    """Bytes of :data:`EXPERT_KEPT` in one expert block over ``tokens``
    positions: float32 logits, the top-k's gates and ids, the row buffer's
    tokens and the held experts' group sizes."""
    return 4 * (tokens * (c.experts_total + 2 * c.experts_per_token)
                + moe_lib.dropless_buffer_rows(
                    tokens, c.experts_per_token, c.experts_held)
                + c.experts_held)


_KEEP_SPARSE_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *pallas_attention.SPARSE_KEPT_BY_REVERSE, *INDEX_GRADS_KEPT)
KEEP_EXPERT_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *EXPERT_KEPT)


class KeyeLM(nn.Module):
    """``tokens (b, l) int32 -> (logits,)``: float32, over the vocabulary
    rows held here.  ``positions``: the (3, b, l) M-RoPE rows (temporal,
    height, width); without them, text positions (the three rows equal)."""

    cfg: LMConfig
    dtype: Any = F32
    remat: bool = True

    @property
    def vocab_size(self) -> int:
        """The ids a token source may draw (the vocabulary rows held)."""
        return self.cfg.vocab_size

    @property
    def loss_weights(self) -> tuple:
        return (1.0,)

    @property
    def aux_loss_weight(self) -> float:
        """The weight of what the model sows into ``losses`` (the indexer's
        alignment loss) in the training objective."""
        return self.cfg.index_align_weight

    @nn.compact
    def __call__(self, tokens, train: bool = False, positions=None):
        c = self.cfg
        d, v = c.hidden_size, c.vocab_size
        embed = self.param("embed", nn.initializers.normal(1.0), (v, d))
        with jax.named_scope(scopes.EMBED):
            x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        attn, moe = SparseAttention, GatedMoE
        if self.remat:
            # per block; an attention block keeps what its reverse pass
            # reads of the forward call, the key set, and the alignment
            # loss's gradient with respect to the indexer's operands
            attn = nn.remat(attn, policy=_KEEP_SPARSE_RESIDUALS)
            moe = nn.remat(moe, policy=KEEP_EXPERT_RESIDUALS)
        for i in range(c.layers):
            with jax.named_scope(scopes.ATTN):
                x = attn(c, self.dtype, name=layer_name(2 * i))(x, positions)
            with jax.named_scope(scopes.MOE):
                x = moe(c, self.dtype, name=layer_name(2 * i + 1))(x)
        final_norm = self.param("final_norm", _ones, (d,))
        lm_head = self.param("lm_head", _dense_init, (d, v))
        with jax.named_scope(scopes.LM_HEAD):
            return (_dot(rms_norm(x, final_norm, c.norm_eps), lm_head,
                         self.dtype, out=F32),)

    def activation_bytes(self, batch: int, seq_len: int) -> int:
        """A bound on the step's live activations on one device, for the
        planner's memory model (``parallel/plan.py``): what per-block
        recomputation keeps (every block's input; an attention block's
        output, log-sum-exp and key set, and the index scores' float32
        gradients; an expert block's :data:`EXPERT_KEPT`), the largest
        single block while it is recomputed and differentiated, and the
        head's float32 logits with their gradient."""
        c = self.cfg
        t = batch * seq_len
        item = jnp.dtype(self.dtype).itemsize
        pairs = batch * seq_len * seq_len
        kept = (2 * c.layers + 2) * t * c.hidden_size * item
        if not self.remat:
            kept *= 8
        else:
            kept += c.layers * expert_kept_bytes(c, t)
        kept += c.layers * t * 4 * (
            c.index_heads * c.index_head_dim + c.index_head_dim
            + c.index_heads)
        if danet.auto_wants_flash(self.dtype):
            kept += c.layers * (t * c.q_heads * c.head_dim * item + pairs)
            # index scores, their gradient and the target (float32), the
            # key set twice (int8), then q, out and their gradients
            attn = pairs * (3 * 4 + 2) + t * c.head_dim * (
                c.q_heads * (4 * item + 3 * 4) + 4 * c.kv_heads * item)
        else:  # the einsum forms: every head's scores, whole
            attn = 3 * pairs * 4 * (c.q_heads + c.index_heads)
        rows = moe_lib.chunk_rows_of(
            moe_lib.dropless_buffer_rows(t, c.experts_per_token,
                                         c.experts_held),
            expert_chunk_rows(t * c.experts_per_token * c.experts_held
                              / c.experts_total))
        experts = 2 * rows * (c.hidden_size + 3 * c.expert_hidden) * item
        return int(kept + max(attn, experts) + 2 * t * c.vocab_size * 4)


def build_keye_lm(lm_config: str | dict = "", dtype=F32,
                  remat: bool = True) -> KeyeLM:
    return KeyeLM(LMConfig.from_dict(load_preset(lm_config, PRESETS)),
                  dtype=dtype, remat=remat)
