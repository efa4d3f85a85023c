"""``kanana_lm``: a token model with multi-head latent attention and a
sigmoid-routed expert layer with shared experts, on the training path (the
Kanana-2 family, ``model_type`` ``deepseek_v3``: 48 layers in the published
30B-A3B, the first dense, every other an expert layer).

Every layer is two blocks, each ``x <- x + f(RMSNorm(x))``:

* :class:`LatentAttention` — multi-head latent attention (MLA) with no
  LoRA on the queries: ``q = x Wq``, each head's ``qk_head_dim`` split into
  ``q_nope`` and ``q_pe``; ``[c, k_pe] = x Wkva``, the latent ``c``
  (``kv_lora_rank`` wide) through its own RMSNorm, ``[k_nope, v] = c Wkvb``
  per head; ``q_pe`` and ``k_pe`` turn by the rotary embedding with the
  pairs **interleaved**, channels ``(2i, 2i + 1)`` (``rope_interleave``),
  and ``k_pe`` is ONE head that every query head reads; ``q = [q_nope ‖
  q_pe]``, ``k = [k_nope ‖ k_pe]``; causal softmax attention scaled by
  ``qk_head_dim^-1/2`` over values narrower than the keys
  (``v_head_dim``); then ``o Wo``.
* :class:`DenseMLP` (the first ``first_k_dense_replace`` layers) — a SwiGLU
  of width ``intermediate_size``.
* :class:`SigmoidMoE` (every later layer) — ``s = sigmoid(x Wr)`` over ALL
  published experts in float32, the top-k of ``s + b`` (``b``, the
  score-correction bias, moves the choice and not the weights, and no
  gradient reaches it: held where the checkpoint puts it), weights ``s_e /
  sum of the chosen s x routed_scaling_factor``
  (``parallel/moe.py::sigmoid_route``, Nemotron's router); the held
  experts' part as SwiGLUs through ``parallel/moe.py``'s dropless layer, as
  ``keye_lm``'s; and the shared experts, one SwiGLU of ``n_shared_experts x
  moe_intermediate_size``, added for every token.  ``n_group`` =
  ``topk_group``: group-limited routing chooses every group, which is no
  limit.

then a final RMSNorm and the untied head.

In bfloat16 on a TPU (``models/danet.py::auto_wants_flash``) attention runs
as the causal Mosaic flash kernels of ``ops/pallas_attention.py``, forward
and reverse, at q and k 192 wide and v 128 wide; otherwise the einsum form.
A rematerialised attention block keeps what its reverse pass reads past a
product (:data:`ATTN_KEPT`): the latent before its norm, and the flash
call's heads-first q, k and v, output and log-sum-exp — 346 MB a layer at
8,192 positions — so its replay runs no projection, no rotary of a head and
no transpose to heads-first; an expert block keeps
the router's logits, choice and chosen scores,
the dispatch's rows and the shared experts' pre-activation; the dense block
its pre-activation.  The counts of experts and vocabulary rows in the
configuration are what this chip HOLDS of a stated deployment; widths are
never cut.

Scopes: blocks ``l00, l01, …``: ``attn`` then ``mlp`` (a dense layer) or
``moe``; inside ``attn/l<k>/``: ``mla_latent`` (the latent path); inside
``moe/l<k>/``: ``router``, ``dispatch``, ``routed_experts``, ``combine``,
``shared_expert``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..ops import pallas_attention
from ..ops.attention import causal_attention
from ..parallel import moe as moe_lib
from ..telemetry import counters, scopes
from . import danet
from .keye_lm import expert_chunk_rows, rotary_angles, swiglu, text_positions
from .nemotron_h import (_dense_init, _dot, _ones, layer_name, load_preset,
                         rms_norm)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

#: a preset small enough for the CPU tests and the README's command line
#: (a dense layer then two expert layers, experts not held, values narrower
#: than keys); the benchmark's configuration file has the published widths
PRESETS: dict[str, dict] = {
    "tiny": {
        "hidden_size": 64, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "q_lora_rank": None, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24,
        "v_head_dim": 16, "rope_theta": 1000000, "rope_interleave": True,
        "rope_scaling": None,
        "n_routed_experts": 4, "published": {"n_routed_experts": 8},
        "num_experts_per_tok": 3, "moe_intermediate_size": 16,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "tie_word_embeddings": False,
    },
}


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The sizes the layers use (hashable: a Flax module field)."""

    hidden_size: int
    vocab_size: int
    layers: int
    dense_layers: int       # the leading layers whose block is dense
    dense_hidden: int
    norm_eps: float
    heads: int
    kv_rank: int            # the latent's width
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    rope_theta: float
    experts_total: int      # the router's width: every published expert
    experts_held: int       # experts whose weights live here
    expert_offset: int      # ... numbered from this one on
    experts_per_token: int
    expert_hidden: int
    shared_hidden: int      # the shared experts as one SwiGLU
    routed_scale: float
    norm_topk: bool
    #: rows of one chunk of the expert layer's row buffer, where the
    #: deployment states them; None: ``keye_lm.expert_chunk_rows``
    expert_chunk_rows: int | None = None

    @classmethod
    def from_dict(cls, c: dict) -> "LMConfig":
        refused = [
            (c.get("q_lora_rank") is not None,
             "the queries are one product: q_lora_rank must be null"),
            (not c.get("rope_interleave", False),
             "the rotary pairs are interleaved: rope_interleave must be true"),
            (bool(c.get("rope_scaling")),
             "the rotary angles are the plain position's: rope_scaling must "
             "be null"),
            (c.get("scoring_func", "sigmoid") != "sigmoid"
             or c.get("topk_method", "noaux_tc") != "noaux_tc",
             "the router is sigmoid scores, top-k with the score-correction "
             "bias (scoring_func sigmoid, topk_method noaux_tc)"),
            (c.get("n_group", 1) != c.get("topk_group", 1),
             "group-limited routing is not built: topk_group must equal "
             "n_group"),
            (c.get("moe_layer_freq", 1) != 1,
             "every layer after the dense ones is an expert layer: "
             "moe_layer_freq must be 1"),
            (c.get("tie_word_embeddings", False),
             "the head is its own: tie_word_embeddings must be false"),
            (c.get("qk_head_dim", c["qk_nope_head_dim"]
                   + c["qk_rope_head_dim"])
             != c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
             "qk_head_dim must be qk_nope_head_dim + qk_rope_head_dim"),
        ]
        for bad, why in refused:
            if bad:
                raise ValueError(f"kanana_lm: {why}")
        held = c["n_routed_experts"]
        return cls(
            hidden_size=c["hidden_size"], vocab_size=c["vocab_size"],
            layers=c["num_hidden_layers"],
            dense_layers=c["first_k_dense_replace"],
            dense_hidden=c["intermediate_size"], norm_eps=c["rms_norm_eps"],
            heads=c["num_attention_heads"], kv_rank=c["kv_lora_rank"],
            qk_nope_dim=c["qk_nope_head_dim"],
            qk_rope_dim=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
            rope_theta=float(c["rope_theta"]),
            experts_total=c.get("published", {}).get("n_routed_experts",
                                                     held),
            experts_held=held, expert_offset=c.get("expert_offset", 0),
            experts_per_token=c["num_experts_per_tok"],
            expert_hidden=c["moe_intermediate_size"],
            shared_hidden=c["n_shared_experts"] * c["moe_intermediate_size"],
            routed_scale=float(c["routed_scaling_factor"]),
            norm_topk=bool(c.get("norm_topk_prob", True)),
            expert_chunk_rows=c.get("expert_chunk_rows"))

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


# ------------------------------------------------------------------ pieces
def rotate_pairs(x, ang):
    """The rotary embedding of ``x`` (B, S, heads, 2·pairs) by ``ang`` (B,
    S, pairs) with the pairs **interleaved**: channels ``(2i, 2i + 1)`` turn
    by ``ang[..., i]`` and stay where they are.  Computed in float32."""
    x32 = x.astype(F32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    even, odd = x32[..., 0], x32[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape).astype(x.dtype)


# ------------------------------------------------------------------ blocks
#: ``checkpoint_name``s of what an attention block's reverse pass reads of
#: its forward pass past a product: the latent with the shared key head
#: before their norm and rotary (``x Wkva``: the up-projection's weight
#: gradient and the norm's reverse read it), and the attention's operands —
#: q after its rotary, the keys assembled, v — which the causal rule below
#: names heads-first on the kernel path, as the reverse call reads them.
#: With the call's output and log-sum-exp beside them the replay holds no
#: product, no rotary of a head and no transpose to heads-first; it renorms
#: the input, one elementwise pass (keeping it too measured no faster on
#: the chip: PERF.md section 4)
_LATENT = "mla_kv_latent"
MLA_CAUSAL = pallas_attention.Causal(
    kept_operands=("mla_attn_q", "mla_attn_k", "mla_attn_v"))
ATTN_KEPT = (_LATENT, *MLA_CAUSAL.kept_operands, *MLA_CAUSAL.kept)


class LatentAttention(nn.Module):
    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        d, h, r = c.hidden_size, c.heads, c.kv_rank
        dn, dr, dv = c.qk_nope_dim, c.qk_rope_dim, c.v_dim
        b, length, _ = u.shape
        norm = self.param("norm", _ones, (d,))
        q_proj = self.param("q_proj", _dense_init, (d, h * c.qk_dim))
        kv_a_proj = self.param("kv_a_proj", _dense_init, (d, r + dr))
        kv_a_norm = self.param("kv_a_norm", _ones, (r,))
        kv_b_proj = self.param("kv_b_proj", _dense_init, (r, h * (dn + dv)))
        o_proj = self.param("o_proj", _dense_init, (h * dv, d))

        x = rms_norm(u, norm, c.norm_eps)
        q = _dot(x, q_proj, self.dtype).reshape(b, length, h, c.qk_dim)
        with jax.named_scope(scopes.MLA_LATENT):
            latent = checkpoint_name(_dot(x, kv_a_proj, self.dtype), _LATENT)
            kv = _dot(rms_norm(latent[..., :r], kv_a_norm, c.norm_eps),
                      kv_b_proj, self.dtype).reshape(b, length, h, dn + dv)
            ang = rotary_angles(text_positions(b, length), dr // 2,
                                c.rope_theta)
            q = jnp.concatenate([q[..., :dn], rotate_pairs(q[..., dn:], ang)],
                                -1)
            k_pe = rotate_pairs(latent[:, :, None, r:], ang)  # one key head
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_pe, (b, length, h, dr))],
                -1)
            v = kv[..., dn:]
        # bfloat16 on a TPU: the Mosaic flash kernels, forward and reverse,
        # and no (length, length) array reaches HBM; else the einsum form,
        # whose reverse reads q, k and v as they are
        if danet.auto_wants_flash(self.dtype):
            out = pallas_attention.flash_causal_attention(q, k, v,
                                                          rule=MLA_CAUSAL)
        else:
            out = causal_attention(*map(checkpoint_name, (q, k, v),
                                        MLA_CAUSAL.kept_operands))
        out = out.reshape(b, length, h * dv)
        return u + _dot(out, o_proj, self.dtype, out=u.dtype)


#: ``checkpoint_name`` of the dense block's pre-activation: its replay runs
#: no product
_MLP_PRE = "mlp_pre"


class DenseMLP(nn.Module):
    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        d = c.hidden_size
        norm = self.param("norm", _ones, (d,))
        w1 = self.param("w1", _dense_init, (d, 2 * c.dense_hidden))
        w2 = self.param("w2", _dense_init, (c.dense_hidden, d))
        pre = checkpoint_name(
            _dot(rms_norm(u, norm, c.norm_eps), w1, self.dtype), _MLP_PRE)
        return u + _dot(swiglu(pre), w2, self.dtype, out=u.dtype)


#: ``checkpoint_name``s of what an expert block's reverse pass reads of its
#: forward pass past a product or a selection: the router's
#: (``parallel/moe.py::ROUTER_KEPT``), the two arrays of the dispatch that
#: the routed experts' residuals hold and the shared experts'
#: pre-activation.  A block that keeps them replays no product, top-k,
#: gather or sort
_ROWS, _GROUP_SIZES, _SHARED_PRE = (
    "moe_dispatch_rows", "moe_group_sizes", "moe_shared_pre")
EXPERT_KEPT = moe_lib.ROUTER_KEPT + (_ROWS, _GROUP_SIZES, _SHARED_PRE)


class SigmoidMoE(nn.Module):
    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        d, held, off = c.hidden_size, c.experts_held, c.expert_offset

        norm = self.param("norm", _ones, (d,))
        router = self.param("router", _dense_init, (d, c.experts_total))
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (c.experts_total,))
        w1 = self.param("w1", _dense_init, (held, d, 2 * c.expert_hidden))
        w2 = self.param("w2", _dense_init, (held, c.expert_hidden, d))
        shared_w1 = self.param("shared_w1", _dense_init,
                               (d, 2 * c.shared_hidden))
        shared_w2 = self.param("shared_w2", _dense_init, (c.shared_hidden, d))

        x = rms_norm(u, norm, c.norm_eps).reshape(-1, d)
        weights, idx = moe_lib.sigmoid_route(
            x, router, bias, k=c.experts_per_token, held=held, offset=off,
            norm_topk=c.norm_topk, scale=c.routed_scale, precision=HIGHEST)
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
            chunk_rows=c.expert_chunk_rows or expert_chunk_rows(
                x.shape[0] * c.experts_per_token * held / c.experts_total))
        with jax.named_scope(scopes.MOE_SHARED_EXPERT):
            pre = checkpoint_name(_dot(x, shared_w1, self.dtype), _SHARED_PRE)
            shared = _dot(swiglu(pre), shared_w2, self.dtype, out=F32)
        self.sow(counters.COLLECTION, moe_lib.COUNTER_DROPPED, disp.dropped)
        self.sow(counters.COLLECTION, moe_lib.COUNTER_LOAD,
                 moe_lib.expert_load_max_over_mean(disp))
        self.sow(counters.COLLECTION, moe_lib.COUNTER_CHUNKS, chunks_run)
        return u + (routed + shared).astype(u.dtype).reshape(u.shape)


def attn_kept_bytes(c: LMConfig, tokens: int, item: int, flash: bool) -> int:
    """Bytes of what one attention block names over ``tokens`` positions at
    ``item`` bytes an element: :data:`ATTN_KEPT` — the latent, q, k and v,
    and where the flash kernels run the call's output and its float32
    log-sum-exp."""
    kept = tokens * (c.kv_rank + c.qk_rope_dim
                     + c.heads * (2 * c.qk_dim + c.v_dim)) * item
    if flash:
        kept += tokens * c.heads * (c.v_dim * item + 4)
    return kept


KEEP_ATTN_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *ATTN_KEPT)
KEEP_MLP_RESIDUALS = jax.checkpoint_policies.save_only_these_names(_MLP_PRE)
KEEP_EXPERT_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *EXPERT_KEPT)


class KananaLM(nn.Module):
    """``tokens (b, l) int32 -> (logits,)``: float32, over the vocabulary
    rows held here."""

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

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        c = self.cfg
        d, v = c.hidden_size, c.vocab_size
        embed = self.param("embed", nn.initializers.normal(1.0), (v, d))
        with jax.named_scope(scopes.EMBED):
            x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        attn, mlp, moe = LatentAttention, DenseMLP, SigmoidMoE
        if self.remat:
            # per block; each keeps by name what its reverse pass reads
            attn = nn.remat(attn, policy=KEEP_ATTN_RESIDUALS)
            mlp = nn.remat(mlp, policy=KEEP_MLP_RESIDUALS)
            moe = nn.remat(moe, policy=KEEP_EXPERT_RESIDUALS)
        for i in range(c.layers):
            with jax.named_scope(scopes.ATTN):
                x = attn(c, self.dtype, name=layer_name(2 * i))(x)
            layer, ffn = (scopes.MLP, mlp) if i < c.dense_layers \
                else (scopes.MOE, moe)
            with jax.named_scope(layer):
                x = ffn(c, self.dtype, name=layer_name(2 * i + 1))(x)
        final_norm = self.param("final_norm", _ones, (d,))
        lm_head = self.param("lm_head", _dense_init, (d, v))
        with jax.named_scope(scopes.LM_HEAD):
            return (_dot(rms_norm(x, final_norm, c.norm_eps), lm_head,
                         self.dtype, out=F32),)

    def activation_bytes(self, batch: int, seq_len: int) -> int:
        """A bound on the step's live activations on one device, for the
        planner's memory model (``parallel/plan.py``): what per-block
        recomputation keeps (every block's input; an attention block's
        :func:`attn_kept_bytes`, a dense block's pre-activation, an expert
        block's :data:`EXPERT_KEPT`), the largest single block while it is
        recomputed and differentiated, and the head's float32 logits with
        their gradient."""
        c = self.cfg
        t = batch * seq_len
        item = jnp.dtype(self.dtype).itemsize
        flash = danet.auto_wants_flash(self.dtype)
        moe_layers = c.layers - c.dense_layers
        buffer_rows = moe_lib.dropless_buffer_rows(
            t, c.experts_per_token, c.experts_held)
        kept = (2 * c.layers + 2) * t * c.hidden_size * item
        if not self.remat:
            kept *= 8
        else:
            kept += c.layers * attn_kept_bytes(c, t, item, flash)
            kept += c.dense_layers * t * 2 * c.dense_hidden * item
            kept += moe_layers * (
                4 * (t * (c.experts_total + 2 * c.experts_per_token)
                     + buffer_rows + c.experts_held)
                + t * 2 * c.shared_hidden * item)
        # q, k, v and their gradients, heads-first copies beside them, the
        # reverse pass's float32 dQ; the einsum form's float32 scores,
        # probabilities and their gradients
        attn = t * c.heads * (2 * c.qk_dim + c.v_dim) * (3 * item + 4)
        if not flash:
            attn += 3 * 4 * c.heads * batch * seq_len ** 2
        rows = moe_lib.chunk_rows_of(
            buffer_rows, c.expert_chunk_rows or expert_chunk_rows(
                t * c.experts_per_token * c.experts_held / c.experts_total))
        experts = 2 * rows * (c.hidden_size + 3 * c.expert_hidden) * item \
            + 2 * t * 3 * c.shared_hidden * item
        dense = 2 * t * 3 * c.dense_hidden * item if c.dense_layers else 0
        return int(kept + max(attn, experts, dense)
                   + 2 * t * c.vocab_size * 4)


def build_kanana_lm(lm_config: str | dict = "", dtype=F32,
                    remat: bool = True) -> KananaLM:
    return KananaLM(LMConfig.from_dict(load_preset(lm_config, PRESETS)),
                    dtype=dtype, remat=remat)
