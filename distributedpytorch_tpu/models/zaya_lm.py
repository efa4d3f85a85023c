"""``zaya_lm``: a token model whose attention runs in a compressed latent
with convolutional mixing and whose router carries its state down the layers
(the ZAYA1 family, ``model_type`` ``zaya``: 40 layers in the published 8B,
each an attention block and an expert block).

Every block is ``x <- a * x + b * f(RMSNorm(x))``, ``a`` and ``b`` learned
per block (``res_scale``, ``out_scale``, one per channel, starting at one):

* :class:`CCAttention` — compressed convolutional attention (CCA): the
  queries and keys are down-projections into a latent of ``q_heads x
  head_dim`` and ``kv_heads x head_dim`` (``q0 = u Wq``, ``k0 = u Wk``); each
  key/value head's values take their first half of channels from token ``t``
  and their second half from token ``t - 1`` (the **value shift**); q0 and
  k0 go through a causal depthwise convolution over time (``cca_time0``
  taps), then a causal convolution over time that mixes the channels within
  each head (``cca_time1`` taps, one group a head); the **q-k mean** ``m_j =
  (mean of q0 over key/value head j's query group + k0_j) / 2`` is added to
  the group's queries and to its key; then q and k are L2-normalised in
  float32, each query head scaled by a learned temperature, and the first
  ``partial_rotary_factor`` of every head turns by the rotary embedding.
  Causal softmax attention over ``logits = q . k`` (the temperature is the
  only scale); the output is projected up from the latent to the hidden size.
* :class:`ZayaExperts` — an MLP router whose input carries across depth:
  ``r_l = u W_down`` and ``rbar_l = (1 - g_l) r_l + g_l rbar_(l-1)``
  (exponential depth averaging, ``g_l`` learned; ``rbar`` starts at the
  stage's first layer, which has no ``g``), then ``logits = GELU(GELU(rbar
  W1 + c1) W2 + c2) W3`` over every published expert **and a skip slot**
  (the mixture-of-depths part), all in float32; each token takes its largest
  logit, its gate is that slot's softmax, and a token that takes the skip
  slot, or an expert held on another chip, adds nothing.  The held experts'
  part (SwiGLU) runs through ``parallel/moe.py``'s dropless layer, as the
  other token models' do.  A rematerialised block keeps the router's state
  and logits, the choice and the dispatch's rows (:data:`EXPERT_KEPT`).

then a final RMSNorm and the head, which is the embedding (tied).  The
balancing bias that the published router adds to its logits before the
choice takes no gradient: the token step has no state outside the
gradient's, so it is held at nought and not built.

In bfloat16 on a TPU (``models/danet.py::auto_wants_flash``) attention runs
as the causal Mosaic flash kernels of ``ops/pallas_attention.py`` with the
temperature and ``sqrt(head_dim)`` folded into q; otherwise the einsum form.
The counts of experts and vocabulary rows in the configuration are what this
chip HOLDS of a stated deployment; widths are never cut.

Scopes: blocks ``l00, l01, …`` alternate ``attn`` / ``moe``; inside
``attn/l<k>/``: ``cca_mix``; inside ``moe/l<k>/``: ``router`` (its MLP and the
depth averaging), ``dispatch``, ``routed_experts``, ``combine``.
"""

from __future__ import annotations

import dataclasses
import math
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
from .keye_lm import (expert_chunk_rows, rotary_angles, rotate, swiglu,
                      text_positions)
from .nemotron_h import (_dense_init, _dot, _ones, causal_depthwise_conv,
                         layer_name, load_preset, rms_norm)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: added to a head's squared norm before the L2 normalisation
L2_EPS = 1e-12

#: the largest share of a step's tokens that one expert layer sent to the
#: skip slot
COUNTER_SKIP_SHARE = counters.declare("moe_skip_share", "max")

#: a preset small enough for the CPU tests and the README's command line
#: (experts not held, the skip slot, partial rotary); the benchmark's
#: configuration file has the published widths
PRESETS: dict[str, dict] = {
    "tiny": {
        "hidden_size": 64, "vocab_size": 256, "rms_norm_eps": 1e-5,
        "num_hidden_layers": 2, "layer_types": ["hybrid", "hybrid"],
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
        "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                       "rope_theta": 5000000,
                                       "rope_type": "default"}},
        "num_experts": 4, "published": {"num_experts": 8},
        "num_experts_per_tok": 1, "moe_intermediate_size": 32,
        "router_hidden_size": 16, "tie_word_embeddings": True,
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
    rotary_dim: int         # the leading channels of a head that turn
    conv_taps: tuple        # (cca_time0, cca_time1)
    router_hidden: int
    experts_total: int      # every published expert; the router has one more
    experts_held: int       # experts whose weights live here
    expert_offset: int      # ... numbered from this one on
    expert_hidden: int

    @classmethod
    def from_dict(cls, c: dict) -> "LMConfig":
        if set(c["layer_types"]) != {"hybrid"}:
            raise ValueError("zaya_lm builds 'hybrid' layers only (an "
                             "attention block and an expert block); "
                             f"layer_types {sorted(set(c['layer_types']))}")
        if c["num_experts_per_tok"] != 1:
            raise ValueError("zaya_lm routes each token to one slot: "
                             "num_experts_per_tok must be 1")
        if not c["tie_word_embeddings"]:
            raise ValueError("zaya_lm's head is its embedding: "
                             "tie_word_embeddings must be true")
        rope = c["rope_parameters"]["hybrid"]
        hd = c["head_dim"]
        held = c["num_experts"]
        return cls(
            hidden_size=c["hidden_size"], vocab_size=c["vocab_size"],
            layers=c["num_hidden_layers"], norm_eps=c["rms_norm_eps"],
            q_heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"], head_dim=hd,
            rope_theta=float(rope["rope_theta"]),
            rotary_dim=int(hd * rope["partial_rotary_factor"]),
            conv_taps=(c["cca_time0"], c["cca_time1"]),
            router_hidden=c["router_hidden_size"],
            experts_total=c.get("published", {}).get("num_experts", held),
            experts_held=held, expert_offset=c.get("expert_offset", 0),
            expert_hidden=c["moe_intermediate_size"])


# ------------------------------------------------------------------ pieces
def shift_time(x, by: int = 1):
    """``x[:, t - by]`` at every ``t``, zeros before the sequence starts."""
    pad = ((0, 0), (by, 0)) + ((0, 0),) * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def causal_grouped_conv(x, w, b, dtype):
    """A causal convolution over time that mixes the channels within each
    group: ``b[g] + sum_i x[:, t - (K - 1) + i, g] @ w[i, g]``.  ``x``: (B,
    L, G, C); ``w``: (K, G, C, C); ``b``: (G, C) -> float32 (B, L, G, C).
    The taps are one product (contracting ``K x C``), operands in ``dtype``
    with float32 accumulation."""
    k = w.shape[0]
    taps = jnp.stack([shift_time(x, k - 1 - i) for i in range(k)], axis=-2)
    return jnp.einsum("blgkc,kgcd->blgd", taps.astype(dtype), w.astype(dtype),
                      preferred_element_type=F32) + b


def l2_normalize(x):
    """``x / |x|`` over the last axis, in float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def rotate_leading(x, ang, dims: int):
    """The rotary embedding over the first ``dims`` channels of each head
    (rotate-half within them); the rest pass unturned."""
    return jnp.concatenate([rotate(x[..., :dims], ang), x[..., dims:]], -1)


def _dot32(x, w):
    return jnp.dot(x.astype(F32), w, precision=HIGHEST)


def _scaled_residual(u, res_scale, out_scale, y):
    """``a * u + b * y`` in float32, in ``u``'s dtype."""
    return (res_scale * u.astype(F32) + out_scale * y).astype(u.dtype)


# ------------------------------------------------------------------ blocks
class CCAttention(nn.Module):
    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        d, qh, kvh, hd = c.hidden_size, c.q_heads, c.kv_heads, c.head_dim
        heads = qh + kvh
        taps0, taps1 = c.conv_taps
        b, length, _ = u.shape

        def grouped_init(key, shape, dtype=F32):
            return jax.random.normal(key, shape, dtype) \
                / math.sqrt(shape[0] * shape[-2])

        norm = self.param("norm", _ones, (d,))
        res_scale = self.param("res_scale", _ones, (d,))
        out_scale = self.param("out_scale", _ones, (d,))
        q_proj = self.param("q_proj", _dense_init, (d, qh * hd))
        k_proj = self.param("k_proj", _dense_init, (d, kvh * hd))
        v_proj = self.param("v_proj", _dense_init, (d, kvh * hd))
        o_proj = self.param("o_proj", _dense_init, (qh * hd, d))
        conv0_w = self.param("conv0_w", _dense_init, (taps0, heads * hd))
        conv0_b = self.param("conv0_b", nn.initializers.zeros, (heads * hd,))
        conv1_w = self.param("conv1_w", grouped_init, (taps1, heads, hd, hd))
        conv1_b = self.param("conv1_b", nn.initializers.zeros, (heads, hd))
        temperature = self.param("temperature", nn.initializers.constant(
            math.sqrt(hd)), (qh,))

        x = rms_norm(u, norm, c.norm_eps)
        q0 = _dot(x, q_proj, self.dtype, out=F32)
        k0 = _dot(x, k_proj, self.dtype, out=F32)
        v = _dot(x, v_proj, self.dtype).reshape(b, length, kvh, hd)
        with jax.named_scope(scopes.ATTN_CCA_MIX):
            v = jnp.concatenate([v[..., :hd // 2],
                                 shift_time(v[..., hd // 2:])], -1)
            mixed = causal_grouped_conv(
                causal_depthwise_conv(jnp.concatenate([q0, k0], -1),
                                      conv0_w, conv0_b
                                      ).reshape(b, length, heads, hd),
                conv1_w, conv1_b, self.dtype)
            k0 = k0.reshape(b, length, kvh, hd)
            mean = 0.5 * (q0.reshape(b, length, kvh, qh // kvh, hd).mean(3)
                          + k0)                           # (B, L, kvh, hd)
            q = (mixed[:, :, :qh].reshape(b, length, kvh, qh // kvh, hd)
                 + mean[:, :, :, None]).reshape(b, length, qh, hd)
            k = mixed[:, :, qh:] + mean
            ang = rotary_angles(text_positions(b, length), c.rotary_dim // 2,
                                c.rope_theta)
            # the kernels and the einsum form divide the scores by
            # sqrt(head_dim): the logits are q . k with the temperature in q
            q = rotate_leading(l2_normalize(q), ang, c.rotary_dim) \
                * (temperature[:, None] * math.sqrt(hd))
            k = rotate_leading(l2_normalize(k), ang, c.rotary_dim)
            q, k = q.astype(self.dtype), k.astype(self.dtype)
        # bfloat16 on a TPU: the Mosaic flash kernels, forward and reverse,
        # and no (length, length) array reaches HBM; else the einsum form
        attend = pallas_attention.flash_causal_attention \
            if danet.auto_wants_flash(self.dtype) else causal_attention
        out = attend(q, k, v).reshape(b, length, qh * hd)
        return _scaled_residual(u, res_scale, out_scale,
                                _dot(out, o_proj, self.dtype, out=F32))


#: ``checkpoint_name``s of what an expert block's reverse pass reads of its
#: forward pass past a product or a selection: the router's state before the
#: depth averaging (its down-projection's result), its logits (before the
#: softmax, whose rule reads its own result), the choice, and the two arrays
#: of the dispatch that the routed experts' residuals hold.  A block that
#: keeps them replays the router's two small products and elementwise passes
EXPERT_KEPT = (_STATE, _LOGITS, _IDX, _ROWS, _GROUP_SIZES) = (
    "moe_router_state", "moe_router_logits", "moe_topk_idx",
    "moe_dispatch_rows", "moe_group_sizes")


class ZayaExperts(nn.Module):
    """``(u, rbar_prev) -> (u', rbar)``: the expert block, and the router
    state it hands the next layer's (``rbar_prev`` None: the stage's first
    layer)."""

    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u, state=None):
        c = self.cfg
        d, rh = c.hidden_size, c.router_hidden
        held, off = c.experts_held, c.expert_offset
        skip = c.experts_total            # the slot after the last expert

        def into_gelu(key, shape, dtype=F32):
            return jax.random.normal(key, shape, dtype) \
                * math.sqrt(2.0 / shape[0])

        def centred(init):
            # a product that reads a GELU's output: no slot starts ahead by
            # the GELUs' common mean (``reference/zaya_lm.py::make_weights``)
            def draw(key, shape, dtype=F32):
                w = init(key, shape, dtype)
                return w - w.mean(axis=0)
            return draw

        norm = self.param("norm", _ones, (d,))
        res_scale = self.param("res_scale", _ones, (d,))
        out_scale = self.param("out_scale", _ones, (d,))
        router_down = self.param("router_down", _dense_init, (d, rh))
        router_w1 = self.param("router_w1", into_gelu, (rh, rh))
        router_b1 = self.param("router_b1", nn.initializers.zeros, (rh,))
        router_w2 = self.param("router_w2", centred(into_gelu), (rh, rh))
        router_b2 = self.param("router_b2", nn.initializers.zeros, (rh,))
        router_w3 = self.param("router_w3", centred(_dense_init),
                               (rh, c.experts_total + 1))
        w1 = self.param("w1", _dense_init, (held, d, 2 * c.expert_hidden))
        w2 = self.param("w2", _dense_init, (held, c.expert_hidden, d))

        x = rms_norm(u, norm, c.norm_eps).reshape(-1, d)
        with jax.named_scope(scopes.MOE_ROUTER):
            r = checkpoint_name(_dot32(x, router_down), _STATE)
            if state is not None:
                depth = self.param("depth_average", nn.initializers.constant(
                    0.5), ())
                r = (1 - depth) * r + depth * state.reshape(r.shape)
            h = jax.nn.gelu(_dot32(r, router_w1) + router_b1,
                            approximate=False)
            h = jax.nn.gelu(_dot32(h, router_w2) + router_b2,
                            approximate=False)
            logits = checkpoint_name(_dot32(h, router_w3), _LOGITS)
            idx = checkpoint_name(
                jnp.argmax(logits, -1).astype(jnp.int32)[:, None], _IDX)
            weights = jax.nn.softmax(logits, -1)[:, off:off + held]
            skipped = (idx == skip).mean(dtype=F32)
        with jax.named_scope(scopes.MOE_DISPATCH):
            disp = moe_lib.dropless_dispatch(
                idx, expert_offset=off, n_held=held)
            disp = disp._replace(
                rows=checkpoint_name(disp.rows, _ROWS),
                group_sizes=checkpoint_name(disp.group_sizes,
                                            _GROUP_SIZES))
        # names its own parts: dispatch / routed_experts / combine; the skip
        # slot takes its share of the rows expected under even routing
        routed, chunks_run = moe_lib.dropless_routed(
            x, weights, w1, w2, disp, swiglu,
            chunk_rows=expert_chunk_rows(
                x.shape[0] * held / (c.experts_total + 1)))
        self.sow(counters.COLLECTION, moe_lib.COUNTER_DROPPED, disp.dropped)
        self.sow(counters.COLLECTION, moe_lib.COUNTER_LOAD,
                 moe_lib.expert_load_max_over_mean(disp))
        self.sow(counters.COLLECTION, moe_lib.COUNTER_CHUNKS, chunks_run)
        self.sow(counters.COLLECTION, COUNTER_SKIP_SHARE, skipped)
        return (_scaled_residual(u, res_scale, out_scale,
                                 routed.reshape(u.shape)),
                r.reshape(u.shape[:-1] + (rh,)))


def expert_kept_bytes(c: LMConfig, tokens: int) -> int:
    """Bytes of :data:`EXPERT_KEPT` in one expert block over ``tokens``
    positions: float32 state and logits, the choice, the row buffer's tokens
    and the held experts' group sizes."""
    return 4 * (tokens * (c.router_hidden + c.experts_total + 2)
                + moe_lib.dropless_buffer_rows(tokens, 1, c.experts_held)
                + c.experts_held)


_KEEP_FLASH_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *pallas_attention.KEPT_BY_REVERSE)
KEEP_EXPERT_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *EXPERT_KEPT)


class ZayaLM(nn.Module):
    """``tokens (b, l) int32 -> (logits,)``: float32, over the vocabulary
    rows held here, by the tied head."""

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
        # the head's rows as well: the stream starts at unit spread, and
        # the final norm's scale at d^-1/2 gives the logits unit spread
        # (``reference/zaya_lm.py::make_weights`` says why)
        embed = self.param("embed", nn.initializers.normal(1.0), (v, d))
        with jax.named_scope(scopes.EMBED):
            x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        attn, moe = CCAttention, ZayaExperts
        if self.remat:
            # per block; an attention block keeps what its reverse pass
            # reads of the forward call
            attn = nn.remat(attn, policy=_KEEP_FLASH_RESIDUALS)
            moe = nn.remat(moe, policy=KEEP_EXPERT_RESIDUALS)
        state = None
        for i in range(c.layers):
            with jax.named_scope(scopes.ATTN):
                x = attn(c, self.dtype, name=layer_name(2 * i))(x)
            with jax.named_scope(scopes.MOE):
                x, state = moe(c, self.dtype, name=layer_name(2 * i + 1))(
                    x, state)
        final_norm = self.param("final_norm", nn.initializers.constant(
            d ** -0.5), (d,))
        with jax.named_scope(scopes.LM_HEAD):
            return (jnp.einsum("bld,vd->blv",
                               rms_norm(x, final_norm, c.norm_eps
                                        ).astype(self.dtype),
                               embed.astype(self.dtype),
                               preferred_element_type=F32),)

    def activation_bytes(self, batch: int, seq_len: int) -> int:
        """A bound on the step's live activations on one device, for the
        planner's memory model (``parallel/plan.py``): what per-block
        recomputation keeps (every block's input and the router state it
        carries; an attention block's output and log-sum-exp; an expert
        block's :data:`EXPERT_KEPT`), the largest single block while it is
        recomputed and differentiated, and the head's float32 logits with
        their gradient."""
        c = self.cfg
        t = batch * seq_len
        item = jnp.dtype(self.dtype).itemsize
        heads = c.q_heads + c.kv_heads
        kept = (2 * c.layers + 2) * t * c.hidden_size * item
        if not self.remat:
            kept *= 8
        else:
            kept += c.layers * (expert_kept_bytes(c, t)
                                + 4 * t * c.router_hidden)
        # the mixing's float32 arrays: q0 and k0, both convolutions' outputs
        # and taps, q and k before and after the norm, and their gradients
        mix = 2 * 8 * t * heads * c.head_dim * 4
        if danet.auto_wants_flash(self.dtype):
            kept += c.layers * t * c.q_heads * (c.head_dim * item + 4)
            attn = t * c.head_dim * (c.q_heads * (4 * item + 3 * 4)
                                     + 4 * c.kv_heads * item)
        else:  # the einsum form: every head's scores, whole
            attn = 3 * 4 * c.q_heads * batch * seq_len ** 2
        rows = moe_lib.chunk_rows_of(
            moe_lib.dropless_buffer_rows(t, 1, c.experts_held),
            expert_chunk_rows(t * c.experts_held / (c.experts_total + 1)))
        experts = 2 * rows * (c.hidden_size + 3 * c.expert_hidden) * item
        return int(kept + max(mix + attn, experts) + 2 * t * c.vocab_size * 4)


def build_zaya_lm(lm_config: str | dict = "", dtype=F32,
                  remat: bool = True) -> ZayaLM:
    return ZayaLM(LMConfig.from_dict(load_preset(lm_config, PRESETS)),
                  dtype=dtype, remat=remat)
