"""``nemotron_h``: a hybrid Mamba-2 / attention / LatentMoE token model on the
training path (the NVIDIA Nemotron-3 family's layer equations).

Every layer is ``x <- x + mixer(RMSNorm(x))`` with the mixer chosen by one
character of ``hybrid_override_pattern``:

* ``M``  :class:`MambaMixer` — Mamba-2 in the chunked (SSD) form: inside a
  chunk a masked ``C B^T`` product with the decay matrix, between chunks a
  scan over the (heads, head_dim, state) states;
* ``*``  :class:`Attention` — causal grouped-query attention, rotary
  embedding over the whole head; in bfloat16 on a TPU (DANet's rule,
  ``models/danet.py::auto_wants_flash``) as the Mosaic flash kernels of
  ``ops/pallas_attention.py``, forward and reverse, otherwise the einsum
  form ``ops/attention.py::causal_attention``; a rematerialised block keeps
  the kernels' output and log-sum-exp, so it runs the forward call once;
* ``E``  :class:`LatentMoE` — sigmoid router over ALL published experts,
  top-k with the score-correction bias, experts in a latent space, one shared
  expert; the layer is told which experts it holds and computes their part
  of the result with the dropless grouped product of ``parallel/moe.py``; a
  rematerialised block keeps the results of its products and selections
  (:data:`EXPERT_KEPT`), so its reverse pass replays elementwise work only.

then a final RMSNorm and the untied head, plus the multi-token-prediction
module (:class:`MTPModule`, DeepSeek-V3 wiring, embedding and head shared).

The counts of heads, groups, experts and vocabulary rows in the
configuration are what this chip HOLDS (a tensor-/expert-parallel share of a
stated deployment); widths are never cut.  Parameters are float32 masters,
the compute dtype is the module's ``dtype``; router scores, ``dt``, the
scan's decays and the softmax are float32 whatever it is.

Scopes (``telemetry/scopes.py``): every block runs under its layer's name
(``mamba`` / ``attn`` / ``moe``) with the block's own name (``l03``) as the
sub-path, so a device trace splits into the layers by construction.
"""

from __future__ import annotations

import dataclasses
import json
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

F32 = jnp.float32

#: a preset small enough for the CPU tests and the README's command line; the
#: benchmark's configuration file has the published widths
PRESETS: dict[str, dict] = {
    "tiny": {
        "hidden_size": 64, "vocab_size": 256, "norm_eps": 1e-5,
        "hybrid_override_pattern": "*EME",
        "mtp_hybrid_override_pattern": "*E", "num_nextn_predict_layers": 1,
        "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2,
        "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "rope_theta": 10000,
        "n_routed_experts": 8, "num_experts_per_tok": 3,
        "moe_latent_size": 32, "moe_intermediate_size": 48,
        "moe_shared_expert_intermediate_size": 96,
        "routed_scaling_factor": 5, "norm_topk_prob": True,
        "mtp_loss_weight": 0.3,
    },
}


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The sizes the layers use (hashable: a Flax module field)."""

    hidden_size: int
    vocab_size: int
    pattern: str
    mtp_pattern: str
    norm_eps: float
    mamba_heads: int
    mamba_head_dim: int
    mamba_groups: int
    state_size: int
    conv_kernel: int
    chunk_size: int
    q_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    experts_total: int      # the router's width: every published expert
    experts_held: int       # experts whose weights live here
    expert_offset: int      # ... numbered from this one on
    experts_per_token: int
    latent_size: int
    expert_hidden: int
    shared_hidden: int
    routed_scale: float
    norm_topk: bool
    mtp_loss_weight: float
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    @classmethod
    def from_dict(cls, c: dict) -> "LMConfig":
        mtp = c["mtp_hybrid_override_pattern"] \
            if c.get("num_nextn_predict_layers", 0) else ""
        held = c["n_routed_experts"]
        return cls(
            hidden_size=c["hidden_size"], vocab_size=c["vocab_size"],
            pattern=c["hybrid_override_pattern"], mtp_pattern=mtp,
            norm_eps=c["norm_eps"], mamba_heads=c["mamba_num_heads"],
            mamba_head_dim=c["mamba_head_dim"], mamba_groups=c["n_groups"],
            state_size=c["ssm_state_size"], conv_kernel=c["conv_kernel"],
            chunk_size=c["chunk_size"], q_heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            rope_theta=float(c["rope_theta"]),
            experts_total=c.get("published", {}).get("n_routed_experts",
                                                     held),
            experts_held=held, expert_offset=c.get("expert_offset", 0),
            experts_per_token=c["num_experts_per_tok"],
            latent_size=c["moe_latent_size"],
            expert_hidden=c["moe_intermediate_size"],
            shared_hidden=c["moe_shared_expert_intermediate_size"],
            routed_scale=float(c["routed_scaling_factor"]),
            norm_topk=bool(c.get("norm_topk_prob", True)),
            mtp_loss_weight=float(c.get("mtp_loss_weight", 0.3)),
            time_step_min=c.get("time_step_min", 0.001),
            time_step_max=c.get("time_step_max", 0.1),
            time_step_floor=c.get("time_step_floor", 1e-4))

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.mamba_groups * self.state_size


def load_preset(spec: str | dict, presets: dict) -> dict:
    """A preset's name (``""`` = ``tiny``), a path to a JSON file of the
    published keys (the benchmark's ``configs/*.json``), or the dict itself."""
    if isinstance(spec, dict):
        return spec
    spec = spec or "tiny"
    if spec in presets:
        return presets[spec]
    try:
        with open(spec) as f:
            return json.load(f)
    except OSError as e:
        raise ValueError(
            f"model.lm_config={spec!r} is neither a preset "
            f"({sorted(presets)}) nor a readable JSON file: {e}") from e


def layer_name(i: int) -> str:
    return f"l{i:02d}"


# ------------------------------------------------------------------ pieces
def _dense_init(key, shape, dtype=F32):
    return jax.random.normal(key, shape, dtype) / math.sqrt(shape[-2])


def _ones(key, shape, dtype=F32):
    del key
    return jnp.ones(shape, dtype)


def rms_norm(x, w, eps):
    """``w * x / rms(x)``: statistics in float32, result in ``x``'s dtype."""
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0))


def _dot(x, w, dtype, out=None):
    """``x @ w`` in the compute dtype with float32 accumulation."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=F32).astype(out or dtype)


def ssd_chunked(x, dt, a, bm, cm, chunk: int):
    """Mamba-2's recurrence ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = C_t . h_t`` in the chunked (SSD) form.

    ``x``: (b, l, h, p) in the compute dtype; ``dt``: (b, l, h) float32,
    already through the softplus; ``a``: (h,) float32, negative; ``bm``,
    ``cm``: (b, l, g, n).  A head reads the B and C of its group.  A length
    that is no multiple of ``chunk`` is padded with steps that neither decay
    nor add (``dt`` 0)."""
    b, length, h, p = x.shape
    g, n = bm.shape[2:]
    r = h // g
    pad = (-length) % chunk
    if pad:
        def padt(v):
            return jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        x, dt, bm, cm = padt(x), padt(dt), padt(bm), padt(cm)
    z = (length + pad) // chunk
    dtype = x.dtype
    x = x.reshape(b, z, chunk, g, r, p)
    dt = dt.reshape(b, z, chunk, g, r)
    bm = bm.reshape(b, z, chunk, g, n)
    cm = cm.reshape(b, z, chunk, g, n)
    cs = jnp.cumsum(dt * a.reshape(g, r), axis=2)          # (b,z,c,g,r) <= 0
    # inside a chunk: y_i += sum_{j<=i} (C_i.B_j) exp(cs_i - cs_j) dt_j x_j
    tril = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None,
                                                    None]
    seg = cs[:, :, :, None] - cs[:, :, None, :]             # (b,z,i,j,g,r)
    decay = jnp.where(tril, jnp.exp(jnp.where(tril, seg, 0.0)), 0.0)
    scores = jnp.einsum("bzign,bzjgn->bzijg", cm, bm,
                        preferred_element_type=F32)
    w = scores[..., None] * decay * dt[:, :, None]          # (b,z,i,j,g,r)
    y = jnp.einsum("bzijgr,bzjgrp->bzigrp", w.astype(dtype), x,
                   preferred_element_type=F32)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cs[:, :, -1:] - cs) * dt               # (b,z,c,g,r)
    states = jnp.einsum("bzjgn,bzjgrp->bzgrpn", bm.astype(F32),
                        x.astype(F32) * to_end[..., None],
                        preferred_element_type=F32)
    chunk_decay = jnp.exp(cs[:, :, -1])                     # (b,z,g,r)

    # between chunks: the state that enters each chunk
    def step(carry, xs):
        st, dec = xs
        return dec[..., None, None] * carry + st, carry

    _, entering = jax.lax.scan(
        step, jnp.zeros((b, g, r, p, n), F32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # (b,z,g,r,p,n)
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "bzign,bzgrpn->bzigrp", cm.astype(F32), entering,
        preferred_element_type=F32)
    return y.reshape(b, z * chunk, h, p)[:, :length]


def rope(x, theta: float):
    """Rotary embedding over the whole head (rotate-half form), computed in
    float32.  ``x``: (b, l, heads, head_dim)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x32 = x.astype(F32)
    x1, x2 = x32[..., :hd // 2], x32[..., hd // 2:]
    return (x32 * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


# ------------------------------------------------------------------ mixers
class MambaMixer(nn.Module):
    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        d, h, p = c.hidden_size, c.mamba_heads, c.mamba_head_dim
        g, n, inner = c.mamba_groups, c.state_size, c.mamba_inner
        b, length, _ = u.shape

        def dt_bias_init(key, shape, dtype=F32):
            dt = jnp.exp(jax.random.uniform(
                key, shape, dtype, math.log(c.time_step_min),
                math.log(c.time_step_max)))
            dt = jnp.maximum(dt, c.time_step_floor)
            return dt + jnp.log(-jnp.expm1(-dt))

        def a_log_init(key, shape, dtype=F32):
            return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))

        norm = self.param("norm", _ones, (d,))
        in_proj = self.param("in_proj", _dense_init,
                             (d, 2 * inner + 2 * g * n + h))
        conv_w = self.param("conv_w", _dense_init, (c.conv_kernel,
                                                    c.conv_dim))
        conv_b = self.param("conv_b", nn.initializers.zeros, (c.conv_dim,))
        dt_bias = self.param("dt_bias", dt_bias_init, (h,))
        a_log = self.param("A_log", a_log_init, (h,))
        skip = self.param("D", _ones, (h,))
        gnorm = self.param("gnorm", _ones, (inner,))
        out_proj = self.param("out_proj", _dense_init, (inner, d))

        x_in = rms_norm(u, norm, c.norm_eps)
        with jax.named_scope(scopes.MAMBA_IN_PROJ):
            zxbcdt = _dot(x_in, in_proj, self.dtype, out=F32)
        z = zxbcdt[..., :inner].astype(self.dtype)
        xbc = zxbcdt[..., inner:inner + c.conv_dim].astype(self.dtype)
        dt = zxbcdt[..., inner + c.conv_dim:]
        with jax.named_scope(scopes.MAMBA_CONV):
            padded = jnp.pad(xbc, ((0, 0), (c.conv_kernel - 1, 0), (0, 0)))
            acc = conv_b.astype(F32)
            for i in range(c.conv_kernel):
                acc = acc + padded[:, i:i + length].astype(F32) * conv_w[i]
            xbc = jax.nn.silu(acc).astype(self.dtype)
        x = xbc[..., :inner].reshape(b, length, h, p)
        bm = xbc[..., inner:inner + g * n].reshape(b, length, g, n)
        cm = xbc[..., inner + g * n:].reshape(b, length, g, n)
        with jax.named_scope(scopes.MAMBA_SCAN):
            dt = jax.nn.softplus(dt + dt_bias)
            y = ssd_chunked(x, dt, -jnp.exp(a_log), bm, cm, c.chunk_size)
            y = y + skip[:, None] * x.astype(F32)
        y = y.reshape(b, length, inner) * jax.nn.silu(z.astype(F32))
        yg = y.reshape(b, length, g, inner // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                                + c.norm_eps)
        y = (yg.reshape(b, length, inner) * gnorm).astype(self.dtype)
        with jax.named_scope(scopes.MAMBA_OUT_PROJ):
            return u + _dot(y, out_proj, self.dtype, out=u.dtype)


class Attention(nn.Module):
    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        d, qh, kvh, hd = c.hidden_size, c.q_heads, c.kv_heads, c.head_dim
        b, length, _ = u.shape
        norm = self.param("norm", _ones, (d,))
        q_proj = self.param("q_proj", _dense_init, (d, qh * hd))
        k_proj = self.param("k_proj", _dense_init, (d, kvh * hd))
        v_proj = self.param("v_proj", _dense_init, (d, kvh * hd))
        o_proj = self.param("o_proj", _dense_init, (qh * hd, d))

        x = rms_norm(u, norm, c.norm_eps)
        q = _dot(x, q_proj, self.dtype).reshape(b, length, qh, hd)
        k = _dot(x, k_proj, self.dtype).reshape(b, length, kvh, hd)
        v = _dot(x, v_proj, self.dtype).reshape(b, length, kvh, hd)
        q = rope(q, c.rope_theta)
        k = rope(k, c.rope_theta)
        # bfloat16 on a TPU: the Mosaic flash kernels, forward and reverse,
        # and no (length, length) array reaches HBM; else the einsum form
        attend = pallas_attention.flash_causal_attention \
            if danet.auto_wants_flash(self.dtype) else causal_attention
        out = attend(q, k, v).reshape(b, length, qh * hd)
        return u + _dot(out, o_proj, self.dtype, out=u.dtype)


#: ``checkpoint_name``s of what an expert block's reverse pass reads of its
#: forward pass past a product or a selection: the router's logits (before
#: the sigmoid, whose rule reads its own result), its choice and the chosen
#: scores (a gather of 22 in 512 a token costs three times the top-22 on
#: the chip), the two arrays of the dispatch that the routed experts'
#: residuals hold, the latent rows, the routed sum as ``latent_up``'s
#: product reads it, and the shared expert's pre-activation.  A block that
#: keeps them replays elementwise passes only: no product, no top-k, no
#: gather, no sort, no chunk loop
EXPERT_KEPT = (_LOGITS, _IDX, _CHOSEN, _ROWS, _GROUP_SIZES, _LOW, _ROUTED,
               _PRE) = (
    "moe_router_logits", "moe_topk_idx", "moe_chosen_scores",
    "moe_dispatch_rows", "moe_group_sizes", "moe_latent_rows",
    "moe_routed_sum", "moe_shared_pre")


class LatentMoE(nn.Module):
    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        d, lat = c.hidden_size, c.latent_size
        held, off = c.experts_held, c.expert_offset

        def router_init(key, shape, dtype=F32):
            return jax.random.normal(key, shape, dtype) \
                * (1.5 / math.sqrt(shape[0]))

        norm = self.param("norm", _ones, (d,))
        router = self.param("router", router_init, (d, c.experts_total))
        router_bias = self.param("router_bias", nn.initializers.zeros,
                                 (c.experts_total,))
        latent_down = self.param("latent_down", _dense_init, (d, lat))
        latent_up = self.param("latent_up", _dense_init, (lat, d))
        w1 = self.param("w1", _dense_init, (held, lat, c.expert_hidden))
        w2 = self.param("w2", _dense_init, (held, c.expert_hidden, lat))
        shared_up = self.param("shared_up", _dense_init,
                               (d, c.shared_hidden))
        shared_down = self.param("shared_down", _dense_init,
                                 (c.shared_hidden, d))

        x = rms_norm(u, norm, c.norm_eps).reshape(-1, d)
        with jax.named_scope(scopes.MOE_ROUTER):
            scores = jax.nn.sigmoid(checkpoint_name(
                jnp.dot(x.astype(F32), router), _LOGITS))
            idx = checkpoint_name(jax.lax.top_k(
                scores + router_bias, c.experts_per_token)[1], _IDX)
            weights = scores[:, off:off + held]
            if c.norm_topk:
                chosen = checkpoint_name(
                    jnp.take_along_axis(scores, idx, axis=-1), _CHOSEN)
                weights = weights / (chosen.sum(-1)[:, None] + 1e-20)
            weights = weights * c.routed_scale                # (N, held)
        with jax.named_scope(scopes.MOE_LATENT):
            low = checkpoint_name(_dot(x, latent_down, self.dtype), _LOW)
        with jax.named_scope(scopes.MOE_DISPATCH):
            disp = moe_lib.dropless_dispatch(
                idx, expert_offset=off, n_held=held)
            disp = disp._replace(
                rows=checkpoint_name(disp.rows, _ROWS),
                group_sizes=checkpoint_name(disp.group_sizes,
                                            _GROUP_SIZES))
        # names its own parts: dispatch / routed_experts / combine
        routed, chunks_run = moe_lib.dropless_routed(
            low, weights, w1, w2, disp, relu2)
        with jax.named_scope(scopes.MOE_LATENT):
            # in the product's operand dtype: the cast the product makes
            routed = checkpoint_name(routed.astype(self.dtype), _ROUTED)
            routed = _dot(routed, latent_up, self.dtype, out=F32)
        with jax.named_scope(scopes.MOE_SHARED_EXPERT):
            pre = checkpoint_name(_dot(x, shared_up, self.dtype), _PRE)
            shared = _dot(relu2(pre), shared_down, self.dtype, out=F32)
        self.sow(counters.COLLECTION, moe_lib.COUNTER_DROPPED, disp.dropped)
        self.sow(counters.COLLECTION, moe_lib.COUNTER_LOAD,
                 moe_lib.expert_load_max_over_mean(disp))
        self.sow(counters.COLLECTION, moe_lib.COUNTER_CHUNKS, chunks_run)
        return u + (routed + shared).astype(u.dtype).reshape(u.shape)


_KEEP_FLASH_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *pallas_attention.KEPT_BY_REVERSE)
_KEEP_EXPERT_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *EXPERT_KEPT)
#: kind -> (layer scope, block, what a rematerialised block keeps beside its
#: input: an attention block what its flash reverse pass reads of the forward
#: call (where the kernels run; nothing otherwise), an expert block the
#: results of its products and selections)
_BLOCKS = {"M": (scopes.MAMBA, MambaMixer, None),
           "*": (scopes.ATTN, Attention, _KEEP_FLASH_RESIDUALS),
           "E": (scopes.MOE, LatentMoE, _KEEP_EXPERT_RESIDUALS)}


def _run_blocks(module: nn.Module, pattern: str, x, *, remat: bool):
    """The blocks of ``pattern`` as children ``l00``, ``l01``, ... of
    ``module``, each under its layer's scope and, with ``remat``, recomputed
    in the reverse pass from the block's input and what :data:`_BLOCKS`
    keeps for its kind."""
    c, dtype = module.cfg, module.dtype
    for i, kind in enumerate(pattern):
        if kind not in _BLOCKS:
            raise ValueError(f"unknown layer kind {kind!r} in pattern "
                             f"{pattern!r} (M | * | E)")
        layer, cls, kept = _BLOCKS[kind]
        if remat:
            cls = nn.remat(cls, policy=kept)
        with jax.named_scope(layer):
            x = cls(c, dtype, name=layer_name(i))(x)
    return x


class MTPModule(nn.Module):
    """One multi-token-prediction depth: ``h'_t = W_p [RMSNorm(h_t) ;
    RMSNorm(Emb(x_{t+1}))]``, the blocks of ``mtp_pattern``, a final
    RMSNorm.  The caller applies the shared head."""

    cfg: LMConfig
    dtype: Any = F32
    remat: bool = True

    @nn.compact
    def __call__(self, h, next_emb):
        c = self.cfg
        d = c.hidden_size
        hnorm = self.param("hnorm", _ones, (d,))
        enorm = self.param("enorm", _ones, (d,))
        proj = self.param("proj", _dense_init, (2 * d, d))
        joined = jnp.concatenate([rms_norm(h, hnorm, c.norm_eps),
                                  rms_norm(next_emb, enorm, c.norm_eps)], -1)
        x = _dot(joined, proj, self.dtype)
        x = _run_blocks(self, c.mtp_pattern, x, remat=self.remat)
        final_norm = self.param("final_norm", _ones, (d,))
        return rms_norm(x, final_norm, c.norm_eps)


class NemotronH(nn.Module):
    """``tokens (b, l) int32 -> (logits,)`` or, training a model that has
    the prediction module, ``(logits, mtp_logits)``: float32, over the
    vocabulary rows held here.  ``mtp_logits[:, t]`` predicts token
    ``t + 2`` (the last two positions have no target; the loss masks them).
    """

    cfg: LMConfig
    dtype: Any = F32
    remat: bool = True

    @property
    def vocab_size(self) -> int:
        """The ids a token source may draw (the vocabulary rows held)."""
        return self.cfg.vocab_size

    @property
    def loss_weights(self) -> tuple:
        """The weight of each training output's loss, where the config
        gives none: the next-token head's 1, then the prediction module's
        lambda."""
        return (1.0, self.cfg.mtp_loss_weight) if self.cfg.mtp_pattern \
            else (1.0,)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        c = self.cfg
        d, v = c.hidden_size, c.vocab_size
        embed = self.param("embed", nn.initializers.normal(1.0), (v, d))
        with jax.named_scope(scopes.EMBED):
            x = jnp.take(embed, tokens, axis=0).astype(self.dtype)
        h = _run_blocks(self, c.pattern, x, remat=self.remat)
        final_norm = self.param("final_norm", _ones, (d,))
        lm_head = self.param("lm_head", _dense_init, (d, v))

        def head(y):
            with jax.named_scope(scopes.LM_HEAD):
                return _dot(y, lm_head, self.dtype, out=F32)

        logits = head(rms_norm(h, final_norm, c.norm_eps))
        if not c.mtp_pattern or not (train or self.is_initializing()):
            return (logits,)
        with jax.named_scope(scopes.MTP):
            nxt = jnp.take(embed, jnp.roll(tokens, -1, axis=1),
                           axis=0).astype(self.dtype)
        # the module's own name is the ``mtp`` scope of what it runs
        h2 = MTPModule(c, self.dtype, self.remat, name=scopes.MTP)(h, nxt)
        with jax.named_scope(scopes.MTP):
            return logits, head(h2)

    def activation_bytes(self, batch: int, seq_len: int) -> int:
        """A bound on the step's live activations on one device, for the
        planner's memory model (``parallel/plan.py``): every block's input
        and every expert block's :data:`EXPERT_KEPT` (what per-block
        recomputation keeps), the largest single block while it is
        recomputed and differentiated, and the two heads' float32 logits
        with their gradients."""
        c = self.cfg
        t = batch * seq_len
        item = jnp.dtype(self.dtype).itemsize
        blocks = c.pattern + c.mtp_pattern
        kept = (len(blocks) + 2) * t * c.hidden_size * item
        buffer_rows = moe_lib.dropless_buffer_rows(
            t, c.experts_per_token, c.experts_held)
        if not self.remat:
            kept *= 8
        else:  # float32 logits, ids and chosen scores (where the weights
            # are normalised), rows, group sizes; then the latent rows, the
            # routed sum and the shared expert's pre-activation
            kept += blocks.count("E") * (
                4 * (t * (c.experts_total
                          + (1 + c.norm_topk) * c.experts_per_token)
                     + buffer_rows + c.experts_held)
                + item * t * (2 * c.latent_size + c.shared_hidden))
        # an expert layer's wide arrays are one chunk of the row buffer
        rows = moe_lib.chunk_rows_of(buffer_rows)
        if danet.auto_wants_flash(self.dtype):
            # the flash kernels: q, out and their gradients, the reverse
            # pass's float32 dQ and per-query-head dK, dV, then k, v and
            # theirs; no (seq_len, seq_len) array
            attn = t * c.head_dim * (c.q_heads * (4 * item + 3 * 4)
                                     + 4 * c.kv_heads * item)
        else:  # the einsum form: float32 scores, probabilities, gradients
            attn = 3 * batch * c.q_heads * seq_len * seq_len * 4
        per_kind = {
            "*": attn,
            "E": 2 * (rows * (2 * c.latent_size + c.expert_hidden) * item
                      + t * c.shared_hidden * item
                      + t * c.latent_size * 4),
            "M": 4 * t * c.mamba_heads * c.chunk_size * 4
            + 6 * t * (2 * c.mamba_inner + c.conv_dim) * 4,
        }
        live = max(per_kind[k] for k in set(blocks))
        heads = (2 if c.mtp_pattern else 1) * 2 * t * c.vocab_size * 4
        return int(kept + live + heads)


def build_nemotron_h(lm_config: str | dict = "", dtype=F32,
                     remat: bool = True) -> NemotronH:
    return NemotronH(LMConfig.from_dict(load_preset(lm_config, PRESETS)),
                     dtype=dtype, remat=remat)
