"""``sdar_lm``: a token model trained by a **block-diffusion objective**
(the SDAR family, ``model_type`` ``sdar_moe``: 48 identical layers of
grouped-query attention and a softmax-routed gated expert layer in the
published 30B-A3B; an autoregressive checkpoint adapted to generate a block
of tokens at a time by iterative unmasking).

What differs from a next-token model is the objective's shape, not a width:

* the **input is twice the batch's sequence**: ids ``[x0 ‖ xt]`` — the clean
  sequence of ``L`` tokens and its noised copy (``ops/diffusion.py``: data of
  the task's device stage; this module draws nothing) — through one
  embedding table, with **positions that repeat**, ``[0..L−1 ‖ 0..L−1]``;
* attention is dense under a **block-structured mask that is not causal**
  (``ops/attention.py::block_diffusion_mask``): the clean copy is
  block-causal, a noised block sees the clean blocks before its own and
  itself in both directions;
* the **head runs over half of the positions**: ``logits = Head(RMSNorm(
  h[L:]))``, the noised copy's; the clean half's logits are never formed.
  Position ``i`` of the noised copy predicts token ``i`` (no shift), and the
  loss weighs it by what the noise gave it
  (``ops/losses.py::weighted_token_xent``).

Every layer is two blocks, each ``x <- x + f(RMSNorm(x))``:
:class:`BlockDiffusionAttention` — 32 query / 4 key-value heads of 128 in
the published widths, no bias, RMSNorm over each head of q and k, rotary over
the whole head by the repeated positions, scores ``q·k/√head_dim`` under the
rule, softmax in float32 — and ``models/keye_lm.py::GatedMoE``, the softmax
router over ALL published experts, top-k renormalised, gated experts, no
shared expert, the held experts' part through ``parallel/moe.py``'s dropless
layer.  Then a final RMSNorm and the untied head.

In bfloat16 on a TPU (``models/danet.py::auto_wants_flash``) attention runs
as the Mosaic flash kernels under the rule
``ops/pallas_attention.py::BlockDiffusion`` (``blockdiff_attn``,
``blockdiff_attn_bwd_…``): no (2L, 2L) array reaches HBM, and a
rematerialised block keeps the forward call's output and log-sum-exp, so its
reverse pass runs no second forward call.  Otherwise the einsum form runs.

The counts of experts and vocabulary rows in the configuration are what this
chip HOLDS of a stated deployment; widths are never cut.  The last held
vocabulary row stands for ``<|MASK|>``.  Scopes as ``keye_lm``: blocks
``l00, l01, …`` alternate ``attn`` / ``moe``; inside ``moe/l<k>/``:
``router``, ``dispatch``, ``routed_experts``, ``combine``; ``embed``,
``lm_head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import attention as attention_ops
from ..ops import diffusion, pallas_attention
from ..parallel import moe as moe_lib
from ..telemetry import scopes
from . import danet
from .keye_lm import (KEEP_EXPERT_RESIDUALS, GatedMoE, expert_chunk_rows,
                      expert_kept_bytes, rotary_angles, rotate)
from .nemotron_h import (_dense_init, _dot, _ones, layer_name, load_preset,
                         rms_norm)

F32 = jnp.float32

#: the loss type of ``parallel/step.py::LOSSES`` this model trains under
LOSS_TYPE = "block_diffusion"

#: a preset small enough for the CPU tests and the README's command line
#: (experts not held, several blocks a sequence); the benchmark's
#: configuration file has the published widths
PRESETS: dict[str, dict] = {
    "tiny": {
        "hidden_size": 64, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "rope_theta": 10000,
        "num_experts": 4, "published": {"num_experts": 8},
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "norm_topk_prob": True, "block_length": 4,
    },
}


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The sizes the layers use (hashable: a Flax module field); the expert
    layer's names are ``keye_lm.LMConfig``'s, whose ``GatedMoE`` reads
    them."""

    hidden_size: int
    vocab_size: int
    layers: int
    norm_eps: float
    q_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    block_length: int       # tokens unmasked together: the mask's block
    experts_total: int      # the router's width: every published expert
    experts_held: int       # experts whose weights live here
    expert_offset: int      # ... numbered from this one on
    experts_per_token: int
    expert_hidden: int
    norm_topk: bool
    #: rows of one chunk of the expert layer's row buffer, where the
    #: deployment states them; None: ``keye_lm.expert_chunk_rows``
    expert_chunk_rows: int | None = None

    @classmethod
    def from_dict(cls, c: dict) -> "LMConfig":
        if c.get("rope_scaling"):
            raise ValueError("sdar_lm turns its rotary pairs by the plain "
                             "position: rope_scaling must be null")
        held = c["num_experts"]
        return cls(
            hidden_size=c["hidden_size"], vocab_size=c["vocab_size"],
            layers=c["num_hidden_layers"], norm_eps=c["rms_norm_eps"],
            q_heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            rope_theta=float(c["rope_theta"]),
            block_length=int(c["block_length"]),
            experts_total=c.get("published", {}).get("num_experts", held),
            experts_held=held, expert_offset=c.get("expert_offset", 0),
            experts_per_token=c["num_experts_per_tok"],
            expert_hidden=c["moe_intermediate_size"],
            norm_topk=bool(c.get("norm_topk_prob", True)),
            expert_chunk_rows=c.get("expert_chunk_rows"))


class BlockDiffusionAttention(nn.Module):
    """``u`` (B, 2L, d), the clean copy's states then the noised copy's."""

    cfg: LMConfig
    dtype: Any = F32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        d, qh, kvh, hd = c.hidden_size, c.q_heads, c.kv_heads, c.head_dim
        b, doubled, _ = u.shape
        length = doubled // 2
        norm = self.param("norm", _ones, (d,))
        q_proj = self.param("q_proj", _dense_init, (d, qh * hd))
        k_proj = self.param("k_proj", _dense_init, (d, kvh * hd))
        v_proj = self.param("v_proj", _dense_init, (d, kvh * hd))
        o_proj = self.param("o_proj", _dense_init, (qh * hd, d))
        q_norm = self.param("q_norm", _ones, (hd,))
        k_norm = self.param("k_norm", _ones, (hd,))

        x = rms_norm(u, norm, c.norm_eps)
        q = _dot(x, q_proj, self.dtype).reshape(b, doubled, qh, hd)
        k = _dot(x, k_proj, self.dtype).reshape(b, doubled, kvh, hd)
        v = _dot(x, v_proj, self.dtype).reshape(b, doubled, kvh, hd)
        # token i of either copy stands at position i
        positions = jnp.broadcast_to(
            jnp.tile(jnp.arange(length, dtype=jnp.int32), 2),
            (1, b, doubled))
        ang = rotary_angles(positions, hd // 2, c.rope_theta)
        q = rotate(rms_norm(q, q_norm, c.norm_eps), ang)
        k = rotate(rms_norm(k, k_norm, c.norm_eps), ang)
        if danet.auto_wants_flash(self.dtype):
            out = pallas_attention.flash_block_diffusion_attention(
                q, k, v, length, c.block_length)
        else:
            out = attention_ops.block_diffusion_attention(
                q, k, v, length, c.block_length)
        out = out.reshape(b, doubled, qh * hd)
        return u + _dot(out, o_proj, self.dtype, out=u.dtype)


_KEEP_FLASH_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *pallas_attention.BlockDiffusion.kept)


class SdarLM(nn.Module):
    """``(tokens, noised) (b, l) int32 -> (logits,)``: float32 (b, l,
    vocabulary rows held), over the noised copy's positions.  Without
    ``noised`` the clean sequence stands for it (``init``: shapes alone)."""

    cfg: LMConfig
    dtype: Any = F32
    remat: bool = True

    @property
    def vocab_size(self) -> int:
        """The ids a token source may draw: the vocabulary rows held less
        the last, which stands for the mask."""
        return self.cfg.vocab_size - 1

    @property
    def mask_id(self) -> int:
        return self.cfg.vocab_size - 1

    @property
    def loss_weights(self) -> tuple:
        return (1.0,)

    @property
    def loss_type(self) -> str:
        return LOSS_TYPE

    @property
    def device_stage(self):
        """The token task's device stage for this model: the noise."""
        return diffusion.noise_stage(self.cfg.block_length, self.mask_id)

    @nn.compact
    def __call__(self, tokens, noised=None, train: bool = False):
        c = self.cfg
        if noised is None:  # ``init`` and shape-only callers: nothing masked
            noised = tokens
        d, v = c.hidden_size, c.vocab_size
        length = tokens.shape[1]
        if length % c.block_length:
            raise ValueError(f"blocks of {c.block_length} do not tile a "
                             f"sequence of {length}")
        embed = self.param("embed", nn.initializers.normal(1.0), (v, d))
        with jax.named_scope(scopes.EMBED):
            ids = jnp.concatenate([tokens, noised], axis=1)
            x = jnp.take(embed, ids, axis=0).astype(self.dtype)
        attn, moe = BlockDiffusionAttention, GatedMoE
        if self.remat:
            # per block; an attention block keeps what its reverse pass
            # reads of the forward call
            attn = nn.remat(attn, policy=_KEEP_FLASH_RESIDUALS)
            moe = nn.remat(moe, policy=KEEP_EXPERT_RESIDUALS)
        for i in range(c.layers):
            with jax.named_scope(scopes.ATTN):
                x = attn(c, self.dtype, name=layer_name(2 * i))(x)
            with jax.named_scope(scopes.MOE):
                x = moe(c, self.dtype, name=layer_name(2 * i + 1))(x)
        final_norm = self.param("final_norm", _ones, (d,))
        lm_head = self.param("lm_head", _dense_init, (d, v))
        with jax.named_scope(scopes.LM_HEAD):
            return (_dot(rms_norm(x[:, length:], final_norm, c.norm_eps),
                         lm_head, self.dtype, out=F32),)

    def activation_bytes(self, batch: int, seq_len: int) -> int:
        """A bound on the step's live activations on one device, for the
        planner's memory model (``parallel/plan.py``): what per-block
        recomputation keeps over the 2·``seq_len`` positions (every block's
        input; an attention block's output and log-sum-exp; an expert
        block's ``keye_lm.EXPERT_KEPT``), the largest single block while it
        is recomputed and differentiated, and the head's float32 logits over
        ``seq_len`` with their gradient."""
        c = self.cfg
        t = 2 * batch * seq_len
        item = jnp.dtype(self.dtype).itemsize
        kept = (2 * c.layers + 2) * t * c.hidden_size * item
        if not self.remat:
            kept *= 8
        else:
            kept += c.layers * expert_kept_bytes(c, t)
        if danet.auto_wants_flash(self.dtype):
            kept += c.layers * t * c.q_heads * (c.head_dim * item + 4)
            attn = t * c.head_dim * (c.q_heads * (4 * item + 3 * 4)
                                     + 4 * c.kv_heads * item)
        else:  # the einsum form: every head's scores, whole
            attn = 3 * 4 * c.q_heads * batch * (2 * seq_len) ** 2
        rows = moe_lib.chunk_rows_of(
            moe_lib.dropless_buffer_rows(t, c.experts_per_token,
                                         c.experts_held),
            c.expert_chunk_rows
            or expert_chunk_rows(t * c.experts_per_token * c.experts_held
                                 / c.experts_total))
        experts = 2 * rows * (c.hidden_size + 3 * c.expert_hidden) * item
        return int(kept + max(attn, experts)
                   + 2 * batch * seq_len * c.vocab_size * 4)


def build_sdar_lm(lm_config: str | dict = "", dtype=F32,
                  remat: bool = True) -> SdarLM:
    return SdarLM(LMConfig.from_dict(load_preset(lm_config, PRESETS)),
                  dtype=dtype, remat=remat)
