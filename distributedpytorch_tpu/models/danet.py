"""DANet — dual attention network segmentation head (flax.linen, NHWC).

The reference's flagship model: ``DANet(1, 'resnet101')`` from PyTorch-Encoding
(reference train_pascal.py:32,86), a dilated ResNet backbone with two parallel
attention branches over the stage-4 features — position attention (full
self-attention over spatial tokens) and channel attention (gram-matrix over
channels) — whose fused sum plus the two branch predictions form a 3-tuple
output, all three supervised by the weighted multi-loss
(train_pascal.py:119,199) and the branch maps visualized as eval panels
(train_pascal.py:258-275).

TPU-first choices:
* the attention math is the batched-einsum primitives in ``ops.attention``
  (MXU-friendly; optionally the blocked online-softmax form so the token-pair
  score matrix never hits HBM at large crops);
* heads predict at output_stride resolution; logits are bilinearly resized to
  input size *inside* the model (jax.image.resize — static shapes, XLA-fused),
  so the loss/metric see input-resolution maps exactly like the reference's
  upsampled outputs;
* with ``nclass=1`` the output is a single-logit sigmoid head — the
  reference's binary interactive-segmentation configuration (evidence: the
  manual sigmoid at train_pascal.py:262,284).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.attention import (
    blocked_position_attention,
    channel_attention,
    position_attention,
)
from .resnet import ResNet, make_norm


#: 'auto' switch point for the position branch outside the bf16-TPU hot
#: path (scripts/pam_crossover.py on the v5e, 2026-07-30): the
#: f32 sweep measured XLA's fused einsum FASTER at every compilable token
#: count (32k: 147 ms vs flash's 185 ms fwd+bwd), so for f32 compute —
#: and on CPU meshes, which run pallas through the slow interpreter —
#: 'auto' keeps einsum while the N^2 scores fit HBM and switches to
#: flash only for memory feasibility: at 64k tokens the N^2 f32 score
#: matrix alone is ~17 GB > v5e HBM.  Under BF16 COMPUTE ON TPU 'auto'
#: is simply flash: the fused VMEM schedule is the default hot path of
#: the mixed-precision regime (model.attention_impl + train.precision,
#: ROADMAP item 4 — the default flip is the bf16-era call; the f32
#: verdict stands).
AUTO_FLASH_MIN_TOKENS = 65536


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def auto_wants_flash(dtype) -> bool:
    """'auto' promotes the fused Pallas kernels only on TPU and only for
    bf16 compute — see :data:`AUTO_FLASH_MIN_TOKENS`: the f32 crossover
    sweep still favors XLA's einsum, so an f32 run (reference parity,
    ``train.precision=float32``) keeps the measured-faster form.  The one
    rule for every model's attention kernels (a token model's causal
    attention asks it too: ``models/nemotron_h.py``)."""
    return _on_tpu() and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)


def _resize_bilinear(x: jax.Array, size: tuple[int, int]) -> jax.Array:
    """Bilinear NHWC resize to (H, W) — static-shape, differentiable."""
    b, _, _, c = x.shape
    return jax.image.resize(x, (b, *size, c), method="bilinear").astype(x.dtype)


class PositionAttentionModule(nn.Module):
    """Spatial self-attention with a learned zero-init residual gate."""

    channels: int
    norm: Any
    dtype: jnp.dtype = jnp.float32
    block_size: int | None = None  # None -> full attention
    impl: str = "einsum"           # auto | einsum | flash | ring
    sp_mesh: Any = None            # ring: mesh to shard the token axis over
    sp_axis: str = "model"         # ring: mesh axis carrying the tokens
    score_dtype: Any = None        # einsum: dtype the N x N scores are
                                   # materialized in (bf16 halves the HBM
                                   # round trip; softmax math stays f32).
                                   # flash/ring/blocked never materialize
                                   # the N x N matrix — no-op there.

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        conv = partial(nn.Conv, use_bias=True, dtype=self.dtype)
        q = conv(self.channels // 8, (1, 1), name="query")(x).reshape(b, h * w, -1)
        k = conv(self.channels // 8, (1, 1), name="key")(x).reshape(b, h * w, -1)
        v = conv(self.channels, (1, 1), name="value")(x).reshape(b, h * w, -1)
        impl = self.impl
        if impl == "auto":
            # bf16 compute on TPU: the fused Pallas kernel IS the hot
            # path.  Otherwise (f32 — where einsum measured faster at
            # every compilable count — or CPU meshes, which run pallas
            # through the interpreter): einsum while the N^2 scores fit
            # HBM, flash beyond (where einsum cannot run at all) — see
            # AUTO_FLASH_MIN_TOKENS.  Backend, dtype and token count
            # are static at trace time: a compile-time choice, one
            # program per shape.
            if auto_wants_flash(self.dtype):
                impl = "flash"
            else:
                impl = "einsum" if h * w < AUTO_FLASH_MIN_TOKENS \
                    else "flash"
        if impl == "flash":
            from ..ops.pallas_attention import flash_position_attention
            blk = self.block_size or 256
            out = flash_position_attention(q, k, v, blk, blk)
        elif impl == "ring":
            # Sequence parallelism live in the model: the spatial-token axis
            # is sharded over ``sp_axis`` and attention runs as a ppermute
            # ring (parallel/ring.py) — each device holds N/axis tokens and
            # no full N x N score matrix exists on any chip.  Requires
            # h*w % axis_size == 0 (and batch % data-axis == 0 when the
            # mesh also has a data axis).
            if self.sp_mesh is None:
                raise ValueError("impl='ring' needs sp_mesh (the mesh whose "
                                 f"'{self.sp_axis}' axis shards the tokens)")
            from ..parallel.mesh import DATA_AXIS
            from ..parallel.ring import make_ring_attention_inline

            sizes = dict(zip(self.sp_mesh.axis_names,
                             self.sp_mesh.devices.shape))
            if (h * w) % sizes[self.sp_axis]:
                raise ValueError(
                    f"impl='ring' needs the token count ({h}*{w}={h * w}) "
                    f"divisible by the '{self.sp_axis}' axis size "
                    f"({sizes[self.sp_axis]})")
            # Shard the batch over the data axis only when it divides (the
            # init dummy batch is 1 and must stay replicated).
            batch_ax = (DATA_AXIS if sizes.get(DATA_AXIS, 1) > 1
                        and b % sizes[DATA_AXIS] == 0 else None)
            ring = make_ring_attention_inline(
                self.sp_mesh, self.sp_axis, batch_axis=batch_ax)
            out = ring(q, k, v)
        elif impl == "einsum":
            if self.block_size is None:
                out = position_attention(q, k, v,
                                         score_dtype=self.score_dtype)
            else:
                out = blocked_position_attention(q, k, v, self.block_size)
        else:
            raise ValueError(
                f"unknown attention impl: {self.impl!r} "
                "(auto | einsum | flash | ring)")
        out = out.reshape(b, h, w, self.channels)
        # Residual gate starts at 0: the module is an identity at init and
        # learns how much attention context to blend in.
        gamma = self.param("gamma", nn.initializers.zeros, (), jnp.float32)
        return gamma.astype(x.dtype) * out + x


class ChannelAttentionModule(nn.Module):
    """Channel gram-matrix attention with a learned zero-init residual gate.

    ``impl``: ``einsum`` (XLA, reference parity) | ``flash`` (the fused
    Pallas gram+softmax kernel, ops.pallas_attention) | ``auto`` (flash
    for bf16 compute on TPU — the mixed-precision hot path — einsum
    elsewhere, including f32 TPU runs, matching the position branch's
    measured crossover verdict).  Parameter-free either way, so the
    impl choice never touches checkpoints.
    """

    dtype: jnp.dtype = jnp.float32
    impl: str = "einsum"           # auto | einsum | flash
    block_size: int | None = None  # flash: token-block rows per VMEM tile

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        impl = self.impl
        if impl == "auto":
            impl = "flash" if auto_wants_flash(self.dtype) else "einsum"
        tokens = x.reshape(b, h * w, c)
        if impl == "flash":
            from ..ops.pallas_attention import flash_channel_attention
            out = flash_channel_attention(tokens, self.block_size or 256)
        elif impl == "einsum":
            out = channel_attention(tokens)
        else:
            raise ValueError(f"unknown channel-attention impl: "
                             f"{self.impl!r} (auto | einsum | flash)")
        out = out.reshape(b, h, w, c)
        gamma = self.param("gamma", nn.initializers.zeros, (), jnp.float32)
        return gamma.astype(x.dtype) * out + x


class DANetHead(nn.Module):
    """Dual-attention head: conv-in -> {PAM, CAM} -> conv-out -> 3 classifiers.

    Returns ``(fused_logits, pam_logits, cam_logits)`` at feature resolution.
    """

    nclass: int
    norm: Any
    dtype: jnp.dtype = jnp.float32
    pam_block_size: int | None = None
    pam_impl: str = "einsum"
    pam_sp_mesh: Any = None
    pam_sp_axis: str = "model"
    pam_score_dtype: Any = None
    cam_impl: str = "einsum"
    dropout_rate: float = 0.1
    moe_experts: int = 0        # >0: MoE FFN on the fused features
    moe_hidden: int | None = None
    moe_k: int = 1
    moe_capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x, train: bool = False):
        inter = max(x.shape[-1] // 4, 1)  # 2048 -> 512
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)

        def conv_bn_relu(y, name):
            y = conv(inter, (3, 3), padding="SAME", name=f"{name}_conv")(y)
            y = self.norm(name=f"{name}_bn")(y)
            return nn.relu(y)

        def classifier(y, name):
            y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
            return nn.Conv(self.nclass, (1, 1), dtype=self.dtype,
                           name=f"{name}_cls")(y)

        pa = conv_bn_relu(x, "pam_in")
        pa = PositionAttentionModule(
            channels=inter, norm=self.norm, dtype=self.dtype,
            block_size=self.pam_block_size, impl=self.pam_impl,
            sp_mesh=self.pam_sp_mesh, sp_axis=self.pam_sp_axis,
            score_dtype=self.pam_score_dtype,
            name="pam")(pa)
        pa = conv_bn_relu(pa, "pam_out")

        ca = conv_bn_relu(x, "cam_in")
        ca = ChannelAttentionModule(dtype=self.dtype, impl=self.cam_impl,
                                    name="cam")(ca)
        ca = conv_bn_relu(ca, "cam_out")

        fused = pa + ca
        if self.moe_experts > 0:
            # Sparse capacity on the fused features: each spatial token is
            # routed to 1/E of the FFN params.  Under the trainer's
            # `mesh.shard_params=true`, tp_param_specs shards these expert
            # stacks one-group-per-device over the model axis (expert
            # parallelism in the flagship step); otherwise they replicate
            # like any other params.  The standalone EP path is
            # `make_moe_apply`/`make_expert_mesh` in parallel/moe.py.
            # MoEMlp keeps the residual, so dropped tokens pass through,
            # and sows the load-balancing aux loss for the train step.
            from ..parallel.moe import MoEMlp

            b, h, w, c = fused.shape
            tokens = fused.astype(jnp.float32).reshape(b, h * w, c)
            tokens = MoEMlp(
                n_experts=self.moe_experts,
                hidden=self.moe_hidden or c,
                k=self.moe_k,
                capacity_factor=self.moe_capacity_factor,
                name="moe")(tokens)
            fused = tokens.reshape(b, h, w, c).astype(fused.dtype)
        return (
            classifier(fused, "fused"),
            classifier(pa, "pam"),
            classifier(ca, "cam"),
        )


class DANet(nn.Module):
    """Backbone + dual-attention head; ``__call__(x, train)`` -> 3-tuple of
    input-resolution logit maps, matching the reference model's output
    contract (tuple indexing at reference train_pascal.py:258-260).

    ``guidance_inject`` picks where the click-guidance channel (the LAST
    input channel, reference custom_transforms.py ConcatInputs) enters:

    * ``'stem'`` (default, reference parity): the backbone consumes the
      full RGB+guidance concat — every click pays the whole forward.
    * ``'head'``: the backbone consumes only the RGB channels and the
      guidance channel joins at the head via a zero-init 1x1 projection
      added to the c4 features — making the backbone encoding a pure
      function of the image.  This is the session-serving architecture:
      ``stage='encode'`` (image -> c4 features, ~90% of the FLOPs) is
      computed once per interactive session, ``stage='decode'``
      (features + guidance -> logits) once per refinement click
      (serve/sessions.py).  Zero-init keeps the module's residual-gate
      idiom: at init the guidance is a no-op and training learns how
      much to blend in.

    Staged calls (``guidance_inject='head'`` only; ``stage`` is a static
    Python string, so each stage traces its own program):

    * ``stage='encode'``: ``x`` is the RGB crop (B, H, W, C-1); returns
      the c4 feature map (B, H/os, W/os, C_feat).
    * ``stage='decode'``: ``x`` is ``(features, guidance)`` with
      guidance (B, H, W, 1) in crop space; ``out_size`` (static) is the
      logit-map resolution (the full path's input size).
    """

    nclass: int = 1
    backbone_depth: int = 101
    output_stride: int = 8
    dtype: jnp.dtype = jnp.float32
    bn_cross_replica_axis: str | None = None
    bn_fp32_stats: bool = True  # False: BN stats in compute dtype (see make_norm)
    pam_block_size: int | None = None
    pam_impl: str = "einsum"  # auto | einsum | flash | ring (seq-parallel)
    pam_sp_mesh: Any = None   # ring: mesh whose axis shards the tokens
    pam_sp_axis: str = "model"
    pam_score_dtype: Any = None  # einsum: N x N score materialization dtype
    cam_impl: str = "einsum"  # auto | einsum | flash (fused Pallas gram)
    remat: bool = False
    remat_policy: str | None = None  # jax.checkpoint_policies name (see ResNet)
    moe_experts: int = 0      # >0: MoE FFN in the head (see DANetHead)
    moe_hidden: int | None = None
    moe_k: int = 1
    moe_capacity_factor: float = 1.25
    guidance_inject: str = "stem"  # stem | head (encode/decode split)

    def _encode(self, x, train: bool):
        """Backbone features — the session-invariant stage."""
        return ResNet(
            depth=self.backbone_depth,
            output_stride=self.output_stride,
            dtype=self.dtype,
            bn_cross_replica_axis=self.bn_cross_replica_axis,
            bn_fp32_stats=self.bn_fp32_stats,
            remat=self.remat,
            remat_policy=self.remat_policy,
            name="backbone",
        )(x, train=train)["c4"]

    def _decode(self, feats, guidance, out_size: tuple[int, int],
                train: bool):
        """Head on (optionally guidance-conditioned) c4 features."""
        if guidance is not None:
            g = _resize_bilinear(guidance.astype(self.dtype),
                                 feats.shape[1:3])
            feats = feats + nn.Conv(
                feats.shape[-1], (1, 1), use_bias=False, dtype=self.dtype,
                kernel_init=nn.initializers.zeros,
                name="guidance_proj")(g)
        norm = make_norm(train, self.dtype, self.bn_cross_replica_axis,
                         fp32_stats=self.bn_fp32_stats)
        outs = DANetHead(
            nclass=self.nclass,
            norm=norm,
            dtype=self.dtype,
            pam_block_size=self.pam_block_size,
            pam_impl=self.pam_impl,
            pam_sp_mesh=self.pam_sp_mesh,
            pam_sp_axis=self.pam_sp_axis,
            pam_score_dtype=self.pam_score_dtype,
            cam_impl=self.cam_impl,
            moe_experts=self.moe_experts,
            moe_hidden=self.moe_hidden,
            moe_k=self.moe_k,
            moe_capacity_factor=self.moe_capacity_factor,
            name="head",
        )(feats, train=train)
        return tuple(_resize_bilinear(o, out_size) for o in outs)

    @nn.compact
    def __call__(self, x, train: bool = False, stage: str = "full",
                 out_size: tuple[int, int] | None = None):
        if self.guidance_inject not in ("stem", "head"):
            raise ValueError(f"unknown guidance_inject: "
                             f"{self.guidance_inject!r} (stem | head)")
        if stage == "full":
            size = out_size or x.shape[1:3]
            if self.guidance_inject == "stem":
                return self._decode(self._encode(x, train), None, size,
                                    train)
            # head injection: backbone sees RGB only; the guidance (last)
            # channel re-enters at the head — x stays the SAME concat the
            # stem path consumes, so the loss/eval/serve wire is unchanged
            return self._decode(self._encode(x[..., :-1], train),
                                x[..., -1:], size, train)
        if self.guidance_inject != "head":
            raise ValueError(
                f"stage={stage!r} needs guidance_inject='head' — the stem "
                "architecture folds the guidance into the backbone, so "
                "its encoding cannot be reused across clicks")
        if stage == "encode":
            return self._encode(x, train)
        if stage == "decode":
            if out_size is None:
                raise ValueError("stage='decode' needs out_size (the "
                                 "logit-map resolution)")
            feats, guidance = x
            return self._decode(feats, guidance, tuple(out_size), train)
        raise ValueError(f"unknown stage: {stage!r} "
                         "(full | encode | decode)")
