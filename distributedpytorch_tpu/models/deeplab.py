"""DeepLabV3 (ASPP) segmentation model (flax.linen, NHWC).

The second model family: the reference driver carries a commented DeepLab
alternative to DANet (reference train_pascal.py:85), and BASELINE.json's
configs name DeepLabV3-ResNet50/101 at output_stride 16 as the
metric-bearing model.  Built natively: atrous spatial pyramid pooling over the
dilated-ResNet stage-4 features, image-level pooling branch, optional FCN
auxiliary head on stage-3 (standard DeepLabV3 training recipe).

Output contract mirrors the framework-wide convention: a tuple of
input-resolution logit maps, primary first — so the same multi-output loss
(``ops.multi_output_loss`` / the reference's ``SegmentationMultiLosses``
semantics) and trainer drive either model family unchanged.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from .resnet import ResNet, make_norm


def _resize_bilinear(x: jax.Array, size: tuple[int, int]) -> jax.Array:
    b, _, _, c = x.shape
    return jax.image.resize(x, (b, *size, c), method="bilinear").astype(x.dtype)


class ASPP(nn.Module):
    """Atrous spatial pyramid pooling: parallel 1x1 + three dilated 3x3
    branches + global-pool branch, concatenated and projected."""

    channels: int
    rates: Sequence[int]
    norm: Any
    dtype: jnp.dtype = jnp.float32
    dropout_rate: float = 0.5

    @nn.compact
    def __call__(self, x, train: bool = False):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)

        def branch(y, kernel, rate, name):
            y = conv(self.channels, kernel,
                     kernel_dilation=(rate, rate), padding="SAME",
                     name=f"{name}_conv")(y)
            y = self.norm(name=f"{name}_bn")(y)
            return nn.relu(y)

        outs = [branch(x, (1, 1), 1, "b0")]
        for i, r in enumerate(self.rates):
            outs.append(branch(x, (3, 3), r, f"b{i + 1}"))

        # Image-level pooling branch: global mean -> 1x1 -> broadcast back.
        pooled = x.mean(axis=(1, 2), keepdims=True)
        pooled = branch(pooled, (1, 1), 1, "pool")
        outs.append(jnp.broadcast_to(pooled, x.shape[:3] + (self.channels,)))

        y = jnp.concatenate(outs, axis=-1)
        y = branch(y, (1, 1), 1, "project")
        return nn.Dropout(self.dropout_rate, deterministic=not train)(y)


class FCNHead(nn.Module):
    """3x3 conv-bn-relu + dropout + 1x1 classifier (auxiliary supervision)."""

    nclass: int
    norm: Any
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        inter = max(x.shape[-1] // 4, 1)
        y = nn.Conv(inter, (3, 3), use_bias=False, padding="SAME",
                    dtype=self.dtype)(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = nn.Dropout(0.1, deterministic=not train)(y)
        return nn.Conv(self.nclass, (1, 1), dtype=self.dtype)(y)


class DecoderV3Plus(nn.Module):
    """DeepLabV3+ decoder: ASPP features upsampled to stride 4 and fused
    with 1x1-projected low-level (c1) features, refined by two 3x3 convs.

    Recovers the object-boundary detail the os=16 encoder path loses —
    the standard accuracy upgrade over plain V3 at the same encoder cost."""

    channels: int
    low_channels: int
    norm: Any
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, y, low, train: bool = False):
        conv = partial(nn.Conv, use_bias=False, padding="SAME",
                       dtype=self.dtype)
        low = conv(self.low_channels, (1, 1), name="low_proj")(low)
        low = self.norm(name="low_bn")(low)
        low = nn.relu(low)
        y = _resize_bilinear(y, low.shape[1:3])
        y = jnp.concatenate([y, low], axis=-1)
        for i in range(2):
            y = conv(self.channels, (3, 3), name=f"refine{i}_conv")(y)
            y = self.norm(name=f"refine{i}_bn")(y)
            y = nn.relu(y)
        return y


class FCN(nn.Module):
    """Fully-convolutional network (Long et al., CVPR'15, the torchvision
    ``fcn_resnet50/101`` structure): dilated ResNet + FCNHead on c4,
    bilinear upsample to input resolution.  ``__call__(x, train)`` ->
    (logits,) or (logits, aux_logits).

    The smallest member of the model zoo — same backbone (so torchvision's
    ImageNet checkpoints warm-start it via ``checkpoint.warm_start``), no
    ASPP/attention context module; the accuracy-per-FLOP baseline the
    fancier heads are judged against."""

    nclass: int = 21
    backbone_depth: int = 50
    output_stride: int = 8     # torchvision dilates stages 3+4
    aux_head: bool = False
    dtype: jnp.dtype = jnp.float32
    bn_cross_replica_axis: str | None = None
    bn_fp32_stats: bool = True  # False: BN stats in compute dtype (see make_norm)
    remat: bool = False
    remat_policy: str | None = None  # jax.checkpoint_policies name (see ResNet)

    @nn.compact
    def __call__(self, x, train: bool = False):
        size = x.shape[1:3]
        feats = ResNet(
            depth=self.backbone_depth,
            output_stride=self.output_stride,
            dtype=self.dtype,
            bn_cross_replica_axis=self.bn_cross_replica_axis,
            bn_fp32_stats=self.bn_fp32_stats,
            remat=self.remat,
            remat_policy=self.remat_policy,
            name="backbone",
        )(x, train=train)
        norm = make_norm(train, self.dtype, self.bn_cross_replica_axis,
                 fp32_stats=self.bn_fp32_stats)
        y = FCNHead(nclass=self.nclass, norm=norm, dtype=self.dtype,
                    name="head")(feats["c4"], train=train)
        outs = [_resize_bilinear(y, size)]
        if self.aux_head:
            aux = FCNHead(nclass=self.nclass, norm=norm, dtype=self.dtype,
                          name="aux")(feats["c3"], train=train)
            outs.append(_resize_bilinear(aux, size))
        return tuple(outs)


class DeepLabV3(nn.Module):
    """Dilated ResNet + ASPP; ``__call__(x, train)`` -> (logits,) or
    (logits, aux_logits) at input resolution."""

    nclass: int = 21
    backbone_depth: int = 50
    output_stride: int = 16
    aspp_channels: int = 256
    aux_head: bool = False
    decoder: bool = False     # True = DeepLabV3+ (low-level c1 skip fusion)
    dtype: jnp.dtype = jnp.float32
    bn_cross_replica_axis: str | None = None
    bn_fp32_stats: bool = True  # False: BN stats in compute dtype (see make_norm)
    remat: bool = False
    remat_policy: str | None = None  # jax.checkpoint_policies name (see ResNet)

    @nn.compact
    def __call__(self, x, train: bool = False):
        size = x.shape[1:3]
        # ASPP rates scale with dilation: (6,12,18) at os=16, doubled at os=8.
        rates = (6, 12, 18) if self.output_stride == 16 else (12, 24, 36)
        feats = ResNet(
            depth=self.backbone_depth,
            output_stride=self.output_stride,
            multi_grid=(1, 2, 4),
            dtype=self.dtype,
            bn_cross_replica_axis=self.bn_cross_replica_axis,
            bn_fp32_stats=self.bn_fp32_stats,
            remat=self.remat,
            remat_policy=self.remat_policy,
            name="backbone",
        )(x, train=train)
        norm = make_norm(train, self.dtype, self.bn_cross_replica_axis,
                 fp32_stats=self.bn_fp32_stats)
        y = ASPP(channels=self.aspp_channels, rates=rates, norm=norm,
                 dtype=self.dtype, name="aspp")(feats["c4"], train=train)
        if self.decoder:
            y = DecoderV3Plus(channels=self.aspp_channels, low_channels=48,
                              norm=norm, dtype=self.dtype,
                              name="decoder")(y, feats["c1"], train=train)
        y = nn.Conv(self.nclass, (1, 1), dtype=self.dtype, name="classifier")(y)
        outs = [_resize_bilinear(y, size)]
        if self.aux_head:
            aux = FCNHead(nclass=self.nclass, norm=norm, dtype=self.dtype,
                          name="aux")(feats["c3"], train=train)
            outs.append(_resize_bilinear(aux, size))
        return tuple(outs)
