"""ResNet backbones (flax.linen, NHWC) with dilated output-stride control.

TPU-native re-design of the backbone family the reference consumes externally:
``DANet(1, 'resnet101')`` pulls a dilated ResNet-101 from PyTorch-Encoding
(reference train_pascal.py:32,86), modified to a 4-channel stem for the
RGB+guidance input (train_pascal.py:65,133).  Here the stem width is just a
constructor argument, and the dilation schedule is expressed as an
``output_stride`` in {8, 16, 32}: strides that would shrink the feature map
below input/output_stride become dilations instead — the standard dilated-FCN
trick DANet (os=8) and DeepLabV3 (os=16) rely on.

TPU notes:
* NHWC everywhere; convs are ``nn.Conv`` (lax.conv_general_dilated -> MXU).
* BatchNorm is per-replica by default, matching the reference's
  ``sync_bn=False`` (train_pascal.py:85); pass ``bn_cross_replica_axis`` to
  sync batch statistics over a mesh axis instead (``axis_name`` is resolved
  inside pjit/shard_map).
* ``dtype`` is the compute/activation dtype (bf16 for the mixed-precision
  configs); params stay float32.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax.numpy as jnp
from flax import linen as nn

ModuleDef = Any

#: block counts per stage
RESNET_DEPTHS = {
    18: (2, 2, 2, 2),
    34: (3, 4, 6, 3),
    50: (3, 4, 6, 3),
    101: (3, 4, 23, 3),
    152: (3, 8, 36, 3),
}
#: depths that use the 3-conv bottleneck block (4x channel expansion)
BOTTLENECK_DEPTHS = (50, 101, 152)


def make_norm(
    train: bool,
    dtype: jnp.dtype = jnp.float32,
    cross_replica_axis: str | None = None,
    momentum: float = 0.9,
    fp32_stats: bool = True,
) -> ModuleDef:
    """BatchNorm factory: per-replica stats by default (the reference's
    ``sync_bn=False``), cross-replica when an axis name is given.

    ``fp32_stats=False`` computes batch statistics in the compute dtype
    instead of flax's float32 promotion (``force_float32_reductions``).
    The op profiles attribute 46% of the b8 flagship's device time — and
    the b16 regression's largest term — to bf16→f32 convert+reduce chains
    riding the conv fusions (2026-08 op profiles); this is the
    measured-mechanism A/B.  Accuracy: bf16 mean/var over >=8·64² elements
    loses ~2-3 decimal digits; gate on a convergence check before
    defaulting."""
    return partial(
        nn.BatchNorm,
        use_running_average=not train,
        momentum=momentum,
        epsilon=1e-5,
        dtype=dtype,
        axis_name=cross_replica_axis,
        force_float32_reductions=fp32_stats,
    )


class BasicBlock(nn.Module):
    """Two 3x3 convs + identity shortcut (ResNet-18/34)."""

    filters: int
    norm: ModuleDef
    strides: int = 1
    dilation: int = 1
    dtype: jnp.dtype = jnp.float32

    expansion: int = 1

    @nn.compact
    def __call__(self, x):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        residual = x
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                 kernel_dilation=(self.dilation, self.dilation), padding="SAME")(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = conv(self.filters, (3, 3),
                 kernel_dilation=(self.dilation, self.dilation), padding="SAME")(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters, (1, 1),
                            strides=(self.strides, self.strides))(residual)
            residual = self.norm()(residual)
        return nn.relu(y + residual)


class BottleneckBlock(nn.Module):
    """1x1 reduce -> 3x3 (carries stride/dilation) -> 1x1 expand x4.

    Stride on the 3x3 (the "v1.5" placement) — the variant dilated
    segmentation backbones use.
    """

    filters: int
    norm: ModuleDef
    strides: int = 1
    dilation: int = 1
    dtype: jnp.dtype = jnp.float32

    expansion: int = 4

    @nn.compact
    def __call__(self, x):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        residual = x
        y = conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                 kernel_dilation=(self.dilation, self.dilation), padding="SAME")(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = conv(self.filters * self.expansion, (1, 1))(y)
        # zero-init the last norm's scale: each block starts as identity,
        # stabilizing early training of deep nets
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters * self.expansion, (1, 1),
                            strides=(self.strides, self.strides))(residual)
            residual = self.norm()(residual)
        return nn.relu(y + residual)


def _stage_plan(output_stride: int) -> tuple[Sequence[int], Sequence[int]]:
    """(strides, dilations) for stages 1-4 given the target output stride.

    Stride 32 is the classification layout; 16 dilates stage 4; 8 dilates
    stages 3 and 4 (DANet's layout).
    """
    if output_stride == 32:
        return (1, 2, 2, 2), (1, 1, 1, 1)
    if output_stride == 16:
        return (1, 2, 2, 1), (1, 1, 1, 2)
    if output_stride == 8:
        return (1, 2, 1, 1), (1, 1, 2, 4)
    raise ValueError(f"output_stride must be 8, 16 or 32, got {output_stride}")


class ResNet(nn.Module):
    """Dilated ResNet feature extractor.

    ``__call__(x, train)`` -> dict of feature maps ``{'c1','c2','c3','c4'}``
    (stage outputs; ``c4`` is the head input at input/output_stride, ``c3``
    feeds auxiliary heads).  ``x`` is NHWC with any channel count — the stem
    adapts, covering the reference's 4-channel RGB+guidance input.
    """

    depth: int = 50
    output_stride: int = 16
    multi_grid: Sequence[int] | None = None  # stage-4 per-block dilation mult
    width: int = 64
    dtype: jnp.dtype = jnp.float32
    bn_cross_replica_axis: str | None = None
    bn_fp32_stats: bool = True  # False: BN stats in compute dtype (see make_norm)
    deep_stem: bool = False  # 3x 3x3 stem (encoding-style) vs single 7x7
    remat: bool = False  # rematerialize blocks: trade FLOPs for HBM
    #: with remat: a jax.checkpoint_policies name ('dots_saveable',
    #: 'dots_with_no_batch_dims_saveable', ...) instead of full recompute.
    #: Rationale (2026-08 b16 op profiles): XLA AUTO-rematerializes under
    #: HBM pressure at b16 with its own op choice; full per-block remat
    #: measured -13.5% there because the recompute re-reads more HBM than
    #: the stash it saves.  'dots_saveable' keeps conv/matmul outputs and
    #: recomputes only the cheap elementwise/BN chains — the explicit
    #: pre-emption to A/B against it.
    remat_policy: str | None = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = make_norm(train, self.dtype, self.bn_cross_replica_axis,
                 fp32_stats=self.bn_fp32_stats)
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        block_cls = (
            BottleneckBlock if self.depth in BOTTLENECK_DEPTHS else BasicBlock
        )
        # Explicit block names (matching linen's auto-numbering) keep the
        # param tree identical whether or not remat is on — a checkpoint
        # written either way restores either way.
        block_name = block_cls.__name__
        if self.remat:
            # jax.checkpoint per residual block: the backward pass recomputes
            # each block's activations instead of holding all ~100 of them in
            # HBM — the standard way to fit bigger batches/crops per chip.
            policy = None
            if self.remat_policy:
                import jax

                policy = getattr(jax.checkpoint_policies, self.remat_policy)
            block_cls = nn.remat(block_cls, policy=policy)
        counts = RESNET_DEPTHS[self.depth]
        strides, dilations = _stage_plan(self.output_stride)

        if self.deep_stem:
            for i, (f, s) in enumerate(
                ((self.width, 2), (self.width, 1), (self.width * 2, 1))
            ):
                x = conv(f, (3, 3), strides=(s, s), padding="SAME")(x)
                x = norm()(x)
                x = nn.relu(x)
        else:
            x = conv(self.width, (7, 7), strides=(2, 2), padding="SAME")(x)
            x = norm()(x)
            x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")

        feats = {}
        filters = self.width
        block_idx = 0
        for stage, n_blocks in enumerate(counts):
            for i in range(n_blocks):
                dil = dilations[stage]
                if stage == 3 and self.multi_grid is not None:
                    dil *= self.multi_grid[min(i, len(self.multi_grid) - 1)]
                x = block_cls(
                    filters=filters,
                    norm=norm,
                    strides=strides[stage] if i == 0 else 1,
                    dilation=dil,
                    dtype=self.dtype,
                    name=f"{block_name}_{block_idx}",
                )(x)
                block_idx += 1
            feats[f"c{stage + 1}"] = x
            filters *= 2
        return feats


def resnet50(**kw) -> ResNet:
    return ResNet(depth=50, **kw)


def resnet101(**kw) -> ResNet:
    return ResNet(depth=101, **kw)
