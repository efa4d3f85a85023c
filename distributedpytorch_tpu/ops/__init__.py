"""Compute primitives: attention, losses, metrics.

These are the pure-function kernels under the model layer — the part of the
reference that lived in external CUDA packages (PyTorch-Encoding's DANet
attention blocks, ``SegmentationMultiLosses``; reference train_pascal.py:32-33)
re-expressed as XLA-compiled jnp (with Pallas variants for the hot attention
path).
"""

from . import augment
from . import diffusion
from . import guidance_device
from .attention import (
    position_attention,
    blocked_position_attention,
    channel_attention,
    causal_attention,
    block_diffusion_attention,
)
from .pallas_attention import (
    flash_block_diffusion_attention,
    flash_causal_attention,
    flash_channel_attention,
    flash_position_attention,
)
from .losses import (
    sigmoid_balanced_bce,
    multi_output_loss,
    next_token_xent,
    se_presence_loss,
    softmax_xent_ignore,
    weighted_token_xent,
)
from .metrics import (
    batched_jaccard,
    confusion_matrix,
    jaccard,
    miou_from_confusion,
    threshold_sweep_jaccard,
)
from .warp import fullres_argmax, resize_bilinear_ragged

__all__ = [
    "augment",
    "diffusion",
    "guidance_device",
    "position_attention",
    "blocked_position_attention",
    "channel_attention",
    "causal_attention",
    "block_diffusion_attention",
    "flash_block_diffusion_attention",
    "flash_causal_attention",
    "flash_channel_attention",
    "flash_position_attention",
    "sigmoid_balanced_bce",
    "multi_output_loss",
    "next_token_xent",
    "se_presence_loss",
    "softmax_xent_ignore",
    "weighted_token_xent",
    "jaccard",
    "batched_jaccard",
    "confusion_matrix",
    "miou_from_confusion",
    "threshold_sweep_jaccard",
    "fullres_argmax",
    "resize_bilinear_ragged",
]
