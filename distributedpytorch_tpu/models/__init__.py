"""Model zoo: dilated ResNet backbones, DANet and DeepLabV3 heads.

``build_model`` is the single factory the trainer and configs use — the
framework equivalent of the reference's hardwired ``DANet(1, 'resnet101')``
construction (reference train_pascal.py:86) plus its commented DeepLab
alternative (train_pascal.py:85).

Contract: every model's ``__call__(x_nhwc, train)`` returns a *tuple* of
input-resolution logit maps, primary prediction first, so the multi-output
loss and eval code are model-agnostic.
"""

from __future__ import annotations

import jax.numpy as jnp

from .ccnet import CCNet, CrissCrossAttention, RCCAHead
from .danet import DANet, DANetHead
from .deeplab import ASPP, DeepLabV3, FCN, FCNHead
from .encnet import EncNet, EncNetHead, Encoding
from .kanana_lm import KananaLM, build_kanana_lm
from .keye_lm import KeyeLM, build_keye_lm
from .nemotron_h import NemotronH, build_nemotron_h
from .pspnet import PSPNet, PyramidPooling
from .resnet import ResNet, resnet50, resnet101
from .sdar_lm import SdarLM, build_sdar_lm
from .zaya_lm import ZayaLM, build_zaya_lm

#: the tasks (``Config.task``) a model trains under, by its ``build_model``
#: name; a name not listed is a segmentation net.  What the trainer checks a
#: configuration against: it knows tasks, not models.
SEGMENTATION_TASKS = ("instance", "semantic")
#: the token models: ``name -> (builder(lm_config, dtype=, remat=), tasks)``.
#: ``build_model``, :data:`MODEL_TASKS` and the ``lm_config`` error read
#: this table; a new token model is one entry
TOKEN_MODELS = {"nemotron_h": (build_nemotron_h, ("tokens",)),
                "keye_lm": (build_keye_lm, ("tokens",)),
                "sdar_lm": (build_sdar_lm, ("tokens",)),
                "zaya_lm": (build_zaya_lm, ("tokens",)),
                "kanana_lm": (build_kanana_lm, ("tokens",))}
MODEL_TASKS = {name: tasks for name, (_, tasks) in TOKEN_MODELS.items()}


def model_tasks(name: str) -> tuple:
    return MODEL_TASKS.get(name, SEGMENTATION_TASKS)


_BACKBONE_DEPTH = {"resnet18": 18, "resnet34": 34, "resnet50": 50,
                   "resnet101": 101, "resnet152": 152}


def build_model(
    name: str = "danet",
    nclass: int = 1,
    backbone: str = "resnet101",
    output_stride: int | None = None,
    dtype: str | jnp.dtype = jnp.float32,
    bn_cross_replica_axis: str | None = None,
    bn_fp32_stats: bool = True,
    **kw,
):
    """Construct a segmentation model by name — or, by a name of
    :data:`TOKEN_MODELS` (``nemotron_h``, ``keye_lm``, ``sdar_lm``,
    ``zaya_lm``, ``kanana_lm``), a token model of the ``tokens`` task
    (``lm_config``: a preset's name, a JSON file of the published keys, or
    that dict; ``remat``; the image options do not apply to it).

    ``dtype`` may be a string ('float32' / 'bfloat16') for config-file use.
    """
    if isinstance(dtype, str):
        dtype = jnp.dtype(dtype)
    if name in TOKEN_MODELS:
        return TOKEN_MODELS[name][0](
            kw.get("lm_config", ""), dtype=dtype,
            remat=kw.get("remat", True))
    if kw.pop("lm_config", ""):
        raise ValueError(
            f"lm_config is for the token models ({' | '.join(TOKEN_MODELS)}"
            f"); model {name!r} does not support it")
    if isinstance(kw.get("pam_score_dtype"), str):
        kw["pam_score_dtype"] = jnp.dtype(kw["pam_score_dtype"])
    depth = _BACKBONE_DEPTH[backbone]
    if name == "danet":
        # model.attention_impl — ONE knob for both attention branches:
        # 'auto' (default: the fused Pallas kernels for bf16 compute on
        # TPU — the mixed-precision hot path — XLA einsum otherwise; the
        # module resolves backend+dtype at trace time), 'xla' (einsum
        # everywhere, reference parity), 'flash' (force Pallas).
        # model.pam_impl, when set, overrides the position branch (its
        # extra forms — ring, blocked — stay reachable).
        attention_impl = kw.pop("attention_impl", "auto") or "auto"
        branch = {"auto": "auto", "xla": "einsum",
                  "flash": "flash"}.get(attention_impl)
        if branch is None:
            raise ValueError(
                f"unknown attention_impl: {attention_impl!r} "
                "(auto | xla | flash)")
        kw["pam_impl"] = kw.pop("pam_impl", "") or branch
        kw.setdefault("cam_impl", branch)
    else:
        # PAM/MoE options are DANet-only.  One config schema drives every
        # model family, so default values are silently dropped — but a
        # non-default setting on another model is a misconfiguration, not
        # something to train past.
        danet_only = {"pam_block_size": (None,),
                      # both the inherit sentinel and the legacy spelled-
                      # out default (pre-attention_impl configs on disk)
                      "pam_impl": ("", "einsum"),
                      "attention_impl": ("auto",),
                      "cam_impl": ("einsum",),
                      "pam_sp_mesh": (None,), "pam_sp_axis": ("model",),
                      "pam_score_dtype": (None,),
                      "moe_experts": (0,), "moe_hidden": (None,),
                      "moe_k": (1,), "moe_capacity_factor": (1.25,),
                      "guidance_inject": ("stem",)}
        for k, defaults in danet_only.items():
            if k in kw and kw.pop(k) not in defaults:
                raise ValueError(
                    f"{k} is DANet-only; model {name!r} does not support it")
    if name != "encnet" and kw.pop("encnet_codes", 32) != 32:
        raise ValueError(
            f"encnet_codes is EncNet-only; model {name!r} does not "
            "support it")
    if name != "ccnet" and kw.pop("ccnet_recurrence", 2) != 2:
        raise ValueError(
            f"ccnet_recurrence is CCNet-only; model {name!r} does not "
            "support it")
    if name == "danet":
        if kw.pop("aux_head", False):
            raise ValueError("aux_head is a DeepLabV3/FCN/PSPNet option; DANet's "
                             "three heads already provide multi-output "
                             "supervision")
        return DANet(
            nclass=nclass,
            backbone_depth=depth,
            output_stride=output_stride or 8,
            dtype=dtype,
            bn_cross_replica_axis=bn_cross_replica_axis,
            bn_fp32_stats=bn_fp32_stats,
            **kw,
        )
    if name in ("deeplabv3", "deeplabv3plus"):
        return DeepLabV3(
            nclass=nclass,
            backbone_depth=depth,
            output_stride=output_stride or 16,
            decoder=(name == "deeplabv3plus"),
            dtype=dtype,
            bn_cross_replica_axis=bn_cross_replica_axis,
            bn_fp32_stats=bn_fp32_stats,
            **kw,
        )
    if name == "fcn":
        return FCN(
            nclass=nclass,
            backbone_depth=depth,
            output_stride=output_stride or 8,
            dtype=dtype,
            bn_cross_replica_axis=bn_cross_replica_axis,
            bn_fp32_stats=bn_fp32_stats,
            **kw,
        )
    if name == "pspnet":
        return PSPNet(
            nclass=nclass,
            backbone_depth=depth,
            output_stride=output_stride or 8,
            dtype=dtype,
            bn_cross_replica_axis=bn_cross_replica_axis,
            bn_fp32_stats=bn_fp32_stats,
            **kw,
        )
    if name == "ccnet":
        kw["recurrence"] = kw.pop("ccnet_recurrence", 2)
        if kw["recurrence"] < 1:
            raise ValueError(
                f"ccnet_recurrence must be >= 1 (got {kw['recurrence']}): "
                "R=0 would skip the criss-cross module entirely, creating "
                "no attention params — a CCNet in name only")
        return CCNet(
            nclass=nclass,
            backbone_depth=depth,
            output_stride=output_stride or 8,
            dtype=dtype,
            bn_cross_replica_axis=bn_cross_replica_axis,
            bn_fp32_stats=bn_fp32_stats,
            **kw,
        )
    if name == "encnet":
        kw["n_codes"] = kw.pop("encnet_codes", 32)
        return EncNet(
            nclass=nclass,
            backbone_depth=depth,
            output_stride=output_stride or 8,
            dtype=dtype,
            bn_cross_replica_axis=bn_cross_replica_axis,
            bn_fp32_stats=bn_fp32_stats,
            **kw,
        )
    raise ValueError(
        f"unknown model: {name!r} (danet | deeplabv3 | deeplabv3plus | fcn "
        f"| pspnet | encnet | ccnet | {' | '.join(TOKEN_MODELS)})")


def build_from_config(mcfg, *, dtype, bn_cross_replica_axis=None,
                      pam_sp_mesh=None):
    """``ModelConfig`` -> model: the one place that names the config's model
    fields as :func:`build_model`'s arguments, so the trainer, the planner's
    shape-only template and ``predict`` rebuild the same module.  A caller
    decides the compute ``dtype`` (a precision policy's, or ``mcfg.dtype``),
    the mesh axis BatchNorm reduces over, the mesh ring PAM shards over."""
    return build_model(
        name=mcfg.name, nclass=mcfg.nclass, backbone=mcfg.backbone,
        output_stride=mcfg.output_stride, dtype=dtype,
        bn_fp32_stats=mcfg.bn_fp32_stats,
        bn_cross_replica_axis=bn_cross_replica_axis,
        pam_block_size=mcfg.pam_block_size,
        attention_impl=mcfg.attention_impl, pam_impl=mcfg.pam_impl,
        pam_score_dtype=mcfg.pam_score_dtype, pam_sp_mesh=pam_sp_mesh,
        remat=mcfg.remat, remat_policy=mcfg.remat_policy or None,
        moe_experts=mcfg.moe_experts, moe_hidden=mcfg.moe_hidden,
        moe_k=mcfg.moe_k, moe_capacity_factor=mcfg.moe_capacity_factor,
        aux_head=mcfg.aux_head, encnet_codes=mcfg.encnet_codes,
        ccnet_recurrence=mcfg.ccnet_recurrence,
        guidance_inject=mcfg.guidance_inject, lm_config=mcfg.lm_config)


__all__ = [
    "model_tasks",
    "ASPP",
    "CCNet",
    "CrissCrossAttention",
    "DANet",
    "DANetHead",
    "DeepLabV3",
    "EncNet",
    "EncNetHead",
    "Encoding",
    "RCCAHead",
    "FCN",
    "FCNHead",
    "KananaLM",
    "KeyeLM",
    "NemotronH",
    "PSPNet",
    "PyramidPooling",
    "ResNet",
    "SdarLM",
    "ZayaLM",
    "build_from_config",
    "build_model",
    "resnet50",
    "resnet101",
]
