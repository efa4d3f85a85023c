"""Parallelism subsystem: mesh topology, shardings, compiled train/eval steps.

The TPU-native replacement for the reference's ``torch.nn.DataParallel``
wrapper (reference train_pascal.py:92) and its planned-but-never-built
NCCL/DDP backend (train_pascal.py:1-8).
"""

from .mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    batch_sharding,
    batch_spec,
    initialize_distributed,
    make_hybrid_mesh,
    make_mesh,
    pad_to_multiple,
    prefetch_to_device,
    replicated_sharding,
    replicated_spec,
    shard_batch,
)
from .moe import (
    EXPERT_AXIS,
    MoEMlp,
    ep_param_specs,
    init_moe_params,
    make_expert_mesh,
    make_moe_apply,
    moe_ffn,
)
from .pipeline import (
    PIPE_AXIS,
    flax_stage_fn,
    init_stacked_stage_params,
    make_pipe_mesh,
    make_pipeline_apply,
    make_pipeline_train_step,
    stage_param_specs,
)
from .ring import (
    make_ring_attention,
    make_ring_attention_inline,
    ring_attention_local,
)
from .consensus import (
    ConsensusError,
    reduce_decision,
    replicated_decision,
)
from .plan import (
    BUCKET_COMPATIBLE,
    STRATEGIES,
    Plan,
    PlanError,
    auto_plan,
    estimate_plan_memory,
    plan_from_config,
    plan_record_block,
    resolve_plan,
)
from .tp import state_shardings, tp_param_specs
from .zero import zero_opt_specs
from .ulysses import make_ulysses_attention, ulysses_attention_local
from .step import (
    DEVICE_KEYS,
    INPUT_KEY,
    NEXT_TOKEN,
    TARGET_KEY,
    TOKENS_KEY,
    WIRE_KEY,
    TrainState,
    create_train_state,
    make_eval_step,
    make_train_step,
    pack_wire,
    unpack_wire,
)

__all__ = [
    "BUCKET_COMPATIBLE",
    "STRATEGIES",
    "ConsensusError",
    "reduce_decision",
    "replicated_decision",
    "Plan",
    "PlanError",
    "auto_plan",
    "estimate_plan_memory",
    "plan_from_config",
    "plan_record_block",
    "resolve_plan",
    "DATA_AXIS",
    "EXPERT_AXIS",
    "MODEL_AXIS",
    "PIPE_AXIS",
    "INPUT_KEY",
    "TARGET_KEY",
    "MoEMlp",
    "TrainState",
    "ep_param_specs",
    "flax_stage_fn",
    "init_moe_params",
    "init_stacked_stage_params",
    "make_expert_mesh",
    "make_moe_apply",
    "make_pipe_mesh",
    "make_pipeline_apply",
    "make_pipeline_train_step",
    "moe_ffn",
    "stage_param_specs",
    "batch_sharding",
    "batch_spec",
    "create_train_state",
    "initialize_distributed",
    "make_eval_step",
    "make_hybrid_mesh",
    "make_mesh",
    "make_ring_attention",
    "make_ring_attention_inline",
    "make_ulysses_attention",
    "make_train_step",
    "DEVICE_KEYS",
    "NEXT_TOKEN",
    "TOKENS_KEY",
    "WIRE_KEY",
    "pack_wire",
    "unpack_wire",
    "ring_attention_local",
    "ulysses_attention_local",
    "pad_to_multiple",
    "prefetch_to_device",
    "replicated_sharding",
    "replicated_spec",
    "shard_batch",
    "state_shardings",
    "tp_param_specs",
    "zero_opt_specs",
]
