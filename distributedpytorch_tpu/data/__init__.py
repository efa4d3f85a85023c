"""Data subsystem: dataset, transforms, guidance synthesis, sharded loading."""

from . import guidance, transforms
from .combine import CombinedDataset
from .governor import GOVERNOR_MODES, FeedActuators, FeedGovernor, feed_block
from .fake import make_fake_sbd, make_fake_voc
from .sbd import SBDInstanceSegmentation, SBDSemanticSegmentation
from .grain_pipeline import (GrainDataLoader, HAVE_GRAIN,
                             make_grain_loader)
from .pipeline import (
    DataLoader,
    build_eval_transform,
    build_prepared_post_transform,
    build_prepared_semantic_post_transform,
    build_semantic_eval_transform,
    build_semantic_train_transform,
    build_train_transform,
    collate,
)
from .packed import (
    PackedDataset,
    PackedRecordError,
    PackFormatError,
    pack_dataset,
    pack_name,
    verify_pack,
)
from .prepared import (
    PreparedInstanceDataset,
    PreparedSemanticDataset,
    cache_fingerprint,
)
from .tokens import PackedTokens, SyntheticTokens, write_token_file
from .voc import (
    CATEGORY_NAMES,
    VOCInstanceSegmentation,
    VOCSemanticSegmentation,
    ensure_voc,
)

__all__ = [
    "CATEGORY_NAMES",
    "CombinedDataset",
    "DataLoader",
    "FeedActuators",
    "FeedGovernor",
    "GOVERNOR_MODES",
    "feed_block",
    "VOCInstanceSegmentation",
    "ensure_voc",
    "VOCSemanticSegmentation",
    "HAVE_GRAIN",
    "PackedDataset",
    "PackedTokens",
    "SyntheticTokens",
    "write_token_file",
    "PackedRecordError",
    "PackFormatError",
    "pack_dataset",
    "pack_name",
    "verify_pack",
    "build_eval_transform",
    "build_prepared_post_transform",
    "build_prepared_semantic_post_transform",
    "PreparedInstanceDataset",
    "PreparedSemanticDataset",
    "cache_fingerprint",
    "build_semantic_eval_transform",
    "build_semantic_train_transform",
    "build_train_transform",
    "collate",
    "guidance",
    "SBDInstanceSegmentation",
    "SBDSemanticSegmentation",
    "make_fake_sbd",
    "make_fake_voc",
    "GrainDataLoader",
    "make_grain_loader",
    "transforms",
]
