from .mesh import make_mesh, replicated, row_sharded
from .frontier import dedup_feature_gather, masked_feature_gather
from .train import (
    TrainState,
    build_train_step,
    build_e2e_train_step,
    build_split_train_step,
    cross_entropy_logits,
)
from .gspmd import build_gspmd_train_step, shard_state, state_sharding
from .dist import build_dist_train_step

__all__ = [
    "make_mesh",
    "replicated",
    "row_sharded",
    "TrainState",
    "build_train_step",
    "build_e2e_train_step",
    "build_split_train_step",
    "build_gspmd_train_step",
    "build_dist_train_step",
    "dedup_feature_gather",
    "masked_feature_gather",
    "shard_state",
    "state_sharding",
    "cross_entropy_logits",
]
