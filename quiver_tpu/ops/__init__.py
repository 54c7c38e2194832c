from .sample import (
    ExactBucketMeta,
    exact_bucket_meta,
    suggest_hub_cap,
    sample_layer,
    sample_layer_exact_wide,
    sample_layer_rotation,
    sample_layer_window,
    permute_csr,
    butterfly_shuffle,
    compose_slot_map,
    reshuffle_csr,
    as_index_rows,
    as_index_rows_overlapping,
    edge_row_ids,
    compact_layer,
    sample_prob_step,
    sample_prob,
    LayerSample,
)
from .sample_multihop import sample_multihop, sample_multihop_dedup
from .relation import NodeTypes, TypedIndices
from .dedup import unique_within_budget, dedup_take
from . import quant
from .quant import QuantizedTensor, HotPlan, plan_hot_capacity
from .random_walk import random_walk, random_walk_step
from .weighted import (
    sample_layer_weighted,
    sample_layer_weighted_window,
    csr_weights_from_eid,
)

__all__ = [
    "ExactBucketMeta",
    "exact_bucket_meta",
    "suggest_hub_cap",
    "sample_layer",
    "sample_layer_exact_wide",
    "sample_layer_rotation",
    "sample_layer_window",
    "permute_csr",
    "butterfly_shuffle",
    "compose_slot_map",
    "reshuffle_csr",
    "as_index_rows",
    "as_index_rows_overlapping",
    "edge_row_ids",
    "compact_layer",
    "sample_prob_step",
    "sample_prob",
    "sample_multihop",
    "sample_multihop_dedup",
    "NodeTypes",
    "TypedIndices",
    "unique_within_budget",
    "dedup_take",
    "quant",
    "QuantizedTensor",
    "HotPlan",
    "plan_hot_capacity",
    "random_walk",
    "random_walk_step",
    "sample_layer_weighted",
    "sample_layer_weighted_window",
    "csr_weights_from_eid",
    "LayerSample",
]
