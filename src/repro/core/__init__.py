"""HiHGNN core: bound-aware stage fusion, independency-aware parallel
execution (lane scheduling), similarity-aware execution scheduling, and
RAB-style data-reuse accounting."""
from . import stages
from .fusion import (
    FusedFPInputs,
    NABackend,
    SemanticGraphBatch,
    batch_semantic_graph,
    mean_aggregate,
    neighbor_aggregate,
    neighbor_aggregate_multi,
    require_tpu,
)
from .reuse import FPTraffic, ReuseCounters, count_reuse, fp_buffer_traffic
from .scheduling import (
    LanePlan,
    brute_force_hamilton_path,
    lane_assignment,
    naive_lane_assignment,
    shortest_hamilton_path,
    similarity_matrix,
    similarity_schedule,
)

__all__ = [
    "stages",
    "FusedFPInputs",
    "NABackend",
    "SemanticGraphBatch",
    "batch_semantic_graph",
    "mean_aggregate",
    "neighbor_aggregate",
    "neighbor_aggregate_multi",
    "require_tpu",
    "FPTraffic",
    "ReuseCounters",
    "count_reuse",
    "fp_buffer_traffic",
    "LanePlan",
    "brute_force_hamilton_path",
    "lane_assignment",
    "naive_lane_assignment",
    "shortest_hamilton_path",
    "similarity_matrix",
    "similarity_schedule",
]
