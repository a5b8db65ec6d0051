from .hetgraph import (
    HetGraph,
    Relation,
    SemanticGraph,
    make_relation,
    relation_semantic_graphs,
    union_graph,
)
from .sgb import build_semantic_graph, build_semantic_graphs
from .formats import (
    BlockCSR,
    PaddedEdges,
    block_csr_to_dense,
    dense_adjacency,
    to_block_csr,
    to_padded_edges,
)
from .datasets import (
    TABLE5,
    dataset_metapaths,
    dataset_target,
    synthetic_hetgraph,
    synthetic_labels,
)

__all__ = [
    "HetGraph",
    "Relation",
    "SemanticGraph",
    "make_relation",
    "relation_semantic_graphs",
    "union_graph",
    "build_semantic_graph",
    "build_semantic_graphs",
    "BlockCSR",
    "PaddedEdges",
    "block_csr_to_dense",
    "dense_adjacency",
    "to_block_csr",
    "to_padded_edges",
    "TABLE5",
    "dataset_metapaths",
    "dataset_target",
    "synthetic_hetgraph",
    "synthetic_labels",
]
