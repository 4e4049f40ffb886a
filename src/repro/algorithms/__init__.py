"""The paper's three irregular algorithms as ``WorkSpec`` definitions.

Each module exports a ``*_spec`` factory consumed by the unified
``repro.core.run_irregular`` driver over any ``make_pool`` backend; the
old per-algorithm entry points (``uts_parallel``, ``mariani_silver``,
``betweenness_centrality``) remain as deprecated shims."""
from .uts import (
    Bag,
    UTSParams,
    UTSResult,
    expand_bag,
    expected_tree_size,
    uts_parallel,
    uts_sequential,
    uts_spec,
)
from .mariani_silver import (
    Action,
    MSParams,
    MSResult,
    Rect,
    evaluate_rect,
    mariani_silver,
    ms_spec,
    naive_render,
    plane_coords,
)
from .betweenness import (
    BCResult,
    RMATParams,
    bc_batch,
    bc_reference,
    bc_single_node,
    bc_spec,
    betweenness_centrality,
    rmat_adjacency,
    rmat_edges,
    rmat_graph,
)

__all__ = [
    "Bag", "UTSParams", "UTSResult", "expand_bag", "expected_tree_size",
    "uts_parallel", "uts_sequential", "uts_spec",
    "Action", "MSParams", "MSResult", "Rect", "evaluate_rect",
    "mariani_silver", "ms_spec", "naive_render", "plane_coords",
    "BCResult", "RMATParams", "bc_batch", "bc_reference", "bc_single_node",
    "bc_spec", "betweenness_centrality", "rmat_adjacency", "rmat_edges",
    "rmat_graph",
]
