"""Core library: the paper's contribution (Graph500 customization) in JAX.

Public API re-exports.
"""
from repro.core.kronecker import EdgeList, generate_edges, sample_roots
from repro.core.graph_build import CSRGraph, build_csr
from repro.core.reorder import Reordering, degree_reorder, reorder_graph
from repro.core.heavy import (
    HeavyCore, build_heavy_core, pack_bitmap, padded_bitmap_words, unpack_bitmap,
)
from repro.core.bfs_steps import (
    ChunkedEdgeView, EdgeView, chunk_edge_view, edge_view, with_edge_weights,
)
from repro.core.graph_build import WEIGHT_UNIT, edge_weights
from repro.core.hybrid_bfs import (
    BFSResult, bfs_batch, bfs_batch_sharded, hybrid_bfs,
)
from repro.core.faults import FAULT_CLASSES, FaultSpec
from repro.core.validate import (
    CHECK_NAMES, SSSP_CHECK_NAMES, validate, validate_batch, validate_sssp,
    validate_sssp_batch,
)
from repro.core.teps import (
    run_graph500, run_graph500_batched, run_graph500_sharded, traversed_edges,
)
from repro.core.kernels import (
    KERNELS, KernelSpec, kernel_spec, rekernel_plan,
)
from repro.core.sssp_steps import (
    SSSP_EXCHANGES, bucket_width, sssp_max_rounds, sssp_oracle,
)
from repro.core.plan import (
    BFSPlan, CompiledBFS, Graph500Result, PreparedGraph, TraversalPlan,
    compile_plan,
)
from repro.core.pipeline import Graph500Config, build, run

# Tuner exports resolve lazily: `python -m repro.core.tune` must be able
# to execute the module as __main__ without this package import having
# already registered it in sys.modules (runpy warns otherwise).
_TUNE_EXPORTS = ("TuneReport", "TuneResult", "enumerate_plans",
                 "load_table", "save_tuned", "sweep", "tuned_plan")


def __getattr__(name):
    if name in _TUNE_EXPORTS:
        from repro.core import tune
        return getattr(tune, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "EdgeList", "generate_edges", "sample_roots",
    "CSRGraph", "build_csr",
    "Reordering", "degree_reorder", "reorder_graph",
    "HeavyCore", "build_heavy_core", "pack_bitmap", "padded_bitmap_words",
    "unpack_bitmap",
    "ChunkedEdgeView", "EdgeView", "chunk_edge_view", "edge_view",
    "with_edge_weights", "WEIGHT_UNIT", "edge_weights",
    "BFSResult", "bfs_batch", "bfs_batch_sharded", "hybrid_bfs",
    "FAULT_CLASSES", "FaultSpec",
    "CHECK_NAMES", "SSSP_CHECK_NAMES", "validate", "validate_batch",
    "validate_sssp", "validate_sssp_batch",
    "run_graph500", "run_graph500_batched",
    "run_graph500_sharded", "traversed_edges",
    "KERNELS", "KernelSpec", "kernel_spec", "rekernel_plan",
    "SSSP_EXCHANGES", "bucket_width", "sssp_max_rounds", "sssp_oracle",
    "BFSPlan", "TraversalPlan", "CompiledBFS", "Graph500Result",
    "PreparedGraph", "compile_plan",
    "TuneReport", "TuneResult", "enumerate_plans", "load_table",
    "save_tuned", "sweep", "tuned_plan",
    "Graph500Config", "build", "run",
]
