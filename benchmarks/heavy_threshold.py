"""Paper Fig. 14: heavy-vertex buffering threshold ablation.

D(>=50) / D(>=100) / D(>=1000) / no-buffer, translated to bench scale:
at scale 36 the paper's D>=100 captures ~5% of active vertices; we sweep
thresholds that bracket the same percentile at our scales plus the
literal values. Reported: TEPS + core occupancy (how much of the
traversal the dense core absorbs — the locality the buffer buys).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import FAST, row, timed
from repro.core import (
    PreparedGraph, TraversalPlan, build_csr, build_heavy_core,
    chunk_edge_view, compile_plan, degree_reorder, edge_view, generate_edges,
    traversed_edges,
)
from repro.core.reorder import relabel_edges


def run():
    rows = []
    # scale >= 13 so V > CORE_ALIGN=4096 and the threshold actually moves
    # the core boundary (at scale 10 the minimum core swallowed the whole
    # graph and the sweep was degenerate — see EXPERIMENTS.md).
    scale = 13
    edges = generate_edges(3, scale)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    ev = edge_view(g)
    chunks = chunk_edge_view(ev)  # construction, untimed (spec)
    ref = compile_plan(TraversalPlan(engine="reference", batch_roots=False),
                       PreparedGraph(ev=ev, degree=g.degree))
    res = ref.bfs(0)
    m = int(traversed_edges(g.degree, res))
    deg = np.asarray(g.degree)

    t_none = timed(lambda: ref.bfs(0).parent)
    rows.append(row("heavy_buffer/none", t_none * 1e6,
                    f"GTEPS={m / t_none / 1e9:.5f}"))

    thresholds = (4, 16, 64) if FAST else (4, 16, 50, 64, 100)
    plan_bm = TraversalPlan(engine="bitmap", batch_roots=False)
    for d_thr in thresholds:
        core = build_heavy_core(g, threshold=d_thr)
        frac_v = float((deg >= d_thr).mean())
        core_edges = int(core.core_nnz)
        frac_e = core_edges / max(int(g.nnz), 1)
        bm = compile_plan(plan_bm, PreparedGraph(
            ev=ev, degree=g.degree, core=core, chunks=chunks))
        t = timed(lambda bm=bm: bm.bfs(0).parent)
        rows.append(row(
            f"heavy_buffer/D>={d_thr}", t * 1e6,
            f"GTEPS={m / t / 1e9:.5f};heavy_vert={frac_v:.2%};"
            f"core_edges={frac_e:.2%};K={core.k};"
            f"core_MiB={core.k * core.k / 32 * 4 / 2**20:.1f}"))
    return rows
