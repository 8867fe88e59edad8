"""Paper Fig. 12/13: vertex-sorting policy ablation + cost/benefit.

Fig. 12's merge/quick/bubble are host sorting algorithms used to produce
the degree permutation; Fig. 13 is the cost (sort time) vs benefit (BFS
speedup) ratio. We measure both: classical host sorts on the true degree
array, and end-to-end TEPS with/without the degree reordering.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import FAST, row, timed
from repro.core import (
    PreparedGraph, TraversalPlan, build_csr, compile_plan, degree_reorder,
    edge_view, generate_edges, traversed_edges,
)
from repro.core.reorder import relabel_edges, sort_host
from repro.core.kronecker import EdgeList
import jax
import jax.numpy as jnp


def run():
    rows = []
    scale = 10 if FAST else 12
    edges = generate_edges(2, scale)
    g0 = build_csr(edges)
    deg = np.asarray(g0.degree)

    # --- Fig. 12: sorting algorithm wall time (permutation identical) ----
    algos = ["merge", "quick", "xla"] if FAST else ["merge", "quick", "bubble", "xla"]
    for alg in algos:
        n = len(deg) if alg != "bubble" else min(len(deg), 2048)
        d = deg[:n]
        t0 = time.perf_counter()
        perm = sort_host(d, alg)
        dt = time.perf_counter() - t0
        assert np.all(np.diff(d[perm]) <= 0)
        rows.append(row(f"sorting/{alg}/n{n}", dt * 1e6,
                        f"keys_per_s={n / max(dt, 1e-9):.3g}"))

    # --- Fig. 12/13: BFS TEPS with and without the reordering -------------
    plan_ref = TraversalPlan(engine="reference", batch_roots=False)

    def ref_bfs(ev, degree):
        return compile_plan(plan_ref, PreparedGraph(ev=ev, degree=degree))

    variants = {}
    ev0 = edge_view(g0)
    res0 = ref_bfs(ev0, g0.degree).bfs(0)
    m = int(traversed_edges(g0.degree, res0))
    variants["without_sorting"] = (ev0, g0.degree)

    r = degree_reorder(g0.degree)
    g1 = build_csr(relabel_edges(edges, r))
    variants["degree_sorted"] = (edge_view(g1), g1.degree)

    rng = np.random.default_rng(0)
    perm = rng.permutation(g0.num_vertices).astype(np.int32)
    e_rand = EdgeList(src=jnp.asarray(perm)[edges.src],
                      dst=jnp.asarray(perm)[edges.dst],
                      num_vertices=edges.num_vertices)
    g2 = build_csr(e_rand)
    variants["random_relabel"] = (edge_view(g2), g2.degree)

    teps = {}
    for name, (ev, degree) in variants.items():
        compiled = ref_bfs(ev, degree)
        t = timed(lambda c=compiled: c.bfs(0).parent)
        teps[name] = m / t
        rows.append(row(f"sorting_teps/{name}", t * 1e6,
                        f"GTEPS={m / t / 1e9:.5f}"))

    # --- Fig. 13: cost-benefit — sort cost amortized over 64 roots --------
    t_sort = timed(lambda: degree_reorder(g0.degree).old_from_new)
    gain_per_bfs = max(1.0 / teps["without_sorting"] - 1.0 / teps["degree_sorted"], 1e-12)
    breakeven = t_sort / gain_per_bfs
    rows.append(row("sorting_cost_benefit", t_sort * 1e6,
                    f"breakeven_roots={breakeven:.1f};"
                    f"speedup={teps['degree_sorted'] / teps['without_sorting']:.2f}x"))
    return rows
