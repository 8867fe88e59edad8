"""SSSP demo: δ-stepping as the second Graph500 kernel (DESIGN.md §16).

    PYTHONPATH=src python examples/sssp.py

Runs the weighted pipeline end-to-end (``Graph500Config(kernel="sssp")``),
prints the per-round bucket trace of one search — the δ-stepping engine
surfaces ``(bucket index, frontier popcount, relaxed edges)`` per round
through the same stats slots the BFS engine uses for direction/frontier —
and asserts the distances AND parents are bitwise-equal to the host
Dijkstra oracle (CI runs this file; a parity break fails the job).

The closing leg runs the same kernel on a road-like 2-D grid
(``repro.data.graphs.grid_graph``): diameter O(side), so the bucket
count explodes compared to the small-world Kronecker graph — the regime
where SSSP and BFS traversal behave most differently.
"""
import numpy as np

from repro.core import (
    WEIGHT_UNIT, Graph500Config, PreparedGraph, TraversalPlan, build_csr,
    chunk_edge_view, compile_plan, edge_view, run, sssp_oracle,
    with_edge_weights,
)

# 1. The weighted pipeline end-to-end --------------------------------------
cfg = Graph500Config(scale=10, n_roots=4, kernel="sssp", heavy_threshold=None)
built, g500 = run(cfg)
print(f"sssp pre-g500   : {g500.harmonic_mean_teps / 1e9:.5f} GTEPS "
      f"(valid={g500.all_valid})")
assert g500.all_valid, "SSSP spec validation failed"

# 2. One search's bucket trace ---------------------------------------------
pg = PreparedGraph(ev=built.ev, degree=built.degree, core=None,
                   chunks=chunk_edge_view(built.ev))
plan = TraversalPlan(layout=(), batch_roots=False, kernel="sssp")
res = compile_plan(plan, pg).bfs(0)
rounds = int(res.stats.levels)
print(f"rounds          : {rounds} δ-bucket rounds from root 0")
print("round  bucket  frontier  relaxed_edges")
buckets = np.asarray(res.stats.direction)
fsz = np.asarray(res.stats.frontier_size)
scanned = np.asarray(res.stats.scanned_edges)
show = list(range(min(rounds, 10))) + ([rounds - 1] if rounds > 10 else [])
for t in show:
    if t == rounds - 1 and rounds > 11:
        print("  ...")
    print(f"{t:5d}  {buckets[t]:6d}  {fsz[t]:8d}  {scanned[t]:13d}")

# 3. Bitwise oracle parity --------------------------------------------------
V = built.n_vertices
par, dist = sssp_oracle(built.ev.src, built.ev.dst, built.ev.valid,
                        built.ev.weight, V, 0)
assert np.array_equal(np.asarray(res.parent)[:V], par), "parent mismatch"
assert np.array_equal(np.asarray(res.level)[:V], dist), "distance mismatch"
print(f"oracle parity   : parents and distances bitwise-identical "
      f"(reached {int(np.sum(dist >= 0))}/{V} vertices)")

# 4. The road-like regime ---------------------------------------------------
from repro.data.graphs import grid_graph

g = build_csr(grid_graph(32, seed=5))
ev = with_edge_weights(edge_view(g), seed=2)
gpg = PreparedGraph(ev=ev, degree=g.degree, core=None,
                    chunks=chunk_edge_view(ev))
gres = compile_plan(plan, gpg).bfs(0)
gpar, gdist = sssp_oracle(ev.src, ev.dst, ev.valid, ev.weight,
                          g.num_vertices, 0)
assert np.array_equal(np.asarray(gres.parent)[:g.num_vertices], gpar)
assert np.array_equal(np.asarray(gres.level)[:g.num_vertices], gdist)
print(f"grid 32x32      : {int(gres.stats.levels)} rounds, "
      f"max distance {gdist.max() * WEIGHT_UNIT:.4f} — the high-diameter "
      f"regime (oracle parity holds)")
