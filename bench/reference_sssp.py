"""Plain host reference for Graph500 kernel 3 (single-source shortest
paths), and the comparison that decides ``correct`` in its cells.

The graph is the BFS reference's (``bench.reference.HostGraph``: the
benchmark's own generator output, undirected, deduplicated, relabelled
by descending degree).  The weights are the benchmark's own copy of the
program's draw (``repro.core.graph_build.edge_weights``), applied to the
original generator ids: uniform over the multiples of ``UNIT`` = 2**-20
in (0, 1), a hash of the seed and the unordered endpoint pair.  On that
grid every path length is an exact sum, so the reference holds weights
and distances as integer counts of ``UNIT`` and its distances are exact:
scipy's Dijkstra adds integers below 2**53 in float64 without rounding.
Distances are unique; a shortest-path tree is not, so an answer is
judged by its distances and by whether every parent lies on a shortest
path.
"""
from __future__ import annotations

import numpy as np

from bench.reference import HostGraph

UNIT_BITS = 20
UNIT = 2.0 ** -UNIT_BITS


def _mix32(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x7FEB352D)
    h = h ^ (h >> np.uint32(15))
    h = h * np.uint32(0x846CA68B)
    return h ^ (h >> np.uint32(16))


def edge_weights(u, v, seed: int) -> np.ndarray:
    """Weight of each edge ``(u[i], v[i])`` (original ids) in units of
    ``UNIT``: in ``[1, 2**20 - 1]``, equal for ``(u, v)`` and ``(v, u)``.
    A copy of the program's draw, not an import."""
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    u = np.asarray(u).astype(np.uint32)
    v = np.asarray(v).astype(np.uint32)
    a, b = np.minimum(u, v), np.maximum(u, v)
    with np.errstate(over="ignore"):
        key = _mix32(np.uint32(seed & 0xFFFFFFFF)
                     ^ _mix32(np.uint32(seed >> 32)))
        h = _mix32(a * np.uint32(0x9E3779B9) + key)
        h = _mix32(h ^ (b * np.uint32(0x85EBCA6B)))
    return np.uint32(1) + h % np.uint32((1 << UNIT_BITS) - 1)


def slot_weights(g: HostGraph, seed: int) -> np.ndarray:
    """int64 weight of each of ``g``'s directed edges, in the order of
    ``g.keys`` (so aligned with ``g.col``), drawn over the original ids."""
    old_from_new = np.argsort(g.new_from_old)
    s = old_from_new[g.keys // g.n]
    d = old_from_new[g.col]
    return edge_weights(s, d, seed).astype(np.int64)


def shortest_distances(g: HostGraph, w: np.ndarray, roots) -> np.ndarray:
    """``[len(roots), n]`` int64 exact distances in units of ``UNIT``,
    -1 where unreachable."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra

    adj = csr_matrix((w.astype(np.float64), g.col, g.indptr),
                     shape=(g.n, g.n))
    dist = dijkstra(adj, directed=True,
                    indices=np.asarray(roots, np.int64).reshape(-1))
    return np.where(np.isinf(dist), -1, dist).astype(np.int64)


def weight_of(g: HostGraph, w: np.ndarray, u, v):
    """``(is_edge, weight)`` of each ``(u[i], v[i])``; weight 0 where
    there is no such edge."""
    key = np.asarray(u, np.int64) * g.n + np.asarray(v, np.int64)
    pos = np.minimum(np.searchsorted(g.keys, key), g.keys.size - 1)
    hit = g.keys[pos] == key
    return hit, np.where(hit, w[pos], 0)


def compare(g: HostGraph, w: np.ndarray, root: int, parent, dist,
            ref_dist) -> dict:
    """How far one answer is from the reference.

    ``dist_mismatch``: vertices whose returned distance differs from the
    reference (-1 for unreached on both sides).
    ``parent_bad``: the root not its own parent, an unreached vertex with
    a parent, or a reached one whose parent ``p`` is not a neighbour with
    ``ref_dist[p] + w(p, v) == ref_dist[v]``.
    """
    parent = np.asarray(parent, np.int64)
    dist = np.asarray(dist, np.int64)
    ref_dist = np.asarray(ref_dist, np.int64)
    if parent.shape != (g.n,) or dist.shape != (g.n,):
        return {"dist_mismatch": g.n, "parent_bad": g.n}
    dist_mismatch = int(np.count_nonzero(dist != ref_dist))
    reached = ref_dist >= 0
    bad = int(parent[root] != root)
    bad += int(np.count_nonzero(parent[~reached] != -1))
    v = np.flatnonzero(reached)
    v = v[v != root]
    p = parent[v]
    inside = (p >= 0) & (p < g.n)
    ok = np.zeros(v.size, bool)
    hit, wt = weight_of(g, w, v[inside], p[inside])
    pd = ref_dist[p[inside]]
    ok[inside] = hit & (pd >= 0) & (pd + wt == ref_dist[v[inside]])
    bad += int(np.count_nonzero(~ok))
    return {"dist_mismatch": dist_mismatch, "parent_bad": bad}


def control_answer(g: HostGraph, w: np.ndarray, root: int):
    """The control: the reference one bit coarser than the configuration
    states.  Weights are rounded to the multiples of 2**-19 (never to
    0); the distances, in units of ``UNIT``, and the min-source parents
    are exact on that coarser grid.  Returns ``(parent, dist)``."""
    coarse = np.maximum((w + 1) >> 1 << 1, 2)
    dist = shortest_distances(g, coarse, [root])[0]
    s, d = g.keys // g.n, g.col
    win = (dist[s] >= 0) & (dist[s] + coarse == dist[d])
    parent = np.full(g.n, g.n, np.int64)
    np.minimum.at(parent, d[win], s[win])
    parent = np.where(parent == g.n, -1, parent)
    parent[root] = root
    return parent, dist
