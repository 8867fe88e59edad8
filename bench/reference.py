"""Plain host reference for breadth-first search, and the comparison that
decides ``correct``.

The reference builds the Graph500 graph from the benchmark's own
generator output (``bench.gen``): undirected, self loops dropped,
duplicate edges merged, vertices relabelled by descending degree with
ties broken by the original id (the relabelling the configurations
state).  It takes nothing from the program.  Its BFS is level-synchronous
top-down over a CSR in NumPy and gives each vertex's distance from the
root, which is unique; a parent tree is not, so an answer is judged by
its levels and by whether every parent is a neighbour one level up.
"""
from __future__ import annotations

import numpy as np


class HostGraph:
    """The undirected, deduplicated, degree-relabelled graph of an edge
    list (original ids), as a CSR in the relabelled ids."""

    def __init__(self, src, dst, n_vertices: int):
        n = int(n_vertices)
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        s = np.concatenate([src, dst])
        d = np.concatenate([dst, src])
        keep = s != d
        key = np.unique(s[keep] * n + d[keep])
        s, d = key // n, key % n
        del key
        degree = np.bincount(s, minlength=n)
        old_from_new = np.argsort(-degree, kind="stable")
        new_from_old = np.empty(n, np.int64)
        new_from_old[old_from_new] = np.arange(n)
        s, d = new_from_old[s], new_from_old[d]
        self.keys = np.sort(s * n + d)      # sorted (src, dst) edge keys
        del s, d
        self.n = n
        self.degree = degree[old_from_new]
        self.indptr = np.concatenate(
            [[0], np.cumsum(self.degree)]).astype(np.int64)
        self.col = self.keys % n
        self.new_from_old = new_from_old
        self.n_active = int(np.count_nonzero(self.degree))

    def bfs_levels(self, root: int, max_depth: int | None = None) -> np.ndarray:
        """Distance from ``root`` of every vertex, -1 where unreachable
        (or deeper than ``max_depth`` when it is given)."""
        level = np.full(self.n, -1, np.int32)
        level[root] = 0
        frontier = np.asarray([root], np.int64)
        depth = 0
        while frontier.size and (max_depth is None or depth < max_depth):
            starts = self.indptr[frontier]
            counts = self.indptr[frontier + 1] - starts
            total = int(counts.sum())
            offsets = np.repeat(starts - (np.cumsum(counts) - counts), counts)
            nbrs = self.col[offsets + np.arange(total)]
            nbrs = nbrs[level[nbrs] < 0]
            level[nbrs] = depth + 1
            depth += 1
            frontier = np.flatnonzero(level == depth)
        return level

    def components(self) -> np.ndarray:
        """Connected-component label of every vertex."""
        from scipy.sparse import csr_matrix
        from scipy.sparse.csgraph import connected_components

        adj = csr_matrix((np.ones(self.col.size, np.int8), self.col,
                          self.indptr), shape=(self.n, self.n))
        return connected_components(adj, directed=False)[1]

    def is_edge(self, u, v) -> np.ndarray:
        """Whether each ``(u[i], v[i])`` is an edge."""
        key = np.asarray(u, np.int64) * self.n + np.asarray(v, np.int64)
        pos = np.minimum(np.searchsorted(self.keys, key), self.keys.size - 1)
        return self.keys[pos] == key


def traversed_edges(degree: np.ndarray, parent: np.ndarray) -> int:
    """Graph500 edge count of one search: undirected edges inside the
    traversed component (half the degree sum of the visited vertices).
    A copy of ``repro.core.teps.traversed_edges``."""
    return int(np.sum(np.where(np.asarray(parent) >= 0, degree, 0)) // 2)


def harmonic_mean(teps) -> float:
    """Graph500 harmonic mean of every search's rate.  A search that
    traversed nothing is not left out: it makes the mean 0.  (The
    program's ``Graph500Run.harmonic_mean_teps`` drops such searches;
    with the spec's keys, each of which has an edge, there are none.)"""
    t = np.asarray(teps, np.float64)
    if t.size == 0 or np.any(t <= 0):
        return 0.0
    return float(t.size / np.sum(1.0 / t))


def compare(g: HostGraph, root: int, parent, level, ref_level) -> dict:
    """How far one answer is from the reference.

    ``level_mismatch``: vertices whose returned level differs from the
    reference distance (-1 for unreached on both sides).
    ``parent_bad``: vertices whose parent breaks the tree: the root not
    its own parent, an unreached vertex with a parent, or a reached one
    whose parent is not a neighbour one level closer to the root.
    """
    parent = np.asarray(parent, np.int64)
    level = np.asarray(level)
    ref_level = np.asarray(ref_level)
    if parent.shape != (g.n,) or level.shape != (g.n,):
        return {"level_mismatch": g.n, "parent_bad": g.n}
    level_mismatch = int(np.count_nonzero(level != ref_level))
    reached = ref_level >= 0
    bad = int(parent[root] != root)
    bad += int(np.count_nonzero(parent[~reached] != -1))
    v = np.flatnonzero(reached)
    v = v[v != root]
    p = parent[v]
    inside = (p >= 0) & (p < g.n)
    ok = np.zeros(v.size, bool)
    ok[inside] = ((ref_level[p[inside]] == ref_level[v[inside]] - 1)
                  & g.is_edge(v[inside], p[inside]))
    bad += int(np.count_nonzero(~ok))
    return {"level_mismatch": level_mismatch, "parent_bad": bad}


def control_answer(g: HostGraph, root: int):
    """The control: the reference with one guarantee broken.  The search
    stops one level short of the last (the shortcut of ending a search
    once its frontier is small); its tree is otherwise exact.  Returns
    ``(parent, level)``."""
    full = g.bfs_levels(root)
    level = g.bfs_levels(root, max_depth=max(int(full.max()) - 1, 0))
    parent = np.full(g.n, -1, np.int64)
    parent[root] = root
    # Each vertex's parent: its smallest neighbour one level closer (the
    # edge keys are sorted, so that is the first such edge of its row).
    s, d = g.keys // g.n, g.col
    up = (level[s] > 0) & (level[d] == level[s] - 1)
    s, d = s[up], d[up]
    first = np.flatnonzero(np.concatenate([[True], s[1:] != s[:-1]]))
    parent[s[first]] = d[first]
    return parent, level
