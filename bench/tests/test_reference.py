"""The plain reference agrees with the program on a sound run, its
control and altered answers fail the comparison, and the copied TEPS
arithmetic equals ``core/teps.py``."""
import numpy as np
import pytest

from bench import gen, reference
from bench.reference import HostGraph, compare

SEED = 2**31 + 7
SCALE = 10


@pytest.fixture(scope="module")
def graphs():
    from repro.core import pipeline
    from repro.core.plan import compile_plan

    cfg = pipeline.Graph500Config.ladder(
        "pre-g500", scale=SCALE, edge_factor=16, seed=SEED, n_roots=8)
    built = pipeline.build(cfg)
    src, dst = gen.kronecker_edges(SEED, SCALE)
    g = HostGraph(src, dst, 1 << SCALE)
    keys = gen.search_keys(SEED, src, dst, 1 << SCALE, 8)
    return g, built, compile_plan(cfg.to_plan(), built), keys


def test_reference_graph_matches_program_build(graphs):
    g, built, _, keys = graphs
    np.testing.assert_array_equal(g.degree, np.asarray(built.degree))
    np.testing.assert_array_equal(g.new_from_old,
                                  np.asarray(built.reorder.new_from_old))
    assert g.keys.size == built.nnz


def test_reference_levels_equal_scipy_distances(graphs):
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import shortest_path

    g = graphs[0]
    adj = csr_matrix((np.ones(g.col.size), g.col, g.indptr), shape=(g.n, g.n))
    for root in (0, 5, g.n_active - 1):
        d = shortest_path(adj, unweighted=True, indices=root)
        want = np.where(np.isinf(d), -1, d).astype(np.int32)
        np.testing.assert_array_equal(g.bfs_levels(root), want)


def test_program_answers_compare_clean(graphs):
    g, built, compiled, keys = graphs
    for k in g.new_from_old[keys]:
        res = compiled.bfs(int(k))
        d = compare(g, int(k), np.asarray(res.parent), np.asarray(res.level),
                    g.bfs_levels(int(k)))
        assert d == {"level_mismatch": 0, "parent_bad": 0}


def test_control_fails_the_comparison(graphs):
    g, _, _, keys = graphs
    for k in g.new_from_old[keys]:
        parent, level = reference.control_answer(g, int(k))
        d = compare(g, int(k), parent, level, g.bfs_levels(int(k)))
        assert d["level_mismatch"] > 0 and d["parent_bad"] > 0


@pytest.mark.parametrize("fault", ["wrong_parent", "wrong_level",
                                   "unreached_parent", "root_parent",
                                   "short_row"])
def test_altered_answer_fails(graphs, fault):
    g, _, compiled, keys = graphs
    k = int(g.new_from_old[keys[0]])
    res = compiled.bfs(k)
    parent, level = np.array(res.parent), np.array(res.level)
    ref = g.bfs_levels(k)
    v = int(np.flatnonzero(ref == ref.max())[0])
    if fault == "wrong_parent":
        parent[v] = v
    elif fault == "wrong_level":
        level[v] += 1
    elif fault == "unreached_parent":
        u = np.flatnonzero(ref < 0)
        parent[u[0] if u.size else v] = k if u.size else -1
    elif fault == "root_parent":
        parent[k] = v
    else:
        parent, level = parent[:-1], level[:-1]
    d = compare(g, k, parent, level, ref)
    assert d["level_mismatch"] + d["parent_bad"] > 0


def test_teps_arithmetic_equals_program():
    import jax.numpy as jnp

    from repro.core.hybrid_bfs import BFSResult
    from repro.core.teps import Graph500Run, traversed_edges

    rng = np.random.default_rng(0)
    degree = rng.integers(0, 50, 1000).astype(np.int32)
    parents = np.where(rng.random((6, 1000)) < 0.7,
                       rng.integers(0, 1000, (6, 1000)), -1).astype(np.int32)
    for p in parents:
        want = int(traversed_edges(jnp.asarray(degree),
                                   BFSResult(parent=jnp.asarray(p),
                                             level=None, stats=None)))
        assert reference.traversed_edges(degree, p) == want
    teps = [3.0e6, 5.5e6, 7.25e6, 4.0e6]
    assert reference.harmonic_mean(teps) == pytest.approx(
        Graph500Run(teps=teps).harmonic_mean_teps, rel=1e-15)
    # A search with no edges is not dropped, as the program drops it.
    assert reference.harmonic_mean(teps + [0.0]) == 0.0
    assert reference.harmonic_mean([]) == 0.0
