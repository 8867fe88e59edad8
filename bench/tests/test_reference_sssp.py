"""The kernel-3 reference: its weight draw is the program's, its
distances equal a brute-force Bellman-Ford, the program's answers
compare clean against it, its control fails, and the two SSSP readers
read what the driver hands them."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import gen, reference_sssp, registry
from bench.reference import HostGraph
from bench.run import Context
from bench.trace import Summary

SEED = 2**32 + 2**31 + 5     # more bits than an int32 holds
SCALE = 10


def bellman_ford(g: HostGraph, w, root: int) -> np.ndarray:
    """Integer distances by relaxing every edge until none improves."""
    inf = np.iinfo(np.int64).max // 2
    dist = np.full(g.n, inf, np.int64)
    dist[root] = 0
    s = g.keys // g.n
    for _ in range(g.n):
        cand = np.where(dist[s] < inf, dist[s] + w, inf)
        new = dist.copy()
        np.minimum.at(new, g.col, cand)
        if np.array_equal(new, dist):
            break
        dist = new
    return np.where(dist == inf, -1, dist)


@pytest.mark.parametrize("scale,seed", [(6, 1), (7, 2**31 + 3), (8, SEED)])
def test_distances_equal_bellman_ford(scale, seed):
    src, dst = gen.kronecker_edges(seed, scale)
    g = HostGraph(src, dst, 1 << scale)
    w = reference_sssp.slot_weights(g, seed)
    roots = [0, 3, g.n_active - 1, g.n - 1]     # the last is isolated
    got = reference_sssp.shortest_distances(g, w, roots)
    for root, d in zip(roots, got):
        np.testing.assert_array_equal(d, bellman_ford(g, w, root))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, SEED, 2**64 - 1])
def test_weight_draw_is_the_programs(seed):
    from repro.core.graph_build import WEIGHT_UNIT, edge_weights

    rng = np.random.default_rng(seed % 2**32)
    u = rng.integers(0, 2**26, 4096)
    v = rng.integers(0, 2**26, 4096)
    ours = reference_sssp.edge_weights(u, v, seed)
    theirs = np.asarray(edge_weights(u, v, np.ones(u.size, bool), seed=seed))
    np.testing.assert_array_equal(ours, theirs)
    np.testing.assert_array_equal(ours, reference_sssp.edge_weights(v, u, seed))
    assert ours.min() >= 1 and ours.max() <= 2**20 - 1
    assert reference_sssp.UNIT == WEIGHT_UNIT


@pytest.fixture(scope="module")
def answers():
    """The program's answers for the spec keys of a scale-10 graph, and
    the reference's graph, weights and distances for them."""
    from repro.core import pipeline
    from repro.core.plan import compile_plan

    cfg = pipeline.Graph500Config.ladder(
        "pre-g500", scale=SCALE, seed=SEED, n_roots=8, heavy_threshold=None,
        kernel="sssp")
    built = pipeline.build(cfg)
    compiled = compile_plan(cfg.to_plan(), built)
    src, dst = gen.kronecker_edges(SEED, SCALE)
    g = HostGraph(src, dst, 1 << SCALE)
    w = reference_sssp.slot_weights(g, SEED)
    roots = g.new_from_old[gen.search_keys(SEED, src, dst, 1 << SCALE, 8)]
    out = [compiled.bfs(int(r)) for r in roots]
    got = [(np.asarray(o.parent), np.asarray(o.level)) for o in out]
    return g, w, roots, got, reference_sssp.shortest_distances(g, w, roots)


def test_program_answers_compare_clean(answers):
    g, w, roots, got, ref = answers
    for root, (parent, dist), rd in zip(roots, got, ref):
        d = reference_sssp.compare(g, w, int(root), parent, dist, rd)
        assert d == {"dist_mismatch": 0, "parent_bad": 0}


def test_control_fails_the_comparison(answers):
    g, w, roots, _, ref = answers
    for root, rd in zip(roots, ref):
        parent, dist = reference_sssp.control_answer(g, w, int(root))
        d = reference_sssp.compare(g, w, int(root), parent, dist, rd)
        assert d["dist_mismatch"] > 0


def test_altered_answers_fail(answers):
    g, w, roots, got, ref = answers
    root, (parent, dist), rd = int(roots[0]), got[0], ref[0]
    far = int(np.argmax(dist))
    bumped = dist.copy()
    bumped[far] += 1
    assert reference_sssp.compare(g, w, root, parent, bumped, rd) == {
        "dist_mismatch": 1, "parent_bad": 0}
    moved = parent.copy()
    moved[far] = far
    assert reference_sssp.compare(g, w, root, moved, dist, rd) == {
        "dist_mismatch": 0, "parent_bad": 1}


SHAPES = {"sssp": {"rounds": 40, "scanned_edges": 3_000, "valid_slots": 500}}


@pytest.mark.parametrize("metric", ["sssp_round_ms.g500",
                                    "sssp_live_slot_share.g500"])
def test_readers_read_nothing_untraced(metric):
    mod = registry.reader(metric)
    assert mod.read(Context(SimpleNamespace(shapes=SHAPES), None, {})) is None
    traced = Summary(ops={"tpu0": [("fusion.1", 0, 10**9)]},
                     spans=[("window", 0, 2 * 10**9)])
    assert mod.read(Context(SimpleNamespace(shapes={}), traced, {})) is None


def test_readers_on_a_hand_made_context():
    # 1.5 s busy in a 2 s window, over 40 rounds of 500 valid slots.
    traced = Summary(ops={"tpu0": [("fusion.1", 0, 10**9),
                                   ("fusion.2", 5 * 10**8, 15 * 10**8)]},
                     spans=[("window", 0, 2 * 10**9)])
    ctx = Context(SimpleNamespace(shapes=SHAPES), traced, {})
    assert registry.reader("sssp_round_ms.g500").read(ctx) == \
        pytest.approx(1e3 * 1.5 / 40)
    assert registry.reader("sssp_live_slot_share.g500").read(ctx) == \
        pytest.approx(100.0 * 3_000 / (40 * 500))
