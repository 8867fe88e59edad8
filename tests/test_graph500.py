"""Core Graph500 pipeline: generator, construction, reorder, heavy core,
hybrid BFS vs independent host oracle, spec validation."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    BFSResult, Graph500Config, PreparedGraph, TraversalPlan, build,
    build_csr, build_heavy_core, chunk_edge_view, compile_plan,
    degree_reorder, edge_view, generate_edges, pack_bitmap, run,
    sample_roots, unpack_bitmap, validate,
)
from repro.core.graph_build import csr_to_edge_arrays
from repro.core.heavy import heavy_count
from repro.core.heavy import testbit as bit_at  # alias: pytest must not collect
from repro.core.reorder import relabel_edges, sort_host
from repro.core.reference import reference_bfs
from repro.core.teps import traversed_edges


@pytest.fixture(scope="module")
def small_graph():
    edges = generate_edges(3, 10)
    g = build_csr(edges)
    return edges, g


# Per-root and batched conveniences over the plan API.

def plan_bfs(ev, degree, root, *, core=None, engine="reference",
             alpha=14.0, beta=24.0, max_levels=64, chunks=None,
             n_chunks=64):
    p = TraversalPlan(engine=engine, layout=(), batch_roots=False,
                      alpha=alpha, beta=beta, max_levels=max_levels,
                      n_chunks=n_chunks)
    return compile_plan(p, PreparedGraph(
        ev=ev, degree=degree, core=core, chunks=chunks)).bfs(root)


def plan_batch(ev, degree, roots, *, core=None, chunks=None):
    p = TraversalPlan(layout=(), batch_roots=True)
    return compile_plan(p, PreparedGraph(
        ev=ev, degree=degree, core=core, chunks=chunks)).bfs(roots)


def test_kronecker_shapes_and_determinism():
    e1 = generate_edges(7, 9)
    e2 = generate_edges(7, 9)
    assert e1.num_edges == 16 << 9
    assert e1.num_vertices == 512
    np.testing.assert_array_equal(np.asarray(e1.src), np.asarray(e2.src))
    assert int(jnp.max(e1.src)) < 512 and int(jnp.min(e1.src)) >= 0


def test_kronecker_quadrant_skew():
    # A=0.57 concentrates mass at low ids: low half must dominate
    e = generate_edges(0, 12)
    frac_low = float(jnp.mean((e.src < 2048).astype(jnp.float32)))
    assert frac_low > 0.6


def test_csr_structure(small_graph):
    edges, g = small_graph
    ro = np.asarray(g.row_offsets)
    assert ro[0] == 0 and ro[-1] == int(g.nnz)
    assert np.all(np.diff(ro) >= 0)
    assert np.all(np.diff(ro) == np.asarray(g.degree))
    # symmetric: every valid (s,d) has (d,s)
    src, dst, valid = (np.asarray(x) for x in csr_to_edge_arrays(g))
    v = g.num_vertices
    fwd = {(a, b) for a, b, ok in zip(src, dst, valid) if ok}
    assert all((b, a) in fwd for (a, b) in fwd)
    # dedupe: no duplicates
    assert len(fwd) == int(g.nnz)
    # no self loops
    assert all(a != b for a, b in fwd)


def test_degree_reorder_is_permutation_sorted_desc(small_graph):
    _, g = small_graph
    r = degree_reorder(g.degree)
    old_from_new = np.asarray(r.old_from_new)
    assert sorted(old_from_new.tolist()) == list(range(g.num_vertices))
    ds = np.asarray(r.degree_sorted)
    assert np.all(np.diff(ds) <= 0)
    # isolated tail
    n_active = int(r.n_active)
    assert np.all(ds[:n_active] > 0)
    assert np.all(ds[n_active:] == 0)
    # new_from_old inverts old_from_new
    nfo = np.asarray(r.new_from_old)
    np.testing.assert_array_equal(nfo[old_from_new], np.arange(g.num_vertices))


def test_relabel_preserves_graph(small_graph):
    edges, g = small_graph
    r = degree_reorder(g.degree)
    e2 = relabel_edges(edges, r)
    g2 = build_csr(e2)
    assert int(g2.nnz) == int(g.nnz)
    # degree multiset preserved
    assert sorted(np.asarray(g2.degree).tolist()) == \
        sorted(np.asarray(g.degree).tolist())


def test_host_sorts_agree():
    rng = np.random.default_rng(0)
    deg = rng.integers(0, 50, size=200)
    perms = {alg: sort_host(deg, alg) for alg in ("merge", "quick", "bubble", "xla")}
    for alg, perm in perms.items():
        assert np.all(np.diff(deg[perm]) <= 0), alg
    # merge is stable: equal keys keep index order
    pm = perms["merge"]
    for i in range(len(pm) - 1):
        if deg[pm[i]] == deg[pm[i + 1]]:
            assert pm[i] < pm[i + 1]


def test_heavy_core_eq4_invariant():
    """{column} = {buffer_column} ∪ {rest_column}, disjoint (paper eq. 4)."""
    edges = generate_edges(5, 11)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    core = build_heavy_core(g, threshold=8)
    src, dst, valid = (np.asarray(x) for x in csr_to_edge_arrays(g))
    k = core.k
    a = np.asarray(core.a_core)
    halo_valid = np.asarray(core.halo_valid)
    in_core_count = 0
    for s, d, ok in zip(src, dst, valid):
        if not ok or s >= k:
            continue
        if d < k:
            word = a[s, d // 32]
            assert (word >> (d % 32)) & 1 == 1
            in_core_count += 1
    assert in_core_count == int(core.core_nnz)
    # halo and core partition the core-row edges
    n_core_rows_edges = sum(1 for s, ok in zip(src, valid) if ok and s < k)
    assert in_core_count + int(halo_valid.sum()) == n_core_rows_edges
    # heavy count consistent with threshold
    deg_sorted = np.asarray(g.degree)
    assert int(heavy_count(g.degree, 8)) == int((deg_sorted >= 8).sum())


def test_bitmap_pack_unpack_roundtrip():
    rng = np.random.default_rng(3)
    mask = jnp.asarray(rng.random(1000) < 0.3)
    bm = pack_bitmap(mask, 32)
    back = unpack_bitmap(bm, 1000)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(mask))
    idx = jnp.asarray(rng.integers(0, 1000, 100))
    np.testing.assert_array_equal(
        np.asarray(bit_at(bm, idx)), np.asarray(mask)[np.asarray(idx)])


@pytest.mark.parametrize("engine,threshold", [
    ("reference", None), ("bitmap", 8), ("bitmap", 4)])
@pytest.mark.parametrize("scale", [8, 10])
def test_hybrid_bfs_matches_host_oracle(engine, threshold, scale):
    edges = generate_edges(11, scale)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    core = build_heavy_core(g, threshold=threshold) if threshold else None
    ev = edge_view(g)
    ro, ci = np.asarray(g.row_offsets), np.asarray(g.col_indices)
    for root in (0, 3, 17):
        res = plan_bfs(ev, g.degree, root, core=core, engine=engine)
        _, l_ref = reference_bfs(ro, ci, root)
        np.testing.assert_array_equal(np.asarray(res.level), l_ref,
                                      err_msg=f"root={root}")
        val = validate(ev, res, jnp.int32(root))
        assert bool(val.ok), {k: bool(getattr(val, k)) for k in val._fields}


@pytest.mark.parametrize("batched", [False, True], ids=["per_root", "batch"])
@pytest.mark.parametrize("family", ["grid", "erdos_renyi"])
def test_bitmap_engine_matches_host_oracle_on_family(family, batched):
    """Graphs that break the Kronecker assumptions: the 2-D grid is
    high-diameter (long top-down stretches, tiny frontiers), the
    Erdős–Rényi graph has no heavy tail.  Both stay under the heavy
    threshold, so the dense core is bypassed as ``pipeline.build`` does
    for them; levels must equal the host BFS and every tree validate."""
    from repro.data.graphs import erdos_renyi_graph, grid_graph

    el = (grid_graph(20, seed=5) if family == "grid"
          else erdos_renyi_graph(400, avg_degree=6, seed=7))
    g0 = build_csr(el)
    g = build_csr(relabel_edges(el, degree_reorder(g0.degree)))
    assert int(heavy_count(g.degree, 100)) == 0
    ev = edge_view(g)
    ro, ci = np.asarray(g.row_offsets), np.asarray(g.col_indices)
    roots = np.array([0, 3, 11], np.int32)
    if batched:
        res = plan_batch(ev, g.degree, roots)
        rows = [(res.parent[i], res.level[i]) for i in range(len(roots))]
    else:
        rows = []
        for root in roots:
            r1 = plan_bfs(ev, g.degree, int(root), engine="bitmap")
            rows.append((r1.parent, r1.level))
            if family == "grid" and root == 0:
                lv = int(r1.stats.levels)
                dirs = np.asarray(r1.stats.direction)[:lv]
                assert lv > 20, lv
                assert (dirs[:8] == 0).all(), dirs.tolist()
    for root, (parent, level) in zip(roots, rows):
        _, l_ref = reference_bfs(ro, ci, int(root))
        np.testing.assert_array_equal(np.asarray(level), l_ref,
                                      err_msg=f"root={root}")
        val = validate(ev, BFSResult(parent=parent, level=level, stats=None),
                       jnp.int32(root))
        assert bool(val.ok), {k: bool(getattr(val, k)) for k in val._fields}


def test_hybrid_switches_direction():
    edges = generate_edges(5, 12)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    ev = edge_view(g)
    res = plan_bfs(ev, g.degree, 0, alpha=14.0, beta=24.0)
    dirs = np.asarray(res.stats.direction)[: int(res.stats.levels)]
    assert 0 in dirs and 1 in dirs, dirs  # both directions used


def test_validation_catches_corruption():
    edges = generate_edges(13, 8)
    g = build_csr(edges)
    ev = edge_view(g)
    res = plan_bfs(ev, g.degree, 1)
    ok = validate(ev, res, jnp.int32(1))
    assert bool(ok.ok)
    # corrupt: point a visited vertex at a non-neighbor
    parent = np.asarray(res.parent).copy()
    visited = np.where(parent >= 0)[0]
    victim = visited[-1]
    if victim != 1:
        parent[victim] = victim  # self-parent non-root -> depth check fails
        bad = res._replace(parent=jnp.asarray(parent))
        assert not bool(validate(ev, bad, jnp.int32(1)).ok)
    # corrupt level parity
    level = np.asarray(res.level).copy()
    if len(visited) > 2:
        level[visited[2]] += 1
        bad = res._replace(level=jnp.asarray(level))
        assert not bool(validate(ev, bad, jnp.int32(1)).ok)


def test_end_to_end_pipeline_ladder():
    for rung in ("reference-3.0.0", "th2", "pre-g500"):
        cfg = Graph500Config.ladder(rung, scale=9, n_roots=2)
        built, result = run(cfg)
        assert result.all_valid, rung
        assert result.harmonic_mean_teps > 0, rung


def test_traversed_edges_counts_component():
    edges = generate_edges(17, 9)
    g = build_csr(edges)
    ev = edge_view(g)
    res = plan_bfs(ev, g.degree, int(np.asarray(sample_roots(0, edges, 1))[0]))
    m = int(traversed_edges(g.degree, res))
    assert 0 < m <= int(g.nnz) // 2


# ---------------------------------------------------------------------------
# Bitmap-resident engine acceptance (DESIGN.md §3).
# ---------------------------------------------------------------------------

def _sorted_graph(scale, seed=11, threshold=32):
    edges = generate_edges(seed, scale)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    core = build_heavy_core(g, threshold=threshold)
    ev = edge_view(g)
    return g, ev, core, chunk_edge_view(ev)


@pytest.mark.parametrize("scale", [12, 14])
def test_bitmap_engine_byte_identical_to_reference(scale):
    threshold = 100 if scale >= 13 else 32
    g, ev, core, chunks = _sorted_graph(scale, threshold=threshold)
    roots = (0, 17) if scale == 12 else (0,)
    for root in roots:
        ref = plan_bfs(ev, g.degree, root, engine="reference")
        res = plan_bfs(ev, g.degree, root, core=core, engine="bitmap",
                         chunks=chunks)
        np.testing.assert_array_equal(
            np.asarray(res.parent), np.asarray(ref.parent),
            err_msg=f"parent scale={scale} root={root}")
        np.testing.assert_array_equal(
            np.asarray(res.level), np.asarray(ref.level),
            err_msg=f"level scale={scale} root={root}")
        assert bool(validate(ev, res, jnp.int32(root)).ok)


def _hub_graph():
    """Vertex 0 joined to 3998 others, so its row spans four tiles of the
    bottom-up pull and crosses the first of four chunk boundaries; vertex
    17 and 4000-4095 isolated; self loops and a duplicate that the build
    turns into padding slots."""
    from repro.core import EdgeList

    n = 4096
    rng = np.random.default_rng(5)
    spokes = np.setdiff1d(np.arange(1, 4000), [17])
    a, b = (np.where(x == 17, 18, x) for x in rng.integers(1, 4000, (2, 93)))
    src = np.concatenate([np.zeros_like(spokes), a, [7, 8, 9, 11], [0]])
    dst = np.concatenate([spokes, b, [7, 8, 9, 11], [5]])
    g = build_csr(EdgeList(src=jnp.asarray(src, jnp.int32),
                           dst=jnp.asarray(dst, jnp.int32), num_vertices=n))
    return g, chunk_edge_view(edge_view(g), 4)


@pytest.mark.parametrize("visited", ["random", "all"])
@pytest.mark.parametrize("case", ["pre-g500-s14", "hub", "hub-core64"])
def test_bottom_up_pull_matches_push(case, visited):
    """The bottom-up pull over src-sorted rows gives the same parents as
    the push relax over the same tail slots, on random frontier and
    visited bitmaps (DESIGN.md §3: I4 holds by symmetry)."""
    import importlib
    hb = importlib.import_module("repro.core.hybrid_bfs")
    from repro.core.heavy import padded_bitmap_words

    if case == "pre-g500-s14":
        g, _, core, chunks = _sorted_graph(14, threshold=100)
        core_k = core.k
    else:
        g, chunks = _hub_graph()
        core_k = 64 if case == "hub-core64" else None
    v = g.num_vertices
    src, dst = chunks.src.reshape(-1), chunks.dst.reshape(-1)
    tail = chunks.valid.reshape(-1)
    if core_k is not None:
        tail = tail & ~((src < core_k) & (dst < core_k))
    deg = np.asarray(g.degree)
    assert 0 < int(tail.sum()) and (deg == 0).any()
    assert not bool(chunks.valid.reshape(-1)[-1])      # padding slots
    if case != "pre-g500-s14":
        tile = hb.PULL_TILE
        assert chunks.chunk_size % tile == 0 and deg[0] >= 3 * tile
        assert deg[0] > chunks.chunk_size and deg[17] == 0

    row_end = hb._row_ends(g.degree)
    push = jax.jit(lambda f, vis, p: hb._relax_edges(
        src, dst, tail, f, vis, p, v))
    pull = jax.jit(lambda f, vis, p: hb._pull_relax(
        chunks, core_k, row_end, f, vis, p, v))
    w = padded_bitmap_words(v)
    rng = np.random.default_rng(0)
    for _ in range(3):
        vm = (np.ones(v, bool) if visited == "all"
              else rng.random(v) < rng.uniform(0.05, 0.95))
        fm = vm & (rng.random(v) < rng.uniform(0.05, 1.0))
        ids = rng.integers(0, v, v + 1)
        # unvisited rows hold the sentinel v, or a core-step winner
        p = np.where(np.append(vm, True) | (rng.random(v + 1) < 0.1), ids, v)
        p[v] = v
        args = (pack_bitmap(jnp.asarray(fm), w),
                pack_bitmap(jnp.asarray(vm), w), jnp.asarray(p, jnp.int32))
        want = np.asarray(push(*args))
        np.testing.assert_array_equal(np.asarray(pull(*args)), want)
        if visited == "random":
            assert (want != p).any()   # the draw relaxed something


def test_bitmap_engine_never_packs_inside_loop(monkeypatch):
    """Zero pack_bitmap calls in the bitmap engine's traced program: the
    resident frontier/visited state never round-trips through bool (the
    epilogue packs only the per-level delta — DESIGN.md §3 I3).  Every
    loaded ``repro`` module's binding of the symbol is instrumented, so
    a ``from ... import pack_bitmap`` anywhere in the library is caught;
    a traced program that does pack is the liveness control."""
    import sys

    from repro.core import heavy

    g, ev, core, chunks = _sorted_graph(9)
    calls = []
    real = heavy.pack_bitmap

    def counting(*a, **k):
        calls.append(1)
        return real(*a, **k)

    bound = [m for name, m in list(sys.modules.items())
             if name.startswith("repro")
             and getattr(m, "pack_bitmap", None) is real]
    assert heavy in bound
    for m in bound:
        monkeypatch.setattr(m, "pack_bitmap", counting)
    # unusual max_levels forces a fresh trace while the counter is active
    res = plan_bfs(ev, g.degree, 0, core=core, engine="bitmap",
                     chunks=chunks, max_levels=61)
    assert bool(validate(ev, res, jnp.int32(0)).ok)
    assert len(calls) == 0, "bitmap engine packed inside the loop"
    w = heavy.padded_bitmap_words(g.num_vertices)
    jax.jit(lambda m: heavy.pack_bitmap(m, w))(
        jnp.zeros((g.num_vertices,), bool))
    assert len(calls) > 0, "instrumentation dead — counter never fired"


def test_chunked_top_down_skips_work():
    """Small-frontier top-down levels must touch < 25% of edge chunks on a
    degree-sorted graph (frontier-proportional scanning)."""
    g, ev, core, chunks = _sorted_graph(12)
    res = plan_bfs(ev, g.degree, 0, core=core, engine="bitmap",
                     chunks=chunks)
    lv = int(res.stats.levels)
    dirs = np.asarray(res.stats.direction)[:lv]
    fs = np.asarray(res.stats.frontier_size)[:lv]
    ch = np.asarray(res.stats.scanned_chunks)[:lv]
    total = int(res.stats.total_chunks)
    assert total == chunks.n_chunks
    small_td = (dirs == 0) & (fs < g.num_vertices // 100)
    assert small_td.any(), (dirs.tolist(), fs.tolist())
    assert np.all(ch[small_td] < 0.25 * total), \
        f"chunks={ch.tolist()} dirs={dirs.tolist()} fs={fs.tolist()}"


def test_bfs_batch_matches_single_runs():
    g, ev, core, chunks = _sorted_graph(10)
    roots = np.asarray([0, 3, 17, 29], np.int32)
    batched = plan_batch(ev, g.degree, roots, core=core, chunks=chunks)
    for i, root in enumerate(roots):
        single = plan_bfs(ev, g.degree, int(root), core=core,
                            engine="bitmap", chunks=chunks)
        np.testing.assert_array_equal(
            np.asarray(batched.parent[i]), np.asarray(single.parent))
        np.testing.assert_array_equal(
            np.asarray(batched.level[i]), np.asarray(single.level))
        assert int(batched.stats.levels[i]) == int(single.stats.levels)


def test_bfs_batch_64_roots_one_jit():
    """Graph500-spec batch width: all 64 search keys in a single program."""
    g, ev, core, chunks = _sorted_graph(9, threshold=8)
    roots = np.arange(64, dtype=np.int32)  # heaviest 64 ids: degree >= 1
    res = plan_batch(ev, g.degree, roots, core=core, chunks=chunks)
    assert res.parent.shape == (64, g.num_vertices)
    assert res.level.shape == (64, g.num_vertices)
    for i in (0, 31, 63):  # spot-check against single runs
        single = plan_bfs(ev, g.degree, int(roots[i]), core=core,
                            engine="bitmap", chunks=chunks)
        np.testing.assert_array_equal(
            np.asarray(res.parent[i]), np.asarray(single.parent))


def test_batched_runner_reports_harmonic_mean():
    edges = generate_edges(11, 10)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    core = build_heavy_core(g, threshold=32)
    ev = edge_view(g)
    roots = np.asarray(r.new_from_old)[np.asarray(sample_roots(3, edges, 8))]
    g500 = compile_plan(
        TraversalPlan(layout=(), batch_roots=True),
        PreparedGraph(ev=ev, degree=g.degree, core=core)).run(roots).run
    assert g500.batched
    assert len(g500.teps) == len(roots)
    assert g500.all_valid
    t = np.asarray(g500.teps)
    expected = len(t) / np.sum(1.0 / t)
    assert np.isclose(g500.harmonic_mean_teps, expected)
    assert g500.harmonic_mean_teps > 0


def test_pipeline_batched_rung():
    cfg = Graph500Config.ladder("pre-g500-batch", scale=9, n_roots=4)
    _, result = run(cfg)
    assert result.batched and result.all_valid
    assert result.harmonic_mean_teps > 0
