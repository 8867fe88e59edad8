"""SSSP kernel tests (DESIGN.md §16): δ-stepping as the second kernel.

The contract is bitwise: every engine path — single-device (batch and
per-root), vertex-sharded under both partitions and both min-family
exchanges, the composed 3-axis layout, and the 2-process launcher gang —
must produce parents AND distances exactly equal to the host Dijkstra +
min-source-parent oracle (:func:`repro.core.sssp_steps.sssp_oracle`).
Multi-device cases run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps seeing 1 device.

The fault leg reuses the §13 machinery unchanged: an exchange fault on
the sharded distance min-combine must be *detected* by ``check="full"``
(distance corruption attributed to the SSSP check names) and *recovered*
bitwise by the degraded single-device fallback; a parent fault that
survives the fallback must quarantine.

The non-Kronecker families (``repro.data.graphs``) ride here: the 2-D
grid is the high-diameter, many-bucket regime Kronecker never produces.
"""
import os
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (
    PreparedGraph, TraversalPlan, build_csr, chunk_edge_view, compile_plan,
    edge_view, generate_edges, sssp_oracle, with_edge_weights,
)
from repro.core.reorder import degree_reorder, relabel_edges

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from repro.util import respawn_with_host_devices  # noqa: E402

SCALE = 8
ROOTS = 4


def run_sub(code: str) -> str:
    out = respawn_with_host_devices(
        [sys.executable, "-c", textwrap.dedent(code)], 8,
        pythonpath=(REPO_SRC,), capture=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def weighted_graph(scale=SCALE, seed=3, wseed=1):
    edges = generate_edges(seed, scale)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    ev = with_edge_weights(edge_view(g), seed=wseed,
                           old_from_new=r.old_from_new)
    return g, ev


def oracle_planes(g, ev, roots):
    V = g.num_vertices
    par = np.empty((len(roots), V), np.int32)
    dist = np.empty((len(roots), V), np.int32)
    for i, root in enumerate(roots):
        p, d = sssp_oracle(ev.src, ev.dst, ev.valid, ev.weight, V, int(root))
        par[i], dist[i] = p, d
    return par, dist


def assert_oracle_parity(res, g, o_par, o_dist, what=""):
    V = g.num_vertices
    assert np.array_equal(np.asarray(res.parent)[:, :V], o_par), what
    assert np.array_equal(np.asarray(res.level)[:, :V], o_dist), what


# ---------------------------------------------------------------------------
# Single device: batch + per-root, Kronecker + both synthetic families
# ---------------------------------------------------------------------------

def test_single_device_batch_and_per_root_match_oracle():
    g, ev = weighted_graph()
    pg = PreparedGraph(ev=ev, degree=g.degree, core=None,
                       chunks=chunk_edge_view(ev))
    roots = np.arange(ROOTS, dtype=np.int32)
    o_par, o_dist = oracle_planes(g, ev, roots)
    batch = compile_plan(TraversalPlan(layout=(), batch_roots=True,
                                       kernel="sssp"), pg).bfs(roots)
    assert_oracle_parity(batch, g, o_par, o_dist, "batch")
    single = compile_plan(TraversalPlan(layout=(), batch_roots=False,
                                        kernel="sssp"), pg)
    for i, root in enumerate(roots):
        res = single.bfs(int(root))
        assert np.array_equal(np.asarray(res.parent)[:g.num_vertices],
                              o_par[i])
        assert np.array_equal(np.asarray(res.level)[:g.num_vertices],
                              o_dist[i])


@pytest.mark.parametrize("family", ["grid", "erdos_renyi"])
def test_synthetic_families_match_oracle(family):
    """The non-Kronecker families (§16): the 2-D grid drives the bucket
    count past anything small-world — the engine's round bound and the
    oracle must still agree bitwise."""
    from repro.data.graphs import erdos_renyi_graph, grid_graph

    el = (grid_graph(20, seed=5) if family == "grid"
          else erdos_renyi_graph(400, avg_degree=6, seed=7))
    g = build_csr(el)
    ev = with_edge_weights(edge_view(g), seed=2)
    pg = PreparedGraph(ev=ev, degree=g.degree, core=None,
                       chunks=chunk_edge_view(ev))
    roots = np.array([0, 3, 11], np.int32)
    o_par, o_dist = oracle_planes(g, ev, roots)
    res = compile_plan(TraversalPlan(layout=(), batch_roots=True,
                                     kernel="sssp"), pg).bfs(roots)
    assert_oracle_parity(res, g, o_par, o_dist, family)
    if family == "grid":
        # the grid's diameter must show up as a many-round traversal
        single = compile_plan(TraversalPlan(layout=(), batch_roots=False,
                                            kernel="sssp"), pg).bfs(0)
        assert int(single.stats.levels) > 20


def test_families_are_deterministic_in_seed():
    from repro.data.graphs import erdos_renyi_graph, grid_graph

    a, b = grid_graph(8, seed=3), grid_graph(8, seed=3)
    assert np.array_equal(np.asarray(a.src), np.asarray(b.src))
    assert np.array_equal(np.asarray(a.dst), np.asarray(b.dst))
    c = grid_graph(8, seed=4)
    assert not np.array_equal(np.asarray(a.src), np.asarray(c.src))
    e1, e2 = (erdos_renyi_graph(100, seed=9) for _ in range(2))
    assert np.array_equal(np.asarray(e1.src), np.asarray(e2.src))


# ---------------------------------------------------------------------------
# Min-source parents on ties: every engine, bitwise, on a disconnected graph
# ---------------------------------------------------------------------------

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def tie_heavy_cases():
    """Two disjoint copies of one Kronecker graph, so a root in the first
    leaves the second's sources unreached, under two tie-heavy
    weightings: every weight 1, and symmetric weights in [1, 3].  Roots:
    the first copy's hub and a leaf, the second copy's hub, and an
    isolated vertex."""
    import jax.numpy as jnp

    from repro.core.kronecker import EdgeList

    a = generate_edges(5, 7)
    n = a.num_vertices
    g = build_csr(EdgeList(jnp.concatenate([a.src, a.src + n]),
                           jnp.concatenate([a.dst, a.dst + n]), 2 * n))
    ev = edge_view(g)
    degree = np.asarray(g.degree)
    roots = np.array([0, int(np.flatnonzero(degree[:n] == 1)[0]), n,
                      int(np.flatnonzero(degree == 0)[0])], np.int32)
    lo, hi = jnp.minimum(ev.src, ev.dst), jnp.maximum(ev.src, ev.dst)
    weights = {"unit": jnp.ones_like(ev.src),
               "one_to_three": 1 + (lo * 7 + hi * 13) % 3}
    return [(name, g, ev.__class__(ev.src, ev.dst, ev.valid, ev.num_vertices,
                                   jnp.where(ev.valid, w, 0)
                                   .astype(jnp.uint32)), roots)
            for name, w in weights.items()]


def check_tie_parity(engine: str) -> int:
    """Run ``engine`` on every tie-heavy case and hold parents and
    distances to the oracle bitwise; returns the cases checked."""
    n_ok = 0
    for name, g, ev, roots in tie_heavy_cases():
        V = g.num_vertices
        o_par, o_dist = oracle_planes(g, ev, roots)
        # The case is what it claims: many vertices have several
        # shortest-path parents, and reached roots leave sources with
        # edges unreached.
        s, d, w = (np.asarray(x)[np.asarray(ev.valid)].astype(np.int64)
                   for x in (ev.src, ev.dst, ev.weight))
        ds, dd = o_dist[0][s], o_dist[0][d]
        wins = (ds >= 0) & (ds + w == dd)
        assert np.count_nonzero(np.bincount(d[wins], minlength=V) > 1) \
            > np.count_nonzero(o_dist[0] >= 0) // 10, name
        assert np.any(o_dist[0][s] < 0), name
        if engine == "sharded_2x2":
            plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2),
                                 batch_roots=True, kernel="sssp")
            pg = PreparedGraph(ev=ev, degree=g.degree, core=None)
        else:
            plan = TraversalPlan(layout=(), batch_roots=engine == "batched",
                                 kernel="sssp")
            pg = PreparedGraph(ev=ev, degree=g.degree, core=None,
                               chunks=chunk_edge_view(ev))
        compiled = compile_plan(plan, pg)
        if engine == "per_root":
            runs = [compiled.bfs(int(r)) for r in roots]
            par = np.stack([np.asarray(r.parent)[:V] for r in runs])
            dist = np.stack([np.asarray(r.level)[:V] for r in runs])
        else:
            res = compiled.bfs(roots)
            par = np.asarray(res.parent)[:, :V]
            dist = np.asarray(res.level)[:, :V]
        assert np.array_equal(par, o_par), (engine, name)
        assert np.array_equal(dist, o_dist), (engine, name)
        n_ok += 1
    return n_ok


TIE_SHARDED = f"""
import sys
sys.path.insert(0, {TESTS_DIR!r})
from test_sssp import check_tie_parity
print(f"TIES_OK n={{check_tie_parity('sharded_2x2')}}")
"""


@pytest.mark.parametrize("engine", ["per_root", "batched", "sharded_2x2"])
def test_tie_heavy_disconnected_parents_match_oracle(engine):
    """Parents are built once per search from the final distances: on
    weightings where many sources reach a vertex at equal distance, and
    with sources the search never reaches, every engine gives the
    oracle's min-source parents and distances bit for bit."""
    if engine == "sharded_2x2":
        assert "TIES_OK n=2" in run_sub(TIE_SHARDED)
    else:
        assert check_tie_parity(engine) == 2


# ---------------------------------------------------------------------------
# Graph500 kernel 3 through the normal path: pipeline.build -> compile_plan
# -> CompiledBFS.bfs, with the spec's weights
# ---------------------------------------------------------------------------

SPEC_SCALE = 11
SPEC_SEED = 2**31 + 9


@pytest.fixture(scope="module")
def spec_builds():
    """The kernel-3 configuration built with and without the degree
    relabelling, and its per-root plan."""
    from repro.core import pipeline

    cfgs = {sort: pipeline.Graph500Config.ladder(
        "pre-g500", scale=SPEC_SCALE, seed=SPEC_SEED, n_roots=4,
        degree_sort=sort, heavy_threshold=None, kernel="sssp")
        for sort in (True, False)}
    return {sort: (cfg, pipeline.build(cfg)) for sort, cfg in cfgs.items()}


def original_pair_weights(built) -> dict:
    """{(min, max) original endpoint ids: weight} over the valid slots."""
    V = built.n_vertices
    old = (np.arange(V) if built.reorder is None
           else np.asarray(built.reorder.old_from_new))
    valid = np.asarray(built.ev.valid)
    s = old[np.asarray(built.ev.src)[valid]]
    d = old[np.asarray(built.ev.dst)[valid]]
    w = np.asarray(built.ev.weight)[valid]
    return dict(zip(zip(np.minimum(s, d).tolist(), np.maximum(s, d).tolist()),
                    w.tolist()))


def test_spec_weights_on_the_grid_symmetric_and_layout_free(spec_builds):
    from repro.core import WEIGHT_UNIT

    assert WEIGHT_UNIT == 2.0 ** -20
    pairs = {}
    for sort, (_, built) in spec_builds.items():
        ev = built.ev
        valid = np.asarray(ev.valid)
        w = np.asarray(ev.weight)
        assert w.dtype == np.uint32
        assert not w[~valid].any()
        real = w[valid] * WEIGHT_UNIT          # k * 2**-20, k an integer
        assert real.min() > 0 and real.max() < 1
        assert w[valid].min() >= 1 and w[valid].max() <= 2**20 - 1
        # symmetric: each undirected pair's two slots carry one weight
        n = built.n_vertices
        src = np.asarray(ev.src)[valid].astype(np.int64)
        dst = np.asarray(ev.dst)[valid].astype(np.int64)
        fwd = dict(zip((src * n + dst).tolist(), w[valid].tolist()))
        assert all(fwd[b * n + a] == x for a, b, x in
                   zip(src.tolist(), dst.tolist(), w[valid].tolist()))
        pairs[sort] = original_pair_weights(built)
    # the relabelling does not change the problem
    assert pairs[True] == pairs[False]
    # and the draw spreads over (0, 1)
    ks = np.fromiter(pairs[True].values(), np.int64)
    assert abs(ks.mean() * WEIGHT_UNIT - 0.5) < 0.02


def test_pipeline_sssp_equals_the_reference(spec_builds):
    """Distances equal the host Dijkstra exactly, and parents are the
    min-source shortest-path parents, with the reference's weights drawn
    here over the original ids."""
    from repro.core.graph_build import edge_weights
    from repro.core.plan import compile_plan

    cfg, built = spec_builds[True]
    V = built.n_vertices
    ev = built.ev
    old = np.append(np.asarray(built.reorder.old_from_new), V)
    src, dst, valid = (np.asarray(ev.src), np.asarray(ev.dst),
                       np.asarray(ev.valid))
    w = np.asarray(edge_weights(old[src], old[dst], valid, seed=SPEC_SEED))
    compiled = compile_plan(cfg.to_plan(), built)
    assert not compiled.plan.batch_roots and compiled.graph.core is None
    degree = np.asarray(built.degree)
    for root in (0, 1, int(np.count_nonzero(degree)) - 1, V - 1):
        res = compiled.bfs(root)
        p, d = sssp_oracle(src, dst, valid, w, V, root)
        np.testing.assert_array_equal(np.asarray(res.parent), p)
        np.testing.assert_array_equal(np.asarray(res.level), d)
        assert int(res.stats.levels) >= (2 if degree[root] else 1)


@pytest.mark.parametrize("case", ["pre-g500-s14", "hub"])
def test_pass_a_pull_matches_push(case):
    """Pass A's pull over the src-sorted rows gives the distances the
    push ``dist.at[dst].min(dist[src] + w)`` over the frontier's slots
    gives, on random frontiers over random distances with some vertices
    unreached: the graph and its weights are symmetric."""
    import jax
    import jax.numpy as jnp

    from repro.core import pipeline
    from repro.core.hybrid_bfs import PULL_TILE, _row_ends
    from repro.core.sssp_steps import _pull_dist
    from test_graph500 import _hub_graph

    if case == "pre-g500-s14":
        cfg = pipeline.Graph500Config.ladder(
            "pre-g500", scale=14, n_roots=1, heavy_threshold=None,
            kernel="sssp")
        built = pipeline.build(cfg)
        chunks = compile_plan(cfg.to_plan(), built).graph.chunks
        degree = built.degree
    else:
        g, _ = _hub_graph()
        chunks = chunk_edge_view(with_edge_weights(edge_view(g), seed=5), 4)
        degree = g.degree
    v = chunks.num_vertices
    src, dst, valid, wgt = (a.reshape(-1) for a in (
        chunks.src, chunks.dst, chunks.valid, chunks.weight))
    deg = np.asarray(degree)
    assert (deg == 0).any() and not bool(valid[-1])    # padding slots
    if case == "hub":
        assert chunks.chunk_size % PULL_TILE == 0
        assert deg[0] >= 3 * PULL_TILE and deg[0] > chunks.chunk_size

    inf = np.uint32(0xFFFFFFFF)
    row_end = _row_ends(degree)

    @jax.jit
    def push(front, dist):
        dist_ext = jnp.concatenate([dist, jnp.full((1,), inf)])
        active = valid & jnp.append(front, False)[src]
        cand = jnp.where(active, dist_ext[src] + wgt, inf)
        return dist_ext.at[jnp.where(active, dst, v)].min(cand)[:v]

    pull = jax.jit(lambda front, dist: _pull_dist(chunks, row_end, front,
                                                  dist))
    rng = np.random.default_rng(0)
    for _ in range(3):
        reached = rng.random(v) < rng.uniform(0.1, 0.9)
        dist = np.where(reached, rng.integers(0, 3 << 20, v), inf)
        # a frontier holds reached vertices only (a bucket of them)
        front = reached & (rng.random(v) < rng.uniform(0.05, 1.0))
        args = (jnp.asarray(front), jnp.asarray(dist, jnp.uint32))
        want = np.asarray(push(*args))
        np.testing.assert_array_equal(np.asarray(pull(*args)), want)
        assert (want < dist).any()   # the draw relaxed something


# ---------------------------------------------------------------------------
# Plan layer: the kernel axis
# ---------------------------------------------------------------------------

def test_plan_kernel_axis_validation_and_shims():
    from repro.core.kernels import rekernel_plan
    from repro.core.plan import validate_plan

    with pytest.raises(ValueError, match="unknown kernel"):
        validate_plan(TraversalPlan(kernel="apsp"))
    with pytest.raises(ValueError, match="unknown engine"):
        validate_plan(TraversalPlan(engine="reference", kernel="sssp"))
    with pytest.raises(ValueError, match="unknown exchange"):
        validate_plan(TraversalPlan(layout=("group", "member"),
                                    exchange="hier_gather", kernel="sssp"))
    # the generic default exchange normalizes to the kernel's family
    p = TraversalPlan(layout=("group", "member"), kernel="sssp")
    assert p.exchange == "hier_min"
    # pre-§16 plan dicts (no kernel key) load as BFS
    d = TraversalPlan(layout=(), batch_roots=True).to_dict()
    del d["kernel"]
    assert TraversalPlan.from_dict(d).kernel == "bfs"
    # re-kerneling keeps the layout but swaps an alien exchange family
    tuned = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2),
                          exchange="hier_gather", partition="word_cyclic")
    rp = rekernel_plan(tuned, "sssp")
    assert (rp.kernel, rp.exchange, rp.partition) == \
        ("sssp", "hier_min", "word_cyclic")
    assert rekernel_plan(rp, "sssp") is rp


def test_validate_sssp_flags_a_distance_without_a_parent():
    """S2 holds the two planes to one reached set: a vertex that keeps
    its distance but loses its parent fails ``tree_dist``, as a distance
    no tree edge achieves."""
    import jax.numpy as jnp

    from repro.core import BFSResult, validate_sssp

    g, ev = weighted_graph()
    o_par, o_dist = oracle_planes(g, ev, [0])
    good = BFSResult(parent=jnp.asarray(o_par[0]),
                     level=jnp.asarray(o_dist[0]), stats=None)
    assert bool(validate_sssp(ev, good, 0).ok)
    far = int(np.argmax(o_dist[0]))
    bad = good._replace(parent=good.parent.at[far].set(-1))
    val = validate_sssp(ev, bad, 0)
    assert not bool(val.tree_dist_ok) and not bool(val.ok)


def test_sssp_requires_weight_plane():
    g, _ = weighted_graph()
    ev = edge_view(g)  # no weights attached
    pg = PreparedGraph(ev=ev, degree=g.degree, core=None)
    with pytest.raises(ValueError, match="weight"):
        compile_plan(TraversalPlan(layout=(), batch_roots=True,
                                   kernel="sssp"), pg)


# ---------------------------------------------------------------------------
# Sharded mesh matrix + composed layout (subprocess, 8 host devices)
# ---------------------------------------------------------------------------

MESH_MATRIX = f"""
import numpy as np
from repro.core import (TraversalPlan, PreparedGraph, build_csr, compile_plan,
                        edge_view, generate_edges, sssp_oracle,
                        with_edge_weights)
from repro.core.reorder import degree_reorder, relabel_edges

edges = generate_edges(3, {SCALE})
g0 = build_csr(edges)
r = degree_reorder(g0.degree)
g = build_csr(relabel_edges(edges, r))
ev = with_edge_weights(edge_view(g), seed=1, old_from_new=r.old_from_new)
pg = PreparedGraph(ev=ev, degree=g.degree, core=None)
roots = np.arange({ROOTS}, dtype=np.int32)
V = g.num_vertices
o_par = np.empty((len(roots), V), np.int32)
o_dist = np.empty((len(roots), V), np.int32)
for i, root in enumerate(roots):
    o_par[i], o_dist[i] = sssp_oracle(ev.src, ev.dst, ev.valid, ev.weight,
                                      V, int(root))

cases = [(shape, part, exch)
         for shape in ((2, 2), (4, 2))
         for part in ("block", "word_cyclic")
         for exch in ("hier_min", "flat")]
n_ok = 0
for shape, part, exch in cases:
    plan = TraversalPlan(layout=("group", "member"), mesh_shape=shape,
                         exchange=exch, partition=part, batch_roots=True,
                         kernel="sssp")
    res = compile_plan(plan, pg).run(roots, check="full")
    run = res.run
    assert run.all_valid, (shape, part, exch, run.check_failures)
    assert all(v == 0 for v in run.check_counts.values()), \\
        (shape, part, exch, run.check_counts)
    assert np.array_equal(np.asarray(res.parent)[:, :V], o_par), \\
        (shape, part, exch)
    assert np.array_equal(np.asarray(res.level)[:, :V], o_dist), \\
        (shape, part, exch)
    n_ok += 1

# composed 3-axis layout: root batch over its own mesh axis outside the
# vertex-sharded SPMD program
plan = TraversalPlan(layout=("root", "group", "member"),
                     mesh_shape=(2, 2, 2), batch_roots=True, kernel="sssp")
res = compile_plan(plan, pg).bfs(roots)
assert np.array_equal(np.asarray(res.parent)[:, :V], o_par)
assert np.array_equal(np.asarray(res.level)[:, :V], o_dist)
n_ok += 1
print(f"MESH_OK n={{n_ok}}")
"""


def test_sharded_mesh_matrix_matches_oracle():
    """2x2 / 4x2 x block / word_cyclic x hier_min / flat, check="full"
    with zero failure counts, plus the composed (2,2,2) layout — all
    bitwise-equal to the host oracle."""
    out = run_sub(MESH_MATRIX)
    assert "MESH_OK n=9" in out


# ---------------------------------------------------------------------------
# Fault injection: distance corruption detected + recovered (§13)
# ---------------------------------------------------------------------------

FAULTS = f"""
import numpy as np
from repro.core import (TraversalPlan, PreparedGraph, build_csr, compile_plan,
                        edge_view, generate_edges, with_edge_weights)
from repro.core.faults import FaultSpec
from repro.core.reorder import degree_reorder, relabel_edges

edges = generate_edges(11, 9)
g0 = build_csr(edges)
r = degree_reorder(g0.degree)
g = build_csr(relabel_edges(edges, r))
ev = with_edge_weights(edge_view(g), seed=1, old_from_new=r.old_from_new)
pg = PreparedGraph(ev=ev, degree=g.degree, core=None)
roots = np.arange(4, dtype=np.int32)
plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2),
                     batch_roots=True, kernel="sssp")
base = compile_plan(TraversalPlan(layout=(), batch_roots=True,
                                  kernel="sssp"), pg).run(roots, check="post")
assert base.run.all_valid

# Zeroing the distance min-exchange corrupts every replica's dist plane;
# check="full" must catch it (attributed to the SSSP invariants + the
# in-loop sentinel) and the single-device fallback — which has no
# exchange — must recover the exact oracle bits.
f = FaultSpec(site="exchange", kind="zero", level=1, persistent=True)
res = compile_plan(plan, pg, fault=f).run(roots, check="full", retries=1,
                                          fallback=True)
run = res.run
assert run.check_counts["tree_dist"] == 4
assert run.check_counts["no_shorter_edge"] == 4
assert run.check_counts["sentinel"] == 4
assert run.retries == 4 and run.fallbacks == 4
assert run.quarantined == [] and run.all_valid
assert np.array_equal(res.parent, base.parent)
assert np.array_equal(res.level, base.level)
print("SSSP_RECOVERED")

# A parent fault on the degraded shape itself survives the fallback ->
# quarantine, never a silently wrong tree.
f2 = FaultSpec(site="parent", kind="offset", level=1, persistent=True)
c2 = compile_plan(TraversalPlan(layout=(), batch_roots=True, kernel="sssp"),
                  pg, fault=f2)
run2 = c2.run(roots, check="post", retries=1, fallback=True).run
assert run2.check_counts["tree_dist"] == 4
assert run2.quarantined == [0, 1, 2, 3]
assert run2.harmonic_mean_teps == 0.0
print("SSSP_QUARANTINED")
"""


def test_sssp_fault_detected_and_recovered():
    out = run_sub(FAULTS)
    assert "SSSP_RECOVERED" in out and "SSSP_QUARANTINED" in out


# ---------------------------------------------------------------------------
# Multiprocess: 2 real processes, distance plane crosses the wire
# ---------------------------------------------------------------------------

def test_two_proc_sssp_parity(tmp_path):
    """One 2-proc x 2-device gang under the sssp kernel: parents AND
    distances bitwise-identical to the in-worker host oracle on both
    min-family exchanges."""
    from repro.launch.multiprocess import launch, rung_name

    payload = launch(2, 2, scale=SCALE, n_roots=ROOTS, seed=3, reps=1,
                     exchanges="hier_min,flat", partitions="block",
                     check="full", kernel="sssp",
                     log_dir=str(tmp_path / "logs"))
    assert payload["kernel"] == "sssp"
    assert payload["parents_bitwise_identical"] is True
    expected = {rung_name(2, 2, e, "block", "sssp")
                for e in ("hier_min", "flat")}
    assert set(payload["rungs"]) == expected
    for name, rung in payload["rungs"].items():
        assert rung["identical"] is True, name
        assert rung["validated"] is True, name
        assert all(v == 0 for v in rung["check_counts"].values()), name
        # the BFS-level exchange replay does not apply to δ-rounds
        assert rung["exchange_seconds"] is None
