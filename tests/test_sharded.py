"""Mesh-sharded Graph500 engine tests (DESIGN.md §9).

Layer 1 (root-parallel shard_map batch) and layer 2 (vertex-sharded
resident bitmaps over the T3 hierarchical collectives) must be
bitwise-locked to the single-device bitmap engine.  Multi-device cases
run in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process keeps seeing 1 device (spec requirement).
"""
import os
import sys
import textwrap

import numpy as np
import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from repro.util import respawn_with_host_devices  # noqa: E402


def run_sub(code: str, extra_env: dict | None = None) -> str:
    out = respawn_with_host_devices(
        [sys.executable, "-c", textwrap.dedent(code)], 8,
        extra_env=extra_env, pythonpath=(REPO_SRC,), capture=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


PREAMBLE = """
import numpy as np
import jax, jax.numpy as jnp
from repro.core import (BFSPlan, PreparedGraph, build_csr, build_heavy_core,
                        chunk_edge_view, compile_plan, degree_reorder,
                        edge_view, generate_edges)
from repro.core.graph_build import csr_to_edge_arrays
from repro.core.reorder import relabel_edges
from repro.util import make_mesh

def sorted_graph(scale, seed=11, threshold=32):
    edges = generate_edges(seed, scale)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    core = build_heavy_core(g, threshold=threshold)
    ev = edge_view(g)
    return g, ev, core, chunk_edge_view(ev)

# plan-API conveniences (the deprecated shims these tests used to route
# through are exercised in tests/test_plan.py)

def plan_bfs(ev, degree, root, *, core=None, chunks=None):
    p = BFSPlan(engine="bitmap", layout=(), batch_roots=False)
    return compile_plan(p, PreparedGraph(ev=ev, degree=degree, core=core,
                                         chunks=chunks)).bfs(root)

def plan_batch(ev, degree, roots, *, core=None, chunks=None):
    p = BFSPlan(layout=(), batch_roots=True)
    return compile_plan(p, PreparedGraph(ev=ev, degree=degree, core=core,
                                         chunks=chunks)).bfs(roots)

def vertex_plan(mesh, sg, *, core=None, degree=None, ev=None,
                exchange="hier_or", batched=False):
    p = BFSPlan(layout=("group", "member"), exchange=exchange,
                batch_roots=batched)
    return compile_plan(p, PreparedGraph(ev=ev, degree=degree, core=core,
                                         sharded=sg), mesh=mesh)
"""


def test_root_parallel_batch_bitwise_identical_to_single_device():
    """Acceptance: the ("root",) plan on a 4-device mesh == the
    single-device batch plan for all 64 roots, bitwise."""
    out = run_sub(PREAMBLE + """
g, ev, core, chunks = sorted_graph(10, seed=1, threshold=8)
roots = np.arange(64, dtype=np.int32)
base = plan_batch(ev, g.degree, roots, core=core, chunks=chunks)
pg = PreparedGraph(ev=ev, degree=g.degree, core=core, chunks=chunks)
mesh = make_mesh((4,), ("root",))
res = compile_plan(BFSPlan(layout=("root",)), pg, mesh=mesh).bfs(roots)
assert np.array_equal(np.asarray(res.parent), np.asarray(base.parent))
assert np.array_equal(np.asarray(res.level), np.asarray(base.level))
assert np.array_equal(np.asarray(res.stats.levels),
                      np.asarray(base.stats.levels))
# root count not a multiple of the axis: padded and sliced
res10 = compile_plan(BFSPlan(layout=("root",)), pg,
                     mesh=make_mesh((8,), ("root",))).bfs(roots[:10])
assert res10.parent.shape[0] == 10
assert np.array_equal(np.asarray(res10.parent),
                      np.asarray(base.parent)[:10])
print("OK")
""")
    assert "OK" in out


@pytest.mark.parametrize("shape", [(1, 1), (2, 1), (2, 2), (4, 2)])
def test_vertex_sharded_equals_single_device_scale12(shape):
    """Satellite: parents/levels identical on host meshes of 1, 2, 4 and
    8 devices at scale 12 (dense core on)."""
    out = run_sub(PREAMBLE + f"""
from repro.core.distributed_bfs import shard_graph
shape = {shape!r}
g, ev, core, chunks = sorted_graph(12, seed=11, threshold=32)
src, dst, valid = (np.asarray(t) for t in csr_to_edge_arrays(g))
p = shape[0] * shape[1]
sg = shard_graph(src, dst, valid, g.num_vertices, p)
mesh = make_mesh(shape, ("group", "member"))
compiled = vertex_plan(mesh, sg, core=core)
for root in (0, 17):
    res = compiled.bfs(root)
    parent, level = np.asarray(res.parent), np.asarray(res.level)
    single = plan_bfs(ev, g.degree, root, core=core, chunks=chunks)
    V = g.num_vertices
    assert np.array_equal(parent[:V], np.asarray(single.parent)), root
    assert np.array_equal(level[:V], np.asarray(single.level)), root
    assert np.all(parent[V:] == -1) and np.all(level[V:] == -1)
print("OK")
""")
    assert "OK" in out


def test_vertex_sharded_word_cyclic_equals_single_device_scale12():
    """Tentpole acceptance: the word-cyclic partition (paper eq. (3) at
    uint32-word granularity) is bitwise-identical to the single-device
    bitmap engine at scale 12 on 2-, 4- and 8-device meshes — the
    reassembly permutation restores global vertex order exactly."""
    out = run_sub(PREAMBLE + """
g, ev, core, chunks = sorted_graph(12, seed=11, threshold=32)
pg = PreparedGraph(ev=ev, degree=g.degree, core=core, chunks=chunks)
V = g.num_vertices
for shape in ((2, 1), (2, 2), (4, 2)):
    plan = BFSPlan(layout=("group", "member"), mesh_shape=shape,
                   partition="word_cyclic", batch_roots=False)
    compiled = compile_plan(plan, pg)
    for root in (0, 17):
        res = compiled.bfs(root)
        parent, level = np.asarray(res.parent), np.asarray(res.level)
        single = plan_bfs(ev, g.degree, root, core=core, chunks=chunks)
        assert np.array_equal(parent[:V], np.asarray(single.parent)), (shape, root)
        assert np.array_equal(level[:V], np.asarray(single.level)), (shape, root)
        assert np.all(parent[V:] == -1) and np.all(level[V:] == -1)
print("OK")
""")
    assert "OK" in out


def test_word_cyclic_balances_degree_sorted_shards():
    """Satellite acceptance: per-shard edge-count skew (max/mean) at
    scale 12 over 8 shards after the degree sort is >= 2x lower under
    word_cyclic than block (host-side partitioner, no devices needed)."""
    import numpy as np

    from repro.core import (
        build_csr, degree_reorder, generate_edges,
    )
    from repro.core.distributed_bfs import shard_edge_skew, shard_graph
    from repro.core.graph_build import csr_to_edge_arrays
    from repro.core.reorder import relabel_edges

    edges = generate_edges(11, 12)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)          # T2a: heavy vertices low ids
    g = build_csr(relabel_edges(edges, r))
    src, dst, valid = (np.asarray(t) for t in csr_to_edge_arrays(g))
    skews = {}
    for part in ("block", "word_cyclic"):
        sg = shard_graph(src, dst, valid, g.num_vertices, 8, partition=part)
        assert sg.partition == part
        skews[part] = shard_edge_skew(sg)
    assert skews["block"]["max_over_mean"] >= \
        2.0 * skews["word_cyclic"]["max_over_mean"], skews
    # both partitions cover every edge exactly once
    n_edges = int(valid.sum())
    assert skews["block"]["max"] <= n_edges
    for part in skews:
        assert sum(skews[part]["per_shard_edges"]) == n_edges, part


def test_shard_graph_counts_source_only_vertices():
    """Satellite: n_active counts the src∪dst endpoint union — a vertex
    with only outgoing edges (possible on a non-symmetrized edge list)
    must not be silently dropped from the eq. (1)/(2) switch denominator."""
    import numpy as np

    from repro.core.distributed_bfs import shard_graph

    # 5 -> 2 and 7 -> 2: vertices 5 and 7 have ONLY outgoing edges
    src = np.asarray([5, 7], np.int32)
    dst = np.asarray([2, 2], np.int32)
    valid = np.ones(2, bool)
    for part in ("block", "word_cyclic"):
        sg = shard_graph(src, dst, valid, 16, 2, partition=part)
        assert int(sg.n_active) == 3, (part, int(sg.n_active))


def test_dead_chunks_killed_and_bu_skips_padding_on_skewed_shard():
    """Satellite regression: a deliberately skewed block partition (star
    graph — every edge points at vertex 0, so shard 0 owns all edges and
    shard 1 is pure padding).  The all-invalid chunks carry the
    src_lo = V_pad / src_hi = -1 sentinels, chunk_range_mask provably
    kills them for ANY frontier, the BU live-chunk prefix excludes them,
    and the traversal stays bitwise-identical to single-device."""
    import numpy as np

    from repro.core.bfs_steps import chunk_range_mask
    from repro.core.distributed_bfs import shard_graph

    n = 64
    hub = np.zeros(n - 1, np.int32)
    spokes = np.arange(1, n, dtype=np.int32)
    src = np.concatenate([spokes, hub])     # symmetric star
    dst = np.concatenate([hub, spokes])
    valid = np.ones(src.shape, bool)
    sg = shard_graph(src, dst, valid, n, 2, n_chunks=4, partition="block")
    counts = np.asarray(sg.valid).sum(axis=(1, 2))
    # shard 0 owns the hub AND every spoke (v_loc >= n), shard 1 nothing
    assert counts[0] == len(src) and counts[1] == 0, counts
    v_pad = sg.num_vertices
    src_lo = np.asarray(sg.src_lo)
    src_hi = np.asarray(sg.src_hi)
    # the dead shard's chunks carry the all-invalid sentinels
    assert np.all(src_lo[1] == v_pad) and np.all(src_hi[1] == -1)
    # chunk_range_mask kills them even for an all-ones frontier
    full_frontier = np.full(v_pad // 32, 0xFFFFFFFF, np.uint32)
    import jax.numpy as jnp
    live = np.asarray(chunk_range_mask(
        jnp.asarray(src_lo[1]), jnp.asarray(src_hi[1]),
        jnp.asarray(full_frontier)))
    assert not live.any(), live
    # the BU prefix bound (live chunks per shard) is exact: padding is a
    # contiguous tail, so nonempty chunks form a prefix
    n_live = (src_hi >= 0).sum(axis=1)
    assert n_live[1] == 0
    assert n_live[0] == -(-counts[0] // sg.chunk_size)

    # parity on the skewed graph, both shards traversing
    out = run_sub(PREAMBLE + """
from repro.core.distributed_bfs import shard_graph
from repro.core.bfs_steps import edge_view as _ev, EdgeView
import jax.numpy as jnp
n = 64
hub = np.zeros(n - 1, np.int32)
spokes = np.arange(1, n, dtype=np.int32)
src = np.concatenate([spokes, hub])
dst = np.concatenate([hub, spokes])
valid = np.ones(src.shape, bool)
degree = np.bincount(src, minlength=n).astype(np.int32)
ev = EdgeView(src=jnp.asarray(src), dst=jnp.asarray(dst),
              valid=jnp.asarray(valid), num_vertices=n)
single = plan_bfs(ev, jnp.asarray(degree), 3)
sg = shard_graph(src, dst, valid, n, 2, n_chunks=4, partition="block")
mesh = make_mesh((2, 1), ("group", "member"))
res = vertex_plan(mesh, sg).bfs(3)
parent = np.asarray(res.parent)
assert np.array_equal(parent[:n], np.asarray(single.parent))
assert np.all(parent[n:] == -1)
print("OK")
""")
    assert "OK" in out


def test_vertex_sharded_nonmultiple_word_count():
    """Satellite: word counts that do NOT divide n_devices (3 and 5
    shards over a 1024-word bitmap) exercise the padded tail path —
    under BOTH vertex partitions (the word-cyclic padded words stride
    across every shard instead of piling onto the last)."""
    out = run_sub(PREAMBLE + """
from repro.core.distributed_bfs import shard_graph
from repro.core.heavy import padded_bitmap_words
g, ev, core, chunks = sorted_graph(12, seed=11, threshold=32)
src, dst, valid = (np.asarray(t) for t in csr_to_edge_arrays(g))
w_base = padded_bitmap_words(g.num_vertices)
for shape in ((3, 1), (1, 5)):
  p = shape[0] * shape[1]
  assert w_base % p != 0, (w_base, p)   # the case under test
  for part in ("block", "word_cyclic"):
    sg = shard_graph(src, dst, valid, g.num_vertices, p, partition=part)
    assert sg.num_vertices > g.num_vertices  # padded tail exists
    # non-pow2 members are allowed through a caller-supplied mesh=
    mesh = make_mesh(shape, ("group", "member"))
    plan = BFSPlan(layout=("group", "member"), partition=part,
                   batch_roots=False)
    res = compile_plan(plan, PreparedGraph(core=core, sharded=sg,
                                           degree=g.degree),
                       mesh=mesh).bfs(0)
    parent, level = np.asarray(res.parent), np.asarray(res.level)
    single = plan_bfs(ev, g.degree, 0, core=core, chunks=chunks)
    V = g.num_vertices
    assert np.array_equal(parent[:V], np.asarray(single.parent)), (shape, part)
    assert np.array_equal(level[:V], np.asarray(single.level)), (shape, part)
    assert np.all(parent[V:] == -1), (shape, part)
print("OK")
""")
    assert "OK" in out


def test_exchange_wirings_bit_identical():
    """hier_or (two-phase OR reduction), hier_gather (monitor all-gather),
    flat all-gather, and the §12 wire-codec variants hier_or_packed
    (density-adaptive codec) and hier_or_sieve (visited-sieve then pack)
    must produce the same traversal — under BOTH vertex partitions (the
    cyclic owner map makes the hier_or scatter strided and transposes
    the gathered device-major blocks)."""
    out = run_sub(PREAMBLE + """
import warnings
from repro.core.distributed_bfs import shard_graph, make_dist_bfs, gather_result
g, ev, core, chunks = sorted_graph(10, seed=3, threshold=8)
src, dst, valid = (np.asarray(t) for t in csr_to_edge_arrays(g))
sg = shard_graph(src, dst, valid, g.num_vertices, 8)
mesh = make_mesh((2, 4), ("group", "member"))
results = {}
for part in ("block", "word_cyclic"):
    sg_p = shard_graph(src, dst, valid, g.num_vertices, 8, partition=part)
    for exch in ("hier_or", "hier_gather", "flat",
                 "hier_or_packed", "hier_or_sieve"):
        plan = BFSPlan(layout=("group", "member"), exchange=exch,
                       partition=part, batch_roots=False)
        res = compile_plan(plan, PreparedGraph(core=core, sharded=sg_p,
                                               degree=g.degree),
                           mesh=mesh).bfs(5)
        results[(part, exch)] = (np.asarray(res.parent),
                                 np.asarray(res.level))
ref_p, ref_l = results[("block", "hier_or")]
for key, (p, l) in results.items():
    assert np.array_equal(p, ref_p), key
    assert np.array_equal(l, ref_l), key
# legacy-compat flag still routes: hierarchical=False -> flat (the one
# intentional shim call here; its DeprecationWarning is acknowledged)
with warnings.catch_warnings():
    warnings.simplefilter("ignore", DeprecationWarning)
    bfs = make_dist_bfs(mesh, sg, hierarchical=False, core=core)
p, l = gather_result(bfs(jnp.int32(5)), sg)
assert np.array_equal(p, ref_p)
print("OK")
""")
    assert "OK" in out


def test_codec_exchanges_bit_identical_across_meshes():
    """Tentpole acceptance: hier_or_packed and hier_or_sieve are
    bitwise-identical to the single-device bitmap engine across meshes
    2x1 / 2x2 / 4x2 under BOTH vertex partitions."""
    out = run_sub(PREAMBLE + """
g, ev, core, chunks = sorted_graph(10, seed=3, threshold=8)
pg = PreparedGraph(ev=ev, degree=g.degree, core=core, chunks=chunks)
V = g.num_vertices
single = plan_bfs(ev, g.degree, 5, core=core, chunks=chunks)
for shape in ((2, 1), (2, 2), (4, 2)):
  for part in ("block", "word_cyclic"):
    for exch in ("hier_or_packed", "hier_or_sieve"):
        plan = BFSPlan(layout=("group", "member"), mesh_shape=shape,
                       exchange=exch, partition=part, batch_roots=False)
        res = compile_plan(plan, pg).bfs(5)
        parent, level = np.asarray(res.parent), np.asarray(res.level)
        key = (shape, part, exch)
        assert np.array_equal(parent[:V], np.asarray(single.parent)), key
        assert np.array_equal(level[:V], np.asarray(single.level)), key
        assert np.all(parent[V:] == -1) and np.all(level[V:] == -1), key
print("OK")
""")
    assert "OK" in out


def test_codec_exchanges_nondividing_and_composed():
    """Tentpole acceptance: the wire-codec exchanges survive word counts
    that do NOT divide the device count ((3,1) and (1,5) meshes take the
    non-dividing member fallback) and the composed 3-axis
    (root, group, member) 2x2x2 layout."""
    out = run_sub(PREAMBLE + """
from repro.core.distributed_bfs import shard_graph
from repro.core.heavy import padded_bitmap_words
g, ev, core, chunks = sorted_graph(12, seed=11, threshold=32)
src, dst, valid = (np.asarray(t) for t in csr_to_edge_arrays(g))
V = g.num_vertices
single = plan_bfs(ev, g.degree, 0, core=core, chunks=chunks)
w_base = padded_bitmap_words(V)
for shape, part, exch in (((3, 1), "block", "hier_or_sieve"),
                          ((1, 5), "word_cyclic", "hier_or_packed")):
    p = shape[0] * shape[1]
    assert w_base % p != 0, (w_base, p)   # the case under test
    sg = shard_graph(src, dst, valid, V, p, partition=part)
    mesh = make_mesh(shape, ("group", "member"))
    plan = BFSPlan(layout=("group", "member"), partition=part,
                   exchange=exch, batch_roots=False)
    res = compile_plan(plan, PreparedGraph(core=core, sharded=sg,
                                           degree=g.degree),
                       mesh=mesh).bfs(0)
    parent, level = np.asarray(res.parent), np.asarray(res.level)
    assert np.array_equal(parent[:V], np.asarray(single.parent)), (shape, exch)
    assert np.array_equal(level[:V], np.asarray(single.level)), (shape, exch)

# composed 3-axis layout: root batch outside the vertex-sharded program
roots = np.asarray([0, 17], np.int32)
base = plan_batch(ev, g.degree, roots, core=core, chunks=chunks)
pg = PreparedGraph(ev=ev, degree=g.degree, core=core, chunks=chunks)
for exch in ("hier_or_packed", "hier_or_sieve"):
    plan = BFSPlan(layout=("root", "group", "member"), mesh_shape=(2, 2, 2),
                   exchange=exch)
    res = compile_plan(plan, pg).bfs(roots)
    assert np.array_equal(np.asarray(res.parent)[:, :V],
                          np.asarray(base.parent)), exch
    assert np.array_equal(np.asarray(res.level)[:, :V],
                          np.asarray(base.level)), exch
print("OK")
""")
    assert "OK" in out


def test_codec_wire_bytes_drop_at_sparse_levels():
    """Acceptance: modeled inter-group wire bytes at sparse levels
    (frontier <= 256 vertices) drop >= 4x under the density-adaptive
    codec vs raw hier_or at scale 12 on the 4x2 acceptance mesh, both
    partitions.  Host-side: the level array comes from a numpy BFS, the
    byte model from repro.core.distributed_bfs.modeled_wire_bytes."""
    import numpy as np

    from repro.core import build_csr, degree_reorder, generate_edges
    from repro.core.distributed_bfs import modeled_wire_bytes
    from repro.core.graph_build import csr_to_edge_arrays
    from repro.core.heavy import padded_bitmap_words
    from repro.core.reorder import relabel_edges

    edges = generate_edges(11, 12)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    src, dst, valid = (np.asarray(t) for t in csr_to_edge_arrays(g))
    src, dst = src[valid], dst[valid]
    V = g.num_vertices
    level = np.full(V, -1, np.int32)
    level[0] = 0
    t = 0
    while True:
        hit = level[src] == t
        nxt = np.unique(dst[hit])
        nxt = nxt[level[nxt] == -1]
        if nxt.size == 0:
            break
        level[nxt] = t + 1
        t += 1
    w_loc = -(-padded_bitmap_words(V) // 8)
    for part in ("block", "word_cyclic"):
        wb = modeled_wire_bytes(level, n_devices=8, w_loc=w_loc,
                                group=4, member=2, partition=part)
        sparse = [p for p in wb["per_level"] if p["frontier"] <= 256]
        assert sparse, ("no sparse level at scale 12", wb["per_level"])
        for p in sparse:
            assert p["inter"]["raw"] >= 4 * p["inter"]["post_codec"], (part, p)
            assert p["inter"]["post_sieve"] <= p["inter"]["raw"], (part, p)


def test_vertex_sharded_batched_roots():
    """Layer composition: all search keys batched inside the vertex-sharded
    SPMD program (vmap over roots under shard_map)."""
    out = run_sub(PREAMBLE + """
from repro.core.distributed_bfs import shard_graph
g, ev, core, chunks = sorted_graph(9, seed=5, threshold=8)
src, dst, valid = (np.asarray(t) for t in csr_to_edge_arrays(g))
roots = np.asarray([0, 3, 17, 29, 40, 41, 42, 43], np.int32)
base = plan_batch(ev, g.degree, roots, core=core, chunks=chunks)
sg = shard_graph(src, dst, valid, g.num_vertices, 8)
mesh = make_mesh((2, 4), ("group", "member"))
res = vertex_plan(mesh, sg, core=core, batched=True).bfs(roots)
V = g.num_vertices
assert np.array_equal(np.asarray(res.parent)[:, :V], np.asarray(base.parent))
assert np.array_equal(np.asarray(res.level)[:, :V], np.asarray(base.level))
print("OK")
""")
    assert "OK" in out


def test_vertex_sharded_program_names_its_phases():
    """The vertex-sharded program carries the single-device engine's
    phase scopes, and its delta exchange's collectives sit under
    ``bfs.exchange``, apart from the epilogue's counts (``psum``)."""
    out = run_sub(PREAMBLE + """
import re
from repro.core.distributed_bfs import shard_graph
g, ev, core, chunks = sorted_graph(9, seed=5, threshold=8)
src, dst, valid = (np.asarray(t) for t in csr_to_edge_arrays(g))
sg = shard_graph(src, dst, valid, g.num_vertices, 8)
mesh = make_mesh((2, 4), ("group", "member"))
text = vertex_plan(mesh, sg, core=core, degree=g.degree).lower(5).compile(
    ).as_text()
paths = [m.split("/") for m in re.findall(r'op_name="([^"]*)"', text)]
phases = {p for path in paths for p in path if p.startswith("bfs.")}
assert phases == {"bfs.init", "bfs.td_relax", "bfs.bu_core", "bfs.bu_relax",
                  "bfs.epilogue", "bfs.exchange", "bfs.finish"}, phases
moves = re.findall(r" (?:all-gather|all-to-all)(?:-start)?\\(.*", text)
assert moves
for inst in moves:
    assert "/bfs.exchange/" in inst, inst[:200]
print("OK")
""")
    assert "OK" in out


def test_vertex_sharded_runner_harness():
    out = run_sub(PREAMBLE + """
from repro.core import sample_roots
from repro.core.distributed_bfs import shard_graph
edges = generate_edges(7, 10)
g0 = build_csr(edges)
r = degree_reorder(g0.degree)
g = build_csr(relabel_edges(edges, r))
core = build_heavy_core(g, threshold=8)
src, dst, valid = (np.asarray(t) for t in csr_to_edge_arrays(g))
ev = edge_view(g)
roots = np.asarray(r.new_from_old)[np.asarray(sample_roots(3, edges, 8))]
sg = shard_graph(src, dst, valid, g.num_vertices, 8)
mesh = make_mesh((2, 4), ("group", "member"))
run = vertex_plan(mesh, sg, core=core, degree=g.degree, ev=ev,
                  batched=True).run(roots).run
assert run.batched and len(run.teps) == len(roots)
assert run.harmonic_mean_teps > 0
assert all(m > 0 for m in run.edges)
assert len(run.validated) == len(roots) and run.all_valid
# without ev there is nothing to validate -> all_valid must NOT be True
run2 = vertex_plan(mesh, sg, core=core, degree=g.degree,
                   batched=True).run(roots[:2]).run
assert not run2.all_valid and run2.harmonic_mean_teps > 0
print("OK")
""")
    assert "OK" in out


def test_hierarchical_por_and_integer_psum_regression():
    """Satellite: uint32 bitmap words must survive the hierarchical
    reductions losslessly — no float compress round trip."""
    out = run_sub("""
import functools
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.util import make_mesh
from repro.comms.hierarchical import (
    compressed_hierarchical_psum, hierarchical_por, hierarchical_psum)

mesh = make_mesh((2, 4), ("group", "member"))
rng = np.random.default_rng(0)

# OR reduction: exact vs the numpy fold, full bit range
x = jnp.asarray(rng.integers(0, 2**32, size=(8, 64), dtype=np.uint32))
f = jax.jit(jax.shard_map(
    lambda v: hierarchical_por(v[0], "group", "member")[None],
    mesh=mesh, in_specs=P(("group", "member")),
    out_specs=P(("group", "member")), check_vma=False))
got = np.asarray(f(x))
want = functools.reduce(np.bitwise_or, np.asarray(x))
assert all(np.array_equal(got[i], want) for i in range(8))

# odd leading dim takes the two-phase fallback, still exact
x2 = jnp.asarray(rng.integers(0, 2**32, size=(8, 63), dtype=np.uint32))
got2 = np.asarray(f(x2))
want2 = functools.reduce(np.bitwise_or, np.asarray(x2))
assert all(np.array_equal(got2[i], want2) for i in range(8))

# float payloads are rejected (OR is meaningless there)
try:
    jax.jit(jax.shard_map(
        lambda v: hierarchical_por(v[0].astype(jnp.float32),
                                   "group", "member")[None],
        mesh=mesh, in_specs=P(("group", "member")),
        out_specs=P(("group", "member")), check_vma=False))(x)
    raise SystemExit("expected TypeError")
except TypeError:
    pass

# compressed psum: integer payloads bypass the bfloat16 cast (lossless).
# These values need >8 mantissa bits, so the float path would corrupt them.
xi = jnp.asarray(rng.integers(2**20, 2**24, size=(8, 64), dtype=np.uint32))
fc = jax.jit(jax.shard_map(
    lambda v: compressed_hierarchical_psum(v[0], "group", "member")[None],
    mesh=mesh, in_specs=P(("group", "member")),
    out_specs=P(("group", "member")), check_vma=False))
got3 = np.asarray(fc(xi))
want3 = np.sum(np.asarray(xi, np.uint64), axis=0).astype(np.uint32)
assert np.array_equal(got3[0], want3)

# float payloads still go through the compressed (lossy) leg
xf = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
gotf = np.asarray(fc(xf))
wantf = np.sum(np.asarray(xf), axis=0)
assert np.allclose(gotf[0], wantf, rtol=1e-2, atol=5e-2)
print("OK")
""")
    assert "OK" in out


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_interpret_mode_follows_backend(monkeypatch, backend):
    """Kernels run interpreted exactly on the CPU backend; nothing
    overrides that."""
    import jax

    from repro.kernels import ops

    monkeypatch.setenv("REPRO_INTERPRET", "1")   # must be ignored
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.interpret_mode() is (backend == "cpu")


def test_respawn_refuses_on_accelerator(monkeypatch):
    """On an accelerator this process holds the chip: no child with
    forced host devices is started."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="forced host devices"):
        respawn_with_host_devices([sys.executable, "-c", "raise SystemExit(7)"],
                                  8, capture=True, timeout=60)


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR is JAX's own setting and is left alone;
    without it the cache goes to <checkout>/.jax_cache.  The cache is
    never actually turned on here."""
    import jax

    from repro.util import use_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(tmp_path / ".jax_cache")
        assert use_compile_cache(str(tmp_path)) == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert use_compile_cache(str(tmp_path)) == env_dir
        assert updates == []


def test_pipeline_mesh_rung_single_device():
    """pre-g500-mesh rung degrades gracefully to the visible device count
    (1 in the main pytest process) and still validates."""
    from repro.core import Graph500Config, run

    cfg = Graph500Config.ladder("pre-g500-mesh", scale=9, n_roots=4)
    _, result = run(cfg)
    assert result.batched and result.all_valid
    assert result.harmonic_mean_teps > 0


def test_plan_device_mesh_shapes():
    from repro.comms.topology import TreeTopology, plan_device_mesh

    assert plan_device_mesh(1) == (1, 1)
    assert plan_device_mesh(2) == (1, 2)
    assert plan_device_mesh(4) == (1, 4)
    assert plan_device_mesh(8) == (2, 4)
    assert plan_device_mesh(512) == (128, 4)
    # member never exceeds the router group size; product always preserved
    for n in range(1, 65):
        g, m = plan_device_mesh(n)
        assert g * m == n and 1 <= m <= 4
    # non-default topology: groups of 8
    t = TreeTopology((8, 8, 4, 2))
    assert plan_device_mesh(16, t) == (2, 8)
    # primes larger than the group size degenerate to member=1
    assert plan_device_mesh(7) == (7, 1)
