"""Spec→plan→runner API tests (DESIGN.md §10).

Covers: plan validation (every invalid combination is a clear ValueError,
never a shard_map trace error), the six legacy entrypoint deprecation
shims (warn + bitwise-identical to the equivalent BFSPlan run, parents
compared at scale 12), the composed ("root", "group", "member") 2x2x2
plan against the single-device engine, and the dry-run graph500 cells
lowering the plan-compiled resident engine.

Multi-device cases run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps seeing 1 device (spec requirement).
"""
import os
import sys
import textwrap

import numpy as np
import pytest

from repro.core import BFSPlan, PreparedGraph, compile_plan
from repro.core.plan import validate_plan

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
import warnings
import numpy as np
import jax, jax.numpy as jnp
from repro.core import (BFSPlan, PreparedGraph, build_csr, build_heavy_core,
                        chunk_edge_view, compile_plan, degree_reorder,
                        edge_view, generate_edges)
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
"""


# ---------------------------------------------------------------------------
# Plan spec + validation (no devices needed — pure ValueError paths).
# ---------------------------------------------------------------------------

def test_plan_to_dict_is_json_ready():
    import json

    p = BFSPlan(layout=("root", "group", "member"), mesh_shape=(2, 2, 2),
                exchange="hier_gather", alpha=8.0)
    d = p.to_dict()
    assert d["layout"] == ["root", "group", "member"]
    assert d["mesh_shape"] == [2, 2, 2]
    assert d["engine"] == "bitmap" and d["alpha"] == 8.0
    json.dumps(d)  # must serialize for BENCH_bfs.json metadata
    # layout normalizes to a tuple even when passed as a list
    assert BFSPlan(layout=["root"], mesh_shape=[2]).layout == ("root",)


@pytest.mark.parametrize("plan,match", [
    (BFSPlan(engine="bogus"), "unknown engine"),
    (BFSPlan(layout=("root", "member")), "unknown layout"),
    (BFSPlan(exchange="bogus"), "unknown exchange"),
    (BFSPlan(engine="reference", layout=("root",)), "requires engine='bitmap'"),
    (BFSPlan(layout=("root",), batch_roots=False), "batch_roots=True"),
    (BFSPlan(engine="legacy", batch_roots=True), "requires engine='bitmap'"),
    (BFSPlan(mesh_shape=(2,)), "layout is ()"),
    (BFSPlan(layout=("group", "member"), mesh_shape=(2,)),
     "does not match layout"),
    (BFSPlan(layout=("group", "member"), mesh_shape=(1, 3)),
     "not a power of two"),
    (BFSPlan(layout=("root", "group", "member"), mesh_shape=(2, 2, 3)),
     "not a power of two"),
    (BFSPlan(partition="bogus"), "unknown partition"),
    (BFSPlan(partition="word_cyclic"), "requires a vertex-sharded"),
    (BFSPlan(layout=("root",), partition="word_cyclic"),
     "requires a vertex-sharded"),
])
def test_plan_validation_value_errors(plan, match):
    with pytest.raises(ValueError, match=match):
        validate_plan(plan)


def test_from_dict_default_fills_missing_fields_rejects_unknown():
    """A plan dict recorded before the `partition` axis existed loads
    with the default (block) — the same default-fill the regression gate
    uses — while unknown fields still fail loudly."""
    d = BFSPlan(layout=("group", "member"), mesh_shape=(2, 4)).to_dict()
    assert d["partition"] == "block"
    d.pop("partition")
    assert BFSPlan.from_dict(d).partition == "block"
    with pytest.raises(ValueError, match="unknown BFSPlan fields"):
        BFSPlan.from_dict({**d, "partition": "block", "owner_map": "x"})


def test_prebuilt_sharded_partition_mismatch_is_clear_value_error():
    """A ShardedGraph carries its owner map; compiling it under a plan
    that names the other partition must be a ValueError, not a silent
    mis-assembled traversal."""
    import numpy as np

    from repro.core import build_csr, generate_edges
    from repro.core.distributed_bfs import shard_graph
    from repro.core.graph_build import csr_to_edge_arrays

    g = build_csr(generate_edges(3, 8))
    src, dst, valid = (np.asarray(t) for t in csr_to_edge_arrays(g))
    sg = shard_graph(src, dst, valid, g.num_vertices, 1, partition="block")
    plan = BFSPlan(layout=("group", "member"), mesh_shape=(1, 1),
                   partition="word_cyclic")
    with pytest.raises(ValueError, match="partition.*re-run shard_graph"):
        compile_plan(plan, PreparedGraph(sharded=sg, degree=g.degree))


def test_axis_names_without_mesh_is_clear_value_error():
    """Role renames only make sense against a caller-supplied mesh — an
    inferred mesh is built with the layout role names."""
    with pytest.raises(ValueError, match="prebuilt mesh"):
        compile_plan(BFSPlan(layout=("root",)), None, axis_names=("r0",))


def test_composed_plan_too_few_devices_is_clear_value_error():
    """A 4x4x4 composed plan on the single-device pytest process must be a
    clear ValueError naming the device shortfall — not a shard_map error."""
    plan = BFSPlan(layout=("root", "group", "member"), mesh_shape=(4, 4, 4))
    with pytest.raises(ValueError, match="needs 64 devices"):
        compile_plan(plan, None)  # fails before touching the graph


def test_planner_nonpow2_member_is_clear_value_error():
    """6 visible devices -> plan_device_mesh gives (2, 3); the plan API must
    reject the member=3 axis with a ValueError, not trace into shard_map."""
    out = run_sub("""
from repro.core import BFSPlan, compile_plan
try:
    compile_plan(BFSPlan(layout=("group", "member")), None)
    print("no raise")
except ValueError as e:
    print("raises:", e)
""", extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=6"})
    assert "raises:" in out and "power of two" in out


def test_mesh_axis_cover_mismatch_is_value_error():
    out = run_sub(PREAMBLE + """
g, ev, core, chunks = sorted_graph(8, seed=1, threshold=8)
pg = PreparedGraph(ev=ev, degree=g.degree, core=core, chunks=chunks)
mesh = make_mesh((2, 4), ("group", "member"))
try:
    compile_plan(BFSPlan(layout=("root",)), pg, mesh=mesh)
    print("no raise")
except ValueError as e:
    print("raises:", e)
""")
    assert "raises:" in out and "cover" in out


# ---------------------------------------------------------------------------
# Deprecation shims: warn + bitwise-identical to the plan run (scale 12).
# ---------------------------------------------------------------------------

def _scale12():
    from repro.core import (
        build_csr, build_heavy_core, chunk_edge_view, degree_reorder,
        edge_view, generate_edges,
    )
    from repro.core.reorder import relabel_edges

    edges = generate_edges(11, 12)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    core = build_heavy_core(g, threshold=32)
    ev = edge_view(g)
    return g, ev, core, chunk_edge_view(ev)


def test_single_device_shims_warn_and_match_plan_scale12():
    from repro.core import bfs_batch, hybrid_bfs, run_graph500_batched

    g, ev, core, chunks = _scale12()
    pg = PreparedGraph(ev=ev, degree=g.degree, core=core, chunks=chunks)
    roots = np.asarray([0, 3, 17, 29], np.int32)

    # hybrid_bfs <-> per-root plan
    plan1 = BFSPlan(engine="bitmap", layout=(), batch_roots=False)
    want1 = compile_plan(plan1, pg).bfs(17)
    with pytest.warns(DeprecationWarning, match="hybrid_bfs"):
        got1 = hybrid_bfs(ev, g.degree, 17, core=core, engine="bitmap",
                          chunks=chunks)
    np.testing.assert_array_equal(np.asarray(got1.parent),
                                  np.asarray(want1.parent))
    np.testing.assert_array_equal(np.asarray(got1.level),
                                  np.asarray(want1.level))

    # bfs_batch <-> batched plan
    plan2 = BFSPlan(layout=(), batch_roots=True)
    want2 = compile_plan(plan2, pg).bfs(roots)
    with pytest.warns(DeprecationWarning, match="bfs_batch"):
        got2 = bfs_batch(ev, g.degree, roots, core=core, chunks=chunks)
    np.testing.assert_array_equal(np.asarray(got2.parent),
                                  np.asarray(want2.parent))

    # run_graph500_batched <-> CompiledBFS.run
    want3 = compile_plan(plan2, pg).run(roots).run
    with pytest.warns(DeprecationWarning, match="run_graph500_batched"):
        got3 = run_graph500_batched(ev, g.degree, roots, core=core)
    assert got3.batched and got3.edges == want3.edges
    assert got3.validated == want3.validated == [True] * len(roots)


def test_mesh_shims_warn_and_match_plan_scale12():
    out = run_sub(PREAMBLE + """
from repro.core import bfs_batch_sharded, run_graph500_sharded
from repro.core.distributed_bfs import gather_result, make_dist_bfs, shard_graph

g, ev, core, chunks = sorted_graph(12, seed=11, threshold=32)
pg = PreparedGraph(ev=ev, degree=g.degree, core=core, chunks=chunks)
V = g.num_vertices
roots = np.asarray([0, 3, 17, 29, 40, 41, 42, 43], np.int32)

def warned(w, name):
    return any(issubclass(x.category, DeprecationWarning)
               and name in str(x.message) for x in w)

# bfs_batch_sharded <-> ("root",) plan
mesh_r = make_mesh((4,), ("root",))
want = compile_plan(BFSPlan(layout=("root",)), pg, mesh=mesh_r).bfs(roots)
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    got = bfs_batch_sharded(ev, g.degree, roots, mesh=mesh_r, core=core,
                            chunks=chunks)
assert warned(w, "bfs_batch_sharded")
assert np.array_equal(np.asarray(got.parent), np.asarray(want.parent))

# make_dist_bfs <-> ("group", "member") plan
mesh_v = make_mesh((2, 4), ("group", "member"))
plan_v = BFSPlan(layout=("group", "member"))
want_v = compile_plan(plan_v, pg, mesh=mesh_v).bfs(roots)  # batched plan
sg = shard_graph(np.asarray(ev.src), np.asarray(ev.dst),
                 np.asarray(ev.valid), V, 8)
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    fn = make_dist_bfs(mesh_v, sg, core=core, batched=True)
assert warned(w, "make_dist_bfs")
got_v = fn(jnp.asarray(roots))
assert np.array_equal(np.asarray(got_v.parent), np.asarray(want_v.parent))

# run_graph500_sharded <-> vertex plan runner
want_r = compile_plan(plan_v, pg, mesh=mesh_v).run(roots[:4]).run
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    got_r = run_graph500_sharded(mesh_v, sg, g.degree, roots[:4], core=core,
                                 ev=ev)
assert warned(w, "run_graph500_sharded")
assert got_r.edges == want_r.edges and got_r.all_valid
print("OK")
""")
    assert "OK" in out


# ---------------------------------------------------------------------------
# Composed 3-axis plan (the tentpole acceptance path).
# ---------------------------------------------------------------------------

def test_composed_2x2x2_plan_matches_single_device_scale12():
    """Acceptance: BFSPlan(layout=("root","group","member")) on a forced
    2x2x2 host mesh, parents bitwise-identical to the single-device
    bitmap engine at scale 12."""
    out = run_sub(PREAMBLE + """
g, ev, core, chunks = sorted_graph(12, seed=11, threshold=32)
pg = PreparedGraph(ev=ev, degree=g.degree, core=core, chunks=chunks)
V = g.num_vertices
roots = np.asarray([0, 3, 17, 29, 40, 41, 42, 43], np.int32)

base = compile_plan(BFSPlan(layout=(), batch_roots=True), pg).bfs(roots)
plan = BFSPlan(layout=("root", "group", "member"), mesh_shape=(2, 2, 2))
compiled = compile_plan(plan, pg)
assert compiled.mesh_axes == {"root": 2, "group": 2, "member": 2}
res = compiled.bfs(roots)
assert np.array_equal(np.asarray(res.parent)[:, :V], np.asarray(base.parent))
assert np.array_equal(np.asarray(res.level)[:, :V], np.asarray(base.level))

# roots not a multiple of the root axis: padded and sliced
res5 = compiled.bfs(roots[:5])
assert res5.parent.shape[0] == 5
assert np.array_equal(np.asarray(res5.parent)[:, :V],
                      np.asarray(base.parent)[:5])

# the uniform runner view validates and reports TEPS
result = compiled.run(roots)
assert result.parent.shape == (len(roots), V)
assert result.run.all_valid and result.run.harmonic_mean_teps > 0
assert result.plan is plan and result.mesh_axes["root"] == 2
print("OK")
""")
    assert "OK" in out


def test_pipeline_mesh3_rung_single_device():
    """pre-g500-mesh3 rung degrades gracefully to (1, 1, 1) on the main
    pytest process's single device and still validates."""
    from repro.core import Graph500Config, run

    cfg = Graph500Config.ladder("pre-g500-mesh3", scale=9, n_roots=4)
    assert cfg.to_plan().layout == ("root", "group", "member")
    _, result = run(cfg)
    assert result.batched and result.all_valid
    assert result.harmonic_mean_teps > 0


# ---------------------------------------------------------------------------
# Dry-run cells lower the plan-compiled resident engine.
# ---------------------------------------------------------------------------

def test_graph500_cell_lowers_resident_engine():
    out = run_sub("""
import re
import jax
from repro.util import make_mesh
from repro.launch.input_specs import build_cell

for shape, axes in (((2, 4), ("data", "model")),
                    ((2, 2, 2), ("pod", "data", "model"))):
    mesh = make_mesh(shape, axes)
    plan = build_cell("graph500", "bfs_s22", mesh)
    assert "vertex_sharded_program" in plan.note, plan.note
    txt = jax.jit(plan.step, in_shardings=plan.in_shardings,
                  out_shardings=plan.out_shardings).lower(*plan.args).as_text()
    ops = set(re.findall(r"stablehlo\\.(all_[a-z_]+)", txt))
    # the T3 two-phase exchange must be present in the lowering
    assert "all_gather" in ops and "all_to_all" in ops, (axes, ops)
    assert "stablehlo.while" in txt
print("OK")
""")
    assert "OK" in out


# ---------------------------------------------------------------------------
# Names a profiler trace finds: the engine's phase scopes and the host span
# of a call.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def per_root_s8():
    from repro.core import pipeline

    cfg = pipeline.Graph500Config.ladder("pre-g500", scale=8, n_roots=1)
    return compile_plan(cfg.to_plan(), pipeline.build(cfg))


def test_per_root_program_names_its_phases(per_root_s8):
    import re

    text = per_root_s8.lower(0).compile().as_text()
    phases = {p for m in re.findall(r'op_name="([^"]*)"', text)
              for p in m.split("/") if p.startswith("bfs.")}
    assert phases == {"bfs.init", "bfs.td_relax", "bfs.bu_core",
                      "bfs.bu_relax", "bfs.epilogue", "bfs.finish"}, phases


def test_bfs_call_is_a_host_span(per_root_s8, tmp_path):
    """``CompiledBFS.bfs`` writes one ``bfs.call`` span per call into a
    profiler trace, on the clock the device ops share."""
    import glob

    import jax
    from jax.profiler import ProfileData

    per_root_s8.bfs(0).parent.block_until_ready()      # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        for root in (0, 1):
            per_root_s8.bfs(root).parent.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events]
    assert names.count("bfs.call") == 2
