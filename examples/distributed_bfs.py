"""Mesh-sharded BFS through the plan API on 8 host devices.

    PYTHONPATH=src python examples/distributed_bfs.py
    PYTHONPATH=src python examples/distributed_bfs.py --inject

Demonstrates the spec→plan→runner lifecycle (DESIGN.md §10): one
scale-12 graph, the three vertex-sharded exchange wirings (T3 monitor
collectives over a (group, member) mesh), both vertex partitions, and
the composed
("root", "group", "member") 2x2x2 plan — the 8 search keys split over
the root axis OUTSIDE the vertex-sharded SPMD program.  Every layout's
parents are asserted bitwise-identical to the single-device bitmap
engine, so this script is also the CI composed-mesh smoke.

``--inject`` runs the fault-injection recovery demo instead (DESIGN.md
§13): a persistent exchange corruption is detected by checked execution
and recovered through the retry → degraded-fallback path, then a
persistent parent-scatter corruption (which survives the fallback too)
drives every root into quarantine.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax

from repro.core import (
    PreparedGraph, TraversalPlan, build_csr, build_heavy_core,
    compile_plan, degree_reorder, edge_view, generate_edges,
)
from repro.core.reference import reference_bfs
from repro.core.reorder import relabel_edges

print(f"devices: {len(jax.devices())}")

edges = generate_edges(5, 12)
g0 = build_csr(edges)
r = degree_reorder(g0.degree)          # T2a: heavy vertices get low ids
g = build_csr(relabel_edges(edges, r))
core = build_heavy_core(g, threshold=32)
ev = edge_view(g)
pg = PreparedGraph(ev=ev, degree=g.degree, core=core)
V = g.num_vertices
roots = np.arange(8, dtype=np.int32)
print(f"graph: {V} vertices, {int(g.nnz)} directed edges")

# single-device oracle: the bitmap-resident engine, all roots one program
base = compile_plan(TraversalPlan(layout=(), batch_roots=True), pg)
base_res = base.bfs(roots)
base_parent = np.asarray(base_res.parent)
_, l_ref = reference_bfs(np.asarray(g.row_offsets),
                         np.asarray(g.col_indices), 0)
assert np.array_equal(np.asarray(base_res.level)[0], l_ref)

if "--inject" in sys.argv[1:]:
    # Fault-injection recovery demo (DESIGN.md §13).  Faults are static:
    # the corruption is compiled into the program, the clean path stays
    # byte-identical.
    from repro.core import FaultSpec

    plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 4))

    # 1. persistent exchange corruption: every per-level delta from every
    #    shard is zeroed from level 1 on — the traversal stalls after the
    #    root's own neighborhood.  check="full" attributes it to the
    #    in-loop conservation sentinel AND the component spec check;
    #    retries can't help (the fault is persistent) but the degraded
    #    single-device fallback has no exchange, so every root recovers.
    f = FaultSpec(site="exchange", kind="zero", level=1, persistent=True)
    compiled = compile_plan(plan, pg, fault=f)
    res = compiled.run(roots, check="full", retries=1, fallback=True)
    run = res.run
    print(f"inject exchange/zero: detected={run.check_counts} "
          f"retries={run.retries} fallbacks={run.fallbacks} "
          f"quarantined={run.quarantined} valid={run.all_valid}")
    assert run.check_counts["component"] == 8
    assert run.check_counts["sentinel"] == 8
    assert run.retries == 8 and run.fallbacks == 8
    assert not run.quarantined and run.all_valid
    assert np.array_equal(res.parent, base_parent), \
        "recovered parents must match the clean single-device oracle"

    # 2. persistent parent-scatter corruption: newly found vertices are
    #    recorded as their own parent.  The depth check catches it, but
    #    the fault site exists on the degraded path too — retry AND
    #    fallback re-fail, so every root is quarantined and the harmonic
    #    mean excludes all of them.
    f2 = FaultSpec(site="parent", kind="self", level=1, persistent=True)
    compiled2 = compile_plan(plan, pg, fault=f2)
    res2 = compiled2.run(roots, check="post", retries=1, fallback=True)
    run2 = res2.run
    print(f"inject parent/self:  detected={run2.check_counts} "
          f"retries={run2.retries} fallbacks={run2.fallbacks} "
          f"quarantined={run2.quarantined}")
    assert run2.check_counts["depth"] == 8
    assert run2.retries == 8 and run2.fallbacks == 8
    assert run2.quarantined == list(range(8))
    assert run2.harmonic_mean_teps == 0.0
    print("INJECT OK")
    sys.exit(0)

# layer 2: vertex-sharded (2, 4) mesh, all three exchange wirings
for exchange in ("hier_or", "hier_gather", "flat"):
    plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 4),
                         exchange=exchange)
    res = compile_plan(plan, pg).bfs(roots)
    ok = np.array_equal(np.asarray(res.parent)[:, :V], base_parent)
    print(f"vertex-sharded 2x4 exchange={exchange:11s}: "
          f"bitwise_identical={ok}")
    assert ok, exchange

# the word-cyclic partition (paper eq. (3) at uint32-word granularity):
# the degree-sorted heavy words interleave round-robin across shards
# instead of piling onto shard 0; parents land back in global vertex
# order through the inverse reassembly permutation, still bitwise
# identical to the single-device engine.
from repro.core.distributed_bfs import shard_edge_skew

for partition in ("block", "word_cyclic"):
    plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 4),
                         partition=partition)
    compiled = compile_plan(plan, pg)
    skew = shard_edge_skew(compiled.graph.sharded)
    res = compiled.bfs(roots)
    ok = np.array_equal(np.asarray(res.parent)[:, :V], base_parent)
    print(f"vertex-sharded 2x4 partition={partition:11s}: "
          f"bitwise_identical={ok} "
          f"edge_skew_max_over_mean={skew['max_over_mean']:.2f}")
    assert ok, partition

# layer 1 x layer 2 composed: 2x2x2 — roots split over their own axis
plan = TraversalPlan(layout=("root", "group", "member"),
                     mesh_shape=(2, 2, 2))
compiled = compile_plan(plan, pg)
result = compiled.run(roots)
ok = np.array_equal(result.parent, base_parent)
print(f"composed 2x2x2 plan: bitwise_identical={ok} "
      f"valid={result.run.all_valid} mesh={compiled.mesh_axes} "
      f"hmean_TEPS={result.run.harmonic_mean_teps:.3g}")
assert ok and result.run.all_valid

# auto-tuned plan (DESIGN.md §11): the persisted TUNED_PLANS.json winner
# for (scale=12, 8 devices, cpu) — swept, parity-checked and recorded by
# `python -m repro.core.tune`; consumed here exactly like a hand-written
# plan.  Explicit fields still override (demonstrated via overrides=).
from repro.core.tune import tuned_plan

tp = tuned_plan(12)
assert tp is not None, "TUNED_PLANS.json has no (scale12, dev8, cpu) entry"
res_t = compile_plan(tp, pg).bfs(roots)
ok = np.array_equal(np.asarray(res_t.parent)[:, :V], base_parent)
print(f"tuned plan layout={tp.layout} mesh={tp.mesh_shape} "
      f"exchange={tp.exchange}: bitwise_identical={ok}")
assert ok
print("OK")
