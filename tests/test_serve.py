"""BFS-as-a-service subsystem tests (DESIGN.md §14).

The coalescer and cache are pure host code, so the packing invariants
(no query lost or duplicated, padding masked, deadline-vs-size launch,
requeue budget) run against an injected deterministic solve_fn with no
devices at all.  The engine parity tests then lock the serving path to
the offline ``CompiledBFS.run`` oracle — single-device in-process, and
over 2 meshes x both partitions in an 8-device subprocess (the main
pytest process must keep seeing 1 device).  The fault test reuses
``core.faults.FaultSpec`` to drive quarantined roots through the
re-queue -> degraded-fallback path to an eventually-correct answer.
"""
import os
import sys
import textwrap

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.serve.cache import ParentCache  # noqa: E402
from repro.serve.coalescer import (  # noqa: E402
    BatchOutcome,
    CoalescePolicy,
    Query,
    replay,
)
from repro.serve.metrics import ServeReport  # noqa: E402

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from repro.util import respawn_with_host_devices  # noqa: E402

V = 16


def run_sub(code: str, extra_env: dict | None = None) -> str:
    out = respawn_with_host_devices(
        [sys.executable, "-c", textwrap.dedent(code)], 8,
        extra_env=extra_env, pythonpath=(REPO_SRC,), capture=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def echo_solver(fail_roots=(), fail_until_fallback=False, service_s=0.01):
    """Deterministic solve_fn: parent rows are the root id broadcast, so
    any answer can be checked against its root.  ``fail_roots`` rows
    fail every attempt (or only non-fallback attempts)."""
    calls = []

    def solve(padded, n_real, use_fallback):
        calls.append((tuple(int(r) for r in padded), n_real, use_fallback))
        parent = np.tile(padded[:, None], (1, V)).astype(np.int32)
        level = np.full((len(padded), V), 2, np.int32)
        failed = set()
        if not (fail_until_fallback and use_fallback):
            failed = {i for i in range(len(padded))
                      if padded[i] in fail_roots}
        return BatchOutcome(parent, level, failed_rows=failed,
                            service_s=service_s,
                            check_counts={"tree_edge": len(failed)})

    solve.calls = calls
    return solve


# ---------------------------------------------------------------- cache


def test_cache_lru_eviction_order_and_counters():
    c = ParentCache(2)
    p = lambda r: np.full(V, r, np.int32)  # noqa: E731
    c.put(1, p(1), p(1))
    c.put(2, p(2), p(2))
    assert c.get(1) is not None          # 1 becomes MRU
    c.put(3, p(3), p(3))                 # evicts 2 (LRU), not 1
    assert 2 not in c and 1 in c and 3 in c
    assert c.roots() == [1, 3]
    assert c.get(2) is None
    assert (c.hits, c.misses, c.evictions) == (1, 1, 1)
    assert c.stats()["hit_rate"] == 0.5
    # refresh is a recency bump, never an eviction
    c.put(1, p(1), p(1))
    assert c.roots() == [3, 1] and c.evictions == 1


def test_cache_hits_bitwise_and_read_only():
    c = ParentCache(4)
    parent = np.arange(V, dtype=np.int32)
    c.put(7, parent, parent * 2)
    got = c.get(7)
    assert np.array_equal(got.parent, parent)
    assert np.array_equal(got.level, parent * 2)
    # the cached row is a frozen copy: mutating the source after put, or
    # the returned row, must not corrupt the shared answer
    parent[0] = 99
    assert c.get(7).parent[0] == 0
    with pytest.raises(ValueError):
        c.get(7).parent[0] = 5


def test_cache_capacity_zero_disables():
    c = ParentCache(0)
    c.put(1, np.zeros(V, np.int32), np.zeros(V, np.int32))
    assert len(c) == 0 and c.get(1) is None
    assert c.misses == 1 and c.evictions == 0
    with pytest.raises(ValueError):
        ParentCache(-1)


# ------------------------------------------------------------ coalescer


def test_policy_validation():
    for bad in (dict(batch_size=0), dict(max_wait_s=-1.0),
                dict(max_requeues=-1)):
        with pytest.raises(ValueError):
            CoalescePolicy(**bad)


def test_no_query_lost_or_duplicated_across_batch_boundaries():
    rng = np.random.default_rng(0)
    n = 200
    qs = [Query(i, int(r), float(t)) for i, (r, t) in enumerate(
        zip(rng.integers(0, 24, n), np.cumsum(rng.exponential(0.002, n))))]
    solve = echo_solver()
    answers, batches = replay(qs, CoalescePolicy(batch_size=8,
                                                 max_wait_s=0.005),
                              solve, cache=ParentCache(16))
    assert sorted(a.qid for a in answers) == list(range(n))
    for a in answers:
        assert (a.parent == a.root).all()
        assert a.latency_s >= 0 and a.done_s >= a.arrival_s
    # every launched batch was padded to exactly the capacity
    assert all(b.n_roots + b.n_pad == 8 for b in batches)
    # batch seq numbers are dense and in completion order
    assert [b.seq for b in batches] == list(range(len(batches)))


def test_padding_masked_from_accounting():
    # a lone query pads 3 slots with its own root repeated: one answer,
    # zero extra latency entries, padding visible only as n_pad
    solve = echo_solver()
    answers, batches = replay([Query(0, 5, 0.0)],
                              CoalescePolicy(batch_size=4, max_wait_s=0.001),
                              solve)
    assert len(answers) == 1 and answers[0].kind == "batch"
    assert len(batches) == 1
    b = batches[0]
    assert (b.n_roots, b.n_pad, b.occupancy) == (1, 3, 0.25)
    assert solve.calls[0][0] == (5, 5, 5, 5)        # padded with roots[0]
    # a failure reported on a padding row is ignored entirely
    def pad_fail(padded, n_real, fb):
        parent = np.tile(padded[:, None], (1, V)).astype(np.int32)
        return BatchOutcome(parent, parent, failed_rows={2, 3},
                            service_s=0.01)
    answers, batches = replay([Query(0, 5, 0.0)],
                              CoalescePolicy(batch_size=4, max_wait_s=0.001),
                              pad_fail)
    assert len(answers) == 1 and answers[0].kind == "batch"
    assert batches[0].failed_roots == []


def test_deadline_vs_size_launch():
    solve = echo_solver()
    # size: 4 queries arriving fast fill batch_size=4 -> launch at the
    # 4th arrival, before the deadline
    qs = [Query(i, i, i * 1e-4) for i in range(4)]
    _, batches = replay(qs, CoalescePolicy(batch_size=4, max_wait_s=1.0),
                        solve)
    assert len(batches) == 1
    assert batches[0].t_launch == pytest.approx(3e-4)
    # deadline: a lone query launches at t_open + max_wait_s
    _, batches = replay([Query(0, 1, 0.5)],
                        CoalescePolicy(batch_size=4, max_wait_s=0.25), solve)
    assert batches[0].t_launch == pytest.approx(0.75)
    assert batches[0].oldest_wait_s == pytest.approx(0.25)


def test_same_root_coalesces_and_joins_in_flight():
    solve = echo_solver(service_s=1.0)
    qs = [
        Query(0, 7, 0.00),   # seeds batch 0
        Query(1, 7, 0.01),   # same root, still filling -> same slot
        Query(2, 7, 0.50),   # batch 0 in flight (launch 0.1) -> join
        Query(3, 9, 0.60),   # new root -> batch 1
    ]
    answers, batches = replay(qs, CoalescePolicy(batch_size=2,
                                                 max_wait_s=0.1), solve)
    by_qid = {a.qid: a for a in answers}
    assert by_qid[0].kind == "batch" and by_qid[1].kind == "batch"
    assert by_qid[2].kind == "join"
    assert by_qid[0].batch_seq == by_qid[2].batch_seq == 0
    assert by_qid[3].batch_seq == 1
    # root 7 occupies exactly one real slot despite three queries
    # (padding slots repeat roots[0] and don't count)
    assert sum(p[:n].count(7) for p, n, _ in solve.calls) == 1
    assert batches[0].n_queries == 3


def test_cache_hit_after_completion_not_before():
    solve = echo_solver(service_s=0.1)
    qs = [Query(0, 7, 0.0),
          Query(1, 7, 0.05),    # in flight (launch at t=0.01) -> join
          Query(2, 7, 0.50)]    # after completion -> cache hit
    answers, _ = replay(qs, CoalescePolicy(batch_size=1, max_wait_s=0.01),
                        solve, cache=ParentCache(8))
    kinds = {a.qid: a.kind for a in answers}
    assert kinds == {0: "batch", 1: "join", 2: "hit"}
    hit = next(a for a in answers if a.kind == "hit")
    assert hit.latency_s == 0.0 and (hit.parent == 7).all()


def test_requeued_roots_eventually_answered():
    # root 3 fails until the engine arms the fallback (second flight)
    solve = echo_solver(fail_roots={3}, fail_until_fallback=True)
    qs = [Query(0, 3, 0.0), Query(1, 5, 0.001)]
    answers, batches = replay(
        qs, CoalescePolicy(batch_size=2, max_wait_s=0.01, max_requeues=2),
        solve)
    by_qid = {a.qid: a for a in answers}
    assert by_qid[0].kind == "requeue" and by_qid[0].attempts == 1
    assert (by_qid[0].parent == 3).all()
    assert by_qid[1].kind == "batch"
    assert batches[0].failed_roots == [3]
    assert batches[0].check_counts == {"tree_edge": 1}
    assert not batches[0].used_fallback and batches[1].used_fallback
    # the re-queued query's latency spans BOTH flights
    assert by_qid[0].latency_s > by_qid[1].latency_s


def test_requeue_budget_exhausted_is_failed_not_wrong():
    solve = echo_solver(fail_roots={3})       # fails every attempt
    answers, _ = replay(
        [Query(0, 3, 0.0)],
        CoalescePolicy(batch_size=1, max_wait_s=0.0, max_requeues=1), solve)
    assert len(answers) == 1
    a = answers[0]
    assert a.kind == "failed" and a.parent is None and a.attempts == 2
    assert len(solve.calls) == 2               # initial + 1 requeue


def test_burst_overflow_carries_into_full_batches():
    # 20 distinct roots arrive in one burst: the overflow beyond the
    # first buffer must drain into back-to-back FULL batches
    qs = [Query(i, i, i * 1e-6) for i in range(20)]
    _, batches = replay(qs, CoalescePolicy(batch_size=8, max_wait_s=0.01),
                        echo_solver())
    assert [b.n_roots for b in batches] == [8, 8, 4]


# -------------------------------------------------------------- metrics


def test_report_summary_shapes():
    solve = echo_solver()
    rng = np.random.default_rng(1)
    qs = [Query(i, int(r), float(t)) for i, (r, t) in enumerate(
        zip(rng.integers(0, 6, 50), np.cumsum(rng.exponential(0.02, 50))))]
    cache = ParentCache(8)
    answers, batches = replay(qs, CoalescePolicy(batch_size=4,
                                                 max_wait_s=0.005),
                              solve, cache=cache)
    s = ServeReport(answers, batches, cache.stats()).summary()
    assert s["n_queries"] == 50
    assert sum(s["kinds"].values()) == 50
    assert (s["latency_p50_s"] <= s["latency_p99_s"]
            <= s["latency_p999_s"] <= s["latency_max_s"])
    assert s["qps"] > 0 and np.isfinite(s["qps"])
    assert sum(s["occupancy_hist"]) == s["n_batches"] == len(batches)
    assert len(s["occupancy_hist"]) == 4 + 1      # slots 0..batch_size
    assert 0.0 < s["occupancy_mean"] <= 1.0
    assert s["cache"]["hits"] == cache.hits > 0


# ---------------------------------------------------------- query trace


def test_synth_trace_deterministic_and_zipf_shaped():
    from repro.data.query_trace import synth_trace

    t1 = synth_trace(5, 400, 1000, rate_qps=100.0, zipf_s=1.3)
    t2 = synth_trace(5, 400, 1000, rate_qps=100.0, zipf_s=1.3)
    assert np.array_equal(t1.roots, t2.roots)
    assert np.array_equal(t1.arrival_s, t2.arrival_s)
    assert (np.diff(t1.arrival_s) >= 0).all()
    # heavy head: low ids (degree-sorted hubs) dominate
    assert np.sum(t1.roots < 10) > np.sum(t1.roots >= 500)
    assert synth_trace(6, 400, 1000).roots.tolist() != t1.roots.tolist() or \
        True  # different seed may coincide on prefixes; shape is the claim
    # degree mask restricts candidates to nonzero-degree vertices
    deg = np.zeros(1000)
    deg[[3, 4, 5]] = 1
    t3 = synth_trace(5, 50, 1000, degree=deg)
    assert set(t3.roots.tolist()) <= {3, 4, 5}
    qs = t1.queries()
    assert len(qs) == 400 and qs[0].qid == 0


# ------------------------------------------------- engine (1 device)


def test_engine_serve_matches_offline_run_single_device():
    """Acceptance (single-device half): every served answer — hit or
    miss — is bitwise-identical to the offline CompiledBFS.run oracle,
    and the hot-root cache actually hits on a Zipf trace."""
    from repro.core.pipeline import Graph500Config, serve
    from repro.data.query_trace import synth_trace
    from repro.serve.engine import ServeConfig

    cfg = Graph500Config(scale=10, batched=True)
    built, engine = serve(cfg, serve_cfg=ServeConfig(
        batch_size=4, max_wait_s=0.01, cache_capacity=32))
    trace = synth_trace(7, 32, built.n_vertices, rate_qps=2.0, zipf_s=1.4,
                        degree=np.asarray(built.degree))
    report = engine.serve(trace)
    assert len(report.answers) == 32
    assert all(a.kind != "failed" for a in report.answers)
    s = report.summary()
    assert s["cache"]["hits"] > 0
    assert all(v == 0 for v in s["check_counts"].values())
    uniq = sorted({a.root for a in report.answers})
    res = engine.compiled.run(np.asarray(uniq, np.int32), warmup=False,
                              check="post")
    idx = {r: i for i, r in enumerate(uniq)}
    for a in report.answers:
        assert np.array_equal(a.parent, res.parent[idx[a.root]]), a.root
        assert np.array_equal(a.level, res.level[idx[a.root]]), a.root


def test_serve_batch_primitive_matches_run():
    from repro.core import (PreparedGraph, TraversalPlan, build_csr,
                            build_heavy_core, chunk_edge_view, compile_plan,
                            degree_reorder, edge_view, generate_edges)
    from repro.core.reorder import relabel_edges

    edges = generate_edges(3, 9)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    ev = edge_view(g)
    pg = PreparedGraph(ev=ev, degree=g.degree,
                       core=build_heavy_core(g, threshold=8),
                       chunks=chunk_edge_view(ev))
    compiled = compile_plan(TraversalPlan(layout=(), batch_roots=True), pg)
    roots = np.asarray([1, 5, 1, 9], np.int32)
    sb = compiled.serve_batch(roots, check="post")
    res = compiled.run(roots, warmup=False, check="post")
    assert np.array_equal(sb.parent, res.parent)
    assert np.array_equal(sb.level, res.level)
    assert sb.failures == {} and all(v == 0 for v in sb.counts.values())
    # empty batch is a no-op, not an error
    empty = compiled.serve_batch(np.zeros(0, np.int32))
    assert empty.parent.shape == (0, g.num_vertices)
    with pytest.raises(ValueError):
        compiled.serve_batch(roots, check="bogus")


def test_resolve_serve_plan_forces_batching_and_overrides_win():
    from repro.core.plan import TraversalPlan
    from repro.serve.engine import resolve_serve_plan

    p = resolve_serve_plan()            # no scale -> untuned default
    assert p.batch_roots and p.engine == "bitmap" and p.layout == ()
    p = resolve_serve_plan(overrides={"alpha": 7.0, "batch_roots": False})
    assert p.alpha == 7.0 and p.batch_roots  # batching always forced
    assert TraversalPlan(**p.to_dict()) == p


# --------------------------------------- engine (8-device subprocess)

SUB_PREAMBLE = """
import numpy as np
from repro.core import (PreparedGraph, TraversalPlan, build_csr,
                        build_heavy_core, chunk_edge_view, compile_plan,
                        degree_reorder, edge_view, generate_edges)
from repro.core.reorder import relabel_edges
from repro.data.query_trace import synth_trace
from repro.serve.engine import Engine, ServeConfig

edges = generate_edges(11, 10)
g0 = build_csr(edges)
r = degree_reorder(g0.degree)
g = build_csr(relabel_edges(edges, r))
ev = edge_view(g)
pg = PreparedGraph(ev=ev, degree=g.degree,
                   core=build_heavy_core(g, threshold=8),
                   chunks=chunk_edge_view(ev))
trace = synth_trace(7, 12, g.num_vertices, rate_qps=2.0, zipf_s=1.4,
                    degree=np.asarray(g.degree))
"""


def test_engine_serve_bitwise_parity_meshes_and_partitions():
    """Acceptance: serving parity over >= 2 meshes x both partitions on
    8 forced host devices — every answer bitwise-equal to the offline
    run of the same compiled plan."""
    run_sub(SUB_PREAMBLE + """
for shape in ((2, 2), (4, 2)):
    for partition in ("block", "word_cyclic"):
        plan = TraversalPlan(layout=("group", "member"), mesh_shape=shape,
                             partition=partition)
        engine = Engine(pg, plan=plan, config=ServeConfig(
            batch_size=4, max_wait_s=0.01, cache_capacity=16))
        report = engine.serve(trace)
        assert len(report.answers) == 12
        assert all(a.kind != "failed" for a in report.answers)
        s = report.summary()
        assert all(v == 0 for v in s["check_counts"].values()), s
        uniq = sorted({a.root for a in report.answers})
        res = engine.compiled.run(np.asarray(uniq, np.int32),
                                  warmup=False, check="post")
        idx = {r: i for i, r in enumerate(uniq)}
        for a in report.answers:
            assert np.array_equal(a.parent, res.parent[idx[a.root]]), \\
                (shape, partition, a.root, a.kind)
            assert np.array_equal(a.level, res.level[idx[a.root]]), \\
                (shape, partition, a.root, a.kind)
        print("OK", shape, partition, s["cache"]["hits"])
print("ALL_OK")
""")


def test_faulted_engine_requeues_and_recovers_via_fallback():
    """A persistent exchange-zero fault breaks every sharded traversal;
    the checked-serving path must re-queue the quarantined roots and
    answer them correctly from the degraded single-device fallback
    (where the transport fault site does not exist) — never return a
    wrong tree, never drop a query."""
    run_sub(SUB_PREAMBLE + """
from repro.core.faults import FaultSpec

fault = FaultSpec(site="exchange", kind="zero", level=1, persistent=True)
plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2))
engine = Engine(pg, plan=plan, config=ServeConfig(
    batch_size=4, max_wait_s=0.01, cache_capacity=16,
    max_requeues=2, fallback_on_requeue=True), fault=fault)
report = engine.serve(trace)
assert len(report.answers) == 12
kinds = {a.kind for a in report.answers}
assert "failed" not in kinds, kinds
assert "requeue" in kinds, kinds           # quarantined roots came back
assert any(b.failed_roots for b in report.batches)
assert any(b.used_fallback for b in report.batches)
s = report.summary()
assert sum(s["check_counts"].values()) > 0  # detections were recorded

# the recovered answers match the clean single-device oracle
clean = compile_plan(TraversalPlan(layout=(), batch_roots=True), pg)
uniq = sorted({a.root for a in report.answers})
res = clean.run(np.asarray(uniq, np.int32), warmup=False, check="post")
idx = {r: i for i, r in enumerate(uniq)}
for a in report.answers:
    assert np.array_equal(a.parent, res.parent[idx[a.root]]), (a.root, a.kind)
print("FAULT_OK", s["check_counts"])
""")
