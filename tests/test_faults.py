"""Fault-injection + checked-execution tests (DESIGN.md §13).

The detection matrix: every fault class from
:data:`repro.core.faults.FAULT_CLASSES`, injected into the real code
paths under the three exchange wirings and both vertex partitions, must
be CAUGHT by ``check="full"`` and attributed to the expected named
check — and combinations where the fault's site is not wired on that
exchange (plus fully clean runs) must report ZERO failures (no false
positives).  Multi-device cases run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps seeing 1 device.

Matrix cost knobs: ``FAULT_MATRIX_SCALE`` (graph scale, default 10) and
``FAULT_MATRIX_FULL=1`` (run every fault-class x exchange x partition
combination instead of the representative tier-1 subset — the CI fault
leg sets this at scale 12).
"""
import os
import sys
import textwrap

import numpy as np
import pytest

from repro.core import PreparedGraph, TraversalPlan, compile_plan
from repro.core.faults import FAULT_CLASSES, FAULT_KINDS, FAULT_SITES, FaultSpec

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from repro.util import respawn_with_host_devices  # noqa: E402


def run_sub(code: str, extra_env: dict | None = None) -> str:
    # the CI fault leg (FAULT_MATRIX_FULL=1, scale 12) compiles ~100
    # faulted programs in one subprocess and raises this
    timeout = int(os.environ.get("FAULT_SUB_TIMEOUT", "900"))
    out = respawn_with_host_devices(
        [sys.executable, "-c", textwrap.dedent(code)], 8,
        extra_env=extra_env, pythonpath=(REPO_SRC,), capture=True,
        timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def small_graph(scale=9, seed=11):
    from repro.core import (build_csr, build_heavy_core, degree_reorder,
                            edge_view, generate_edges)
    from repro.core.reorder import relabel_edges

    edges = generate_edges(seed, scale)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    ev = edge_view(g)
    return PreparedGraph(ev=ev, degree=g.degree,
                         core=build_heavy_core(g, threshold=32))


# ---------------------------------------------------------------------------
# FaultSpec validation + plumbing (no devices needed)
# ---------------------------------------------------------------------------

def test_faultspec_validates_site_and_kind():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec(site="bogus", kind="zero")
    with pytest.raises(ValueError, match="unknown kind"):
        FaultSpec(site="exchange", kind="stale")
    f = FaultSpec(site="parent", kind="self", level=2, persistent=True)
    assert "level>=2" in f.describe()
    assert hash(f) == hash(FaultSpec(site="parent", kind="self", level=2,
                                     persistent=True))
    # one class per (site, kind) pair: 5 distinct fault classes
    assert FAULT_SITES == ("exchange", "parent", "inter_group")
    assert len(FAULT_CLASSES) == 5
    assert FAULT_CLASSES == tuple(
        (s, k) for s in FAULT_SITES for k in FAULT_KINDS[s])


def test_fault_rejects_reference_engine():
    pg = small_graph()
    with pytest.raises(ValueError, match="engine='bitmap'"):
        compile_plan(TraversalPlan(engine="reference", layout=(),
                                   batch_roots=False), pg,
                     fault=FaultSpec(site="parent", kind="self"))


def test_run_rejects_unknown_check_mode():
    pg = small_graph()
    c = compile_plan(TraversalPlan(layout=(), batch_roots=True), pg)
    with pytest.raises(ValueError, match="check must be"):
        c.run(np.arange(2, dtype=np.int32), check="bogus")


# ---------------------------------------------------------------------------
# Batched validation (the satellite replacing the per-root host loop)
# ---------------------------------------------------------------------------

def test_validate_batch_matches_per_root_validate():
    import jax.numpy as jnp
    from repro.core import validate, validate_batch

    pg = small_graph()
    roots = np.arange(6, dtype=np.int32)
    c = compile_plan(TraversalPlan(layout=(), batch_roots=True), pg)
    res = c.bfs(roots)
    val = validate_batch(pg.ev, res.parent, res.level, roots)
    assert val.ok.shape == (6,)
    for i, r in enumerate(roots):
        from repro.core.hybrid_bfs import BFSResult
        single = validate(pg.ev, BFSResult(parent=res.parent[i],
                                           level=res.level[i], stats=None),
                          jnp.int32(int(r)))
        for field in val._fields:
            assert bool(getattr(val, field)[i]) == bool(
                getattr(single, field)), (field, i)


def test_failure_report_counts_and_attribution():
    from repro.core.validate import CHECK_NAMES, failure_report

    pg = small_graph()
    roots = np.arange(4, dtype=np.int32)
    c = compile_plan(TraversalPlan(layout=(), batch_roots=True), pg)
    res = c.run(roots, check="post")
    counts = res.run.check_counts
    assert set(CHECK_NAMES) <= set(counts)
    assert all(v == 0 for v in counts.values())
    assert res.run.check_failures == {}
    assert res.run.all_valid


# ---------------------------------------------------------------------------
# Single-device detection + recovery policy (in-process, scale 9)
# ---------------------------------------------------------------------------

def test_single_device_parent_fault_detected_and_quarantined():
    pg = small_graph()
    roots = np.arange(4, dtype=np.int32)
    f = FaultSpec(site="parent", kind="self", level=1, persistent=True)
    c = compile_plan(TraversalPlan(layout=(), batch_roots=True), pg, fault=f)
    res = c.run(roots, check="post")
    run = res.run
    assert run.check_counts["depth"] == 4
    assert all("depth" in names for names in run.check_failures.values())
    assert not run.all_valid
    # quarantine zeroes the failing TEPS so the hmean excludes them
    assert run.quarantined == [0, 1, 2, 3]
    assert run.harmonic_mean_teps == 0.0
    # the () batched bitmap plan IS the degraded shape: no fallback exists
    res2 = c.run(roots, check="post", retries=2, fallback=True)
    assert res2.run.retries == 8 and res2.run.fallbacks == 0
    assert res2.run.quarantined == [0, 1, 2, 3]


def test_single_device_level_scoped_fault_spares_other_roots():
    # root predicate: only root 2 is corrupted; the others stay valid
    pg = small_graph()
    roots = np.arange(4, dtype=np.int32)
    f = FaultSpec(site="parent", kind="offset", level=1, persistent=True,
                  root=2)
    c = compile_plan(TraversalPlan(layout=(), batch_roots=True), pg, fault=f)
    run = c.run(roots, check="post").run
    assert set(run.check_failures) == {2}
    assert set(run.check_failures[2]) & {"depth", "tree_edge"}
    assert run.quarantined == [2]
    assert run.validated == [True, True, False, True]
    assert run.harmonic_mean_teps > 0.0   # 3 healthy roots still count


def test_clean_full_check_has_zero_false_positives():
    pg = small_graph()
    roots = np.arange(4, dtype=np.int32)
    for batched in (True, False):
        c = compile_plan(TraversalPlan(layout=(), batch_roots=batched), pg)
        run = c.run(roots, check="full").run
        assert run.all_valid
        assert run.check_counts["sentinel"] == 0
        assert all(v == 0 for v in run.check_counts.values())
        assert not run.quarantined and run.retries == 0


def test_check_off_preserves_legacy_semantics():
    pg = small_graph()
    c = compile_plan(TraversalPlan(layout=(), batch_roots=True), pg)
    run = c.run(np.arange(2, dtype=np.int32), check="off").run
    assert run.validated == [] and not run.all_valid
    assert run.check_counts == {} and run.check_failures == {}


# ---------------------------------------------------------------------------
# Tuner: a crashing measurement is a recorded failure, not a dead sweep
# ---------------------------------------------------------------------------

def test_sweep_survives_raising_measurement():
    from repro.core import tune

    def boom(compiled, roots, reps):
        raise RuntimeError("injected measurement crash")

    report = tune.sweep(8, plans=[TraversalPlan(layout=(), batch_roots=True)],
                        measure=boom, log=lambda s: None)
    assert report.results == []
    assert len(report.skipped) == 1
    r = report.skipped[0]
    assert r.status == "failed"
    assert "RuntimeError" in r.reason and "injected measurement crash" in r.reason
    # failed rows must still render in the ranked table
    assert "failed:" in report.table()


# ---------------------------------------------------------------------------
# The sharded detection matrix (subprocess, 8 host devices)
# ---------------------------------------------------------------------------

MATRIX = """
import os
import numpy as np
from repro.core import (PreparedGraph, TraversalPlan, build_csr,
                        build_heavy_core, compile_plan, degree_reorder,
                        edge_view, generate_edges)
from repro.core.faults import FaultSpec
from repro.core.reorder import relabel_edges

scale = int(os.environ.get("FAULT_MATRIX_SCALE", "10"))
full = os.environ.get("FAULT_MATRIX_FULL") == "1"

edges = generate_edges(11, scale)
g0 = build_csr(edges)
r = degree_reorder(g0.degree)
g = build_csr(relabel_edges(edges, r))
ev = edge_view(g)
pg = PreparedGraph(ev=ev, degree=g.degree,
                   core=build_heavy_core(g, threshold=32))
roots = np.array([0], dtype=np.int32)

EXCHANGES = ("hier_or", "hier_gather", "flat")
PARTITIONS = ("block", "word_cyclic")

# which exchanges actually wire each injection site
ACTIVE = {
    "exchange": set(EXCHANGES),
    "parent": set(EXCHANGES),
    "inter_group": {"hier_or"},
}

SPECS = {
    ("exchange", "zero"): FaultSpec(site="exchange", kind="zero",
                                    level=1, persistent=True),
    ("exchange", "flip"): FaultSpec(site="exchange", kind="flip",
                                    level=1, device=0, word=0, bit=0),
    ("parent", "self"): FaultSpec(site="parent", kind="self",
                                  level=1, persistent=True),
    ("parent", "offset"): FaultSpec(site="parent", kind="offset",
                                    level=1, persistent=True),
    ("inter_group", "drop"): FaultSpec(site="inter_group", kind="drop",
                                       level=1, persistent=True),
}

# expected attribution: ("subset", S) = S must be among the failed
# checks; ("any", S) = at least one of S; ("exact", S) = exactly S.
EXPECT = {
    ("exchange", "zero"): ("subset", {"component", "sentinel"}),
    ("exchange", "flip"): ("exact", {"sentinel"}),
    ("parent", "self"): ("subset", {"depth"}),
    ("parent", "offset"): ("any", {"depth", "tree_edge"}),
    ("inter_group", "drop"): ("any", None),
}


def harmless_allowed(cls, ex, part):
    # The content-dependent combo where the injected corruption can be
    # PROVABLY consequence-free (asserted below: zero failures AND
    # parents bitwise equal to the clean run) rather than detected:
    # inter_group/drop under the block partition — w_loc is padded to
    # the kernel tile, so at matrix scales device 0 owns every real
    # vertex and the dropped non-first-group legs carry only padding
    # words.
    return cls == ("inter_group", "drop") and part == "block"

if full:
    cases = [(cls, ex, part) for cls in SPECS
             for ex in EXCHANGES for part in PARTITIONS]
    clean_cases = [(ex, part) for ex in EXCHANGES for part in PARTITIONS]
else:
    # representative tier-1 subset: every fault class once on an active
    # wiring (both partitions covered across the set), plus two
    # inactive-site combinations and one clean run as the
    # false-positive leg
    cases = [
        (("exchange", "zero"), "hier_or", "block"),
        (("exchange", "flip"), "hier_or", "word_cyclic"),
        (("parent", "self"), "flat", "block"),
        (("parent", "offset"), "hier_gather", "word_cyclic"),
        (("inter_group", "drop"), "hier_or", "word_cyclic"),
        (("inter_group", "drop"), "flat", "block"),              # inactive
        (("inter_group", "drop"), "hier_gather", "word_cyclic"),  # inactive
    ]
    clean_cases = [("hier_or", "block")]

n_detected = n_clean = n_harmless = 0

# clean legs first: the false-positive check AND the parent oracle the
# harmless-combo escape below compares against
clean_parent = {}
for (ex, part) in clean_cases:
    plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2),
                         exchange=ex, partition=part, batch_roots=True)
    c = compile_plan(plan, pg)
    for mode in ("post", "full"):
        res = c.run(roots, check=mode, warmup=False)
        run = res.run
        assert run.all_valid, (ex, part, mode, run.check_failures)
        assert all(v == 0 for v in run.check_counts.values()), (ex, part, mode)
    clean_parent[(ex, part)] = np.array(res.parent)
    n_clean += 1
    print(f"CLEAN    none x {ex} x {part}")

for (cls, ex, part) in cases:
    plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2),
                         exchange=ex, partition=part, batch_roots=True)
    c = compile_plan(plan, pg, fault=SPECS[cls])
    res = c.run(roots, check="full", warmup=False)
    run = res.run
    got = set()
    for names in run.check_failures.values():
        got |= set(names)
    tag = f"{cls[0]}/{cls[1]} x {ex} x {part}"
    if ex in ACTIVE[cls[0]]:
        if not got and harmless_allowed(cls, ex, part):
            # not detected -> must be PROVABLY harmless: bitwise equal
            # to the clean oracle for this wiring (no silent corruption)
            oracle = clean_parent.get((ex, part))
            assert oracle is not None, f"{tag}: no clean oracle leg"
            assert np.array_equal(np.array(res.parent), oracle), \
                f"{tag}: undetected fault CHANGED parents (silent corruption)"
            assert run.all_valid and not run.quarantined, tag
            n_harmless += 1
            print(f"HARMLESS {tag} (parents bitwise equal to clean)")
            continue
        assert got, f"{tag}: fault NOT detected"
        mode, exp = EXPECT[cls]
        if mode == "subset":
            assert exp <= got, f"{tag}: expected {exp} <= {got}"
        elif mode == "exact":
            assert got == exp, f"{tag}: expected exactly {exp}, got {got}"
        elif exp is not None:
            assert got & exp, f"{tag}: expected one of {exp}, got {got}"
        assert run.quarantined == [0], f"{tag}: bad quarantine {run.quarantined}"
        assert run.harmonic_mean_teps == 0.0
        n_detected += 1
        print(f"DETECTED {tag} -> {sorted(got)}")
    else:
        assert not got, f"{tag}: FALSE POSITIVE {got} (site not wired)"
        assert run.all_valid and not run.quarantined, tag
        n_clean += 1
        print(f"CLEAN    {tag}")

print(f"MATRIX_OK detected={n_detected} clean={n_clean} "
      f"harmless={n_harmless}")
"""


def test_sharded_detection_matrix():
    out = run_sub(MATRIX)
    assert "MATRIX_OK" in out
    # the reduced matrix detects every fault class once (no harmless
    # escapes: its combos are pinned to deterministically-detecting
    # wirings), plus 3 clean legs
    assert "detected=5 clean=3 harmless=0" in out


# ---------------------------------------------------------------------------
# Sharded recovery: retry -> degraded fallback -> quarantine (subprocess)
# ---------------------------------------------------------------------------

RECOVERY = """
import numpy as np
from repro.core import (PreparedGraph, TraversalPlan, build_csr, compile_plan,
                        degree_reorder, edge_view, generate_edges)
from repro.core.faults import FaultSpec
from repro.core.reorder import relabel_edges

edges = generate_edges(11, 10)
g0 = build_csr(edges)
r = degree_reorder(g0.degree)
g = build_csr(relabel_edges(edges, r))
ev = edge_view(g)
pg = PreparedGraph(ev=ev, degree=g.degree, core=None)
roots = np.arange(4, dtype=np.int32)
plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2),
                     batch_roots=True)

oracle = compile_plan(TraversalPlan(layout=(), batch_roots=True), pg)
base = oracle.run(roots, check="post")
assert base.run.all_valid

# exchange corruption: persists across retries, but the degraded
# single-device fallback has no exchange -> full recovery
f = FaultSpec(site="exchange", kind="zero", level=1, persistent=True)
c = compile_plan(plan, pg, fault=f)
res = c.run(roots, check="full", retries=2, fallback=True)
run = res.run
assert run.retries == 8, run.retries          # 4 roots x 2 attempts
assert run.fallbacks == 4, run.fallbacks
assert run.quarantined == [] and run.all_valid
assert np.array_equal(res.parent, base.parent)
assert run.harmonic_mean_teps > 0.0
# detection-time attribution is preserved even after recovery
assert run.check_counts["component"] == 4
print("RECOVERED")

# parent corruption survives the fallback too -> quarantine with counts
f2 = FaultSpec(site="parent", kind="self", level=1, persistent=True)
c2 = compile_plan(plan, pg, fault=f2)
run2 = c2.run(roots, check="post", retries=1, fallback=True).run
assert run2.retries == 4 and run2.fallbacks == 4
assert run2.quarantined == [0, 1, 2, 3]
assert run2.validated == [False] * 4
assert run2.harmonic_mean_teps == 0.0
print("QUARANTINED")
"""


def test_sharded_retry_fallback_quarantine():
    out = run_sub(RECOVERY)
    assert "RECOVERED" in out and "QUARANTINED" in out
