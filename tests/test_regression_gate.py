"""CI perf-gate plan matching (benchmarks/check_regression.py).

The gate matches rungs on name + plan dict + interpret mode.  Plan
dicts are compared after default-filling missing fields with the
current TraversalPlan defaults, so growing the plan schema (the v2
``partition`` axis) does not zero-match every committed baseline —
while a field present on both sides with different values still
mismatches (a partition flip IS a plan change).
"""
import copy
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.check_regression import (  # noqa: E402
    collect_rungs,
    compare,
    normalize_plan,
)
from repro.core.plan import TraversalPlan  # noqa: E402


def _doc(plan_dict, teps=1000.0):
    return {
        "interpret_mode": True,
        "modules_from_this_run": ["bfs_sharded"],
        "modules": {
            "bfs_sharded": {
                "latest_scale": 12,
                "by_scale": {
                    "12": {
                        "interpret_mode": True,
                        "rungs_from_this_run": ["4x2"],
                        "vertex_sharded": {
                            "4x2": {
                                "plan": plan_dict,
                                "harmonic_mean_teps": teps,
                            },
                        },
                    },
                },
            },
        },
    }


def test_normalize_plan_fills_current_defaults():
    filled = normalize_plan({"layout": ["group", "member"]})
    assert filled["partition"] == "block"
    assert filled["engine"] == "bitmap"
    # every TraversalPlan field is present after the fill
    assert set(filled) >= set(TraversalPlan().to_dict())
    # an explicit value survives the fill
    assert normalize_plan({"partition": "word_cyclic"})["partition"] == \
        "word_cyclic"


def test_pre_partition_baseline_still_matches():
    """A baseline recorded before the partition field existed gates
    against a current rung carrying partition='block'."""
    old_plan = TraversalPlan(layout=("group", "member"),
                             mesh_shape=(4, 2)).to_dict()
    old_plan.pop("partition")
    new_plan = TraversalPlan(layout=("group", "member"),
                             mesh_shape=(4, 2)).to_dict()
    base = collect_rungs(_doc(old_plan, teps=1000.0))
    cur = collect_rungs(_doc(new_plan, teps=990.0), only_fresh=True)
    regressions, matched, unmatched = compare(base, cur, 0.25)
    assert len(matched) == 1 and not unmatched and not regressions
    # and the threshold still bites on a matched pair
    cur_slow = collect_rungs(_doc(new_plan, teps=100.0), only_fresh=True)
    regressions, matched, _ = compare(base, cur_slow, 0.25)
    assert len(regressions) == 1


def test_partition_flip_is_a_plan_change_not_a_match():
    """Fields present on BOTH sides with different values must not be
    papered over by the default fill."""
    block = TraversalPlan(layout=("group", "member"),
                          mesh_shape=(4, 2)).to_dict()
    cyc = TraversalPlan(layout=("group", "member"), mesh_shape=(4, 2),
                        partition="word_cyclic").to_dict()
    base = collect_rungs(_doc(block))
    cur = collect_rungs(_doc(cyc), only_fresh=True)
    regressions, matched, unmatched = compare(base, cur, 0.25)
    assert not matched and not regressions
    assert unmatched == [
        ("bfs_sharded/scale12/vertex_sharded/4x2", "plan dict changed")]


def test_unknown_exchange_rejected_with_valid_values():
    """Satellite: an unknown exchange name fails fast at validate_plan
    with the full SHARD_EXCHANGES list in the message — it must never
    reach the SPMD program or the gate."""
    from repro.core.hybrid_bfs import SHARD_EXCHANGES
    from repro.core.plan import validate_plan

    assert SHARD_EXCHANGES == ("hier_or", "hier_gather", "flat")
    plan = TraversalPlan(layout=("group", "member"), mesh_shape=(4, 2),
                         exchange="hier_or_zstd")
    with pytest.raises(ValueError) as e:
        validate_plan(plan)
    msg = str(e.value)
    assert "hier_or_zstd" in msg
    for name in SHARD_EXCHANGES:
        assert name in msg


def test_pre_codec_baseline_default_fills_and_new_rung_not_gated():
    """Satellite: a committed baseline predating the §12 exchanges still
    default-fills and gates its hier_or rung, while a NEW-exchange rung
    absent from the baseline reports as unmatched (not gated), never as
    a regression."""
    old_plan = TraversalPlan(layout=("group", "member"),
                             mesh_shape=(4, 2)).to_dict()
    old_plan.pop("partition")          # pre-v2 baseline shape
    base = collect_rungs(_doc(old_plan, teps=1000.0))

    # current run carries the old rung plus a fresh 4x2_sieve rung
    doc = _doc(TraversalPlan(layout=("group", "member"), mesh_shape=(4, 2))
               .to_dict(), teps=990.0)
    scale = doc["modules"]["bfs_sharded"]["by_scale"]["12"]
    sieve_plan = TraversalPlan(layout=("group", "member"), mesh_shape=(4, 2),
                               exchange="hier_or_sieve").to_dict()
    scale["vertex_sharded"]["4x2_sieve"] = {
        "plan": sieve_plan, "harmonic_mean_teps": 10.0}
    scale["rungs_from_this_run"] = ["4x2", "4x2_sieve"]
    cur = collect_rungs(doc, only_fresh=True)
    regressions, matched, unmatched = compare(base, cur, 0.25)
    assert len(matched) == 1 and not regressions
    assert unmatched == [
        ("bfs_sharded/scale12/vertex_sharded/4x2_sieve",
         "missing from baseline")]


def test_old_baseline_vs_old_current_unaffected():
    """Two pre-partition docs (the committed trajectory before this PR)
    still compare exactly as before the default fill existed."""
    plan = TraversalPlan(layout=("root",), mesh_shape=(4,)).to_dict()
    plan.pop("partition")
    base = collect_rungs(_doc(plan, teps=500.0))
    cur = collect_rungs(_doc(copy.deepcopy(plan), teps=500.0),
                        only_fresh=True)
    _, matched, unmatched = compare(base, cur, 0.25)
    assert len(matched) == 1 and not unmatched


def _serve_doc(plan_dict, p99=0.5, rungs=("serve_steady",)):
    return {
        "interpret_mode": True,
        "modules_from_this_run": ["bfs_serve"],
        "modules": {
            "bfs_serve": {
                "latest_scale": 12,
                "by_scale": {
                    "12": {
                        "interpret_mode": True,
                        "rungs_from_this_run": list(rungs),
                        "rungs": {
                            name: {"plan": copy.deepcopy(plan_dict),
                                   "latency_p99_s": p99}
                            for name in rungs
                        },
                    },
                },
            },
        },
    }


def test_latency_rung_gates_lower_is_better():
    """Satellite: serve rungs gate on p99 latency with the direction
    INVERTED — a p99 increase past the latency threshold fails, a
    decrease never does (it would be a 'regression' under the TEPS
    rule)."""
    plan = TraversalPlan(layout=(), batch_roots=True).to_dict()
    base = collect_rungs(_serve_doc(plan, p99=1.0))
    assert base == {"bfs_serve/scale12/serve_steady/p99": {
        "plan": plan, "interpret_mode": True,
        "metric": "p99_latency_s", "value": 1.0}}
    # 20% slower p99: within the 50% latency threshold
    cur = collect_rungs(_serve_doc(plan, p99=1.2), only_fresh=True)
    regressions, matched, unmatched = compare(base, cur, 0.25, 0.5)
    assert len(matched) == 1 and not regressions and not unmatched
    # 80% slower p99: fails
    cur = collect_rungs(_serve_doc(plan, p99=1.8), only_fresh=True)
    regressions, _, _ = compare(base, cur, 0.25, 0.5)
    assert len(regressions) == 1
    name, ratio, b, c, metric = regressions[0]
    assert metric == "p99_latency_s" and (b, c) == (1.0, 1.8)
    # 4x FASTER p99 must pass (lower is better — the TEPS rule would
    # have called this a 0.25x regression)
    cur = collect_rungs(_serve_doc(plan, p99=0.25), only_fresh=True)
    regressions, matched, _ = compare(base, cur, 0.25, 0.5)
    assert len(matched) == 1 and not regressions


def test_first_run_serve_rung_unmatched_not_gated():
    """Satellite: a serve rung absent from the committed baseline (the
    first run after this subsystem lands) reports as unmatched — it
    must neither fail nor count toward the vacuity check."""
    plan = TraversalPlan(layout=(), batch_roots=True).to_dict()
    base = collect_rungs(_doc(plan, teps=1000.0))     # sharded-only baseline
    cur = collect_rungs(_serve_doc(plan), only_fresh=True)
    regressions, matched, unmatched = compare(base, cur, 0.25, 0.5)
    assert not regressions and not matched
    assert unmatched == [("bfs_serve/scale12/serve_steady/p99",
                          "missing from baseline")]


def test_serve_rung_default_fills_plan_like_teps_rungs():
    """The default-fill plan matching applies to latency rungs too: a
    baseline recorded before a plan field existed still gates."""
    old_plan = TraversalPlan(layout=(), batch_roots=True).to_dict()
    old_plan.pop("partition")
    new_plan = TraversalPlan(layout=(), batch_roots=True).to_dict()
    base = collect_rungs(_serve_doc(old_plan, p99=1.0))
    cur = collect_rungs(_serve_doc(new_plan, p99=1.1), only_fresh=True)
    regressions, matched, unmatched = compare(base, cur, 0.25, 0.5)
    assert len(matched) == 1 and not unmatched and not regressions


def _sssp_doc(plan_dict, teps=1000.0):
    return {
        "interpret_mode": True,
        "modules_from_this_run": ["sssp"],
        "modules": {
            "sssp": {
                "latest_scale": 12,
                "by_scale": {
                    "12": {
                        "interpret_mode": True,
                        "rungs_from_this_run": ["2x2_min"],
                        "rungs": {
                            "2x2_min": {
                                "plan": plan_dict,
                                "harmonic_mean_teps": teps,
                            },
                        },
                    },
                },
            },
        },
    }


def test_pre_kernel_baseline_default_fills_and_gates():
    """Satellite (§16): a committed baseline recorded before the
    ``kernel`` plan field existed still matches a current BFS rung that
    carries ``kernel="bfs"`` — adding the kernel axis must not
    zero-match every committed BFS baseline."""
    old_plan = TraversalPlan(layout=("group", "member"),
                             mesh_shape=(4, 2)).to_dict()
    assert old_plan["kernel"] == "bfs"
    old_plan.pop("kernel")             # pre-§16 baseline shape
    new_plan = TraversalPlan(layout=("group", "member"),
                             mesh_shape=(4, 2)).to_dict()
    base = collect_rungs(_doc(old_plan, teps=1000.0))
    cur = collect_rungs(_doc(new_plan, teps=990.0), only_fresh=True)
    regressions, matched, unmatched = compare(base, cur, 0.25)
    assert len(matched) == 1 and not unmatched and not regressions


def test_sssp_rungs_collect_and_gate_separately():
    """Satellite (§16): sssp-module rungs flatten under their own
    ``sssp/`` names and gate against sssp baselines only — on first run
    they report unmatched (not gated), and a kernel flip on an
    identically-named rung is a plan change, never a match."""
    sssp_plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2),
                              exchange="hier_min", kernel="sssp").to_dict()
    cur = collect_rungs(_sssp_doc(sssp_plan, teps=500.0), only_fresh=True)
    assert set(cur) == {"sssp/scale12/2x2_min"}

    # first run: no sssp baseline -> unmatched, vacuity-neutral
    bfs_base = collect_rungs(_doc(TraversalPlan(
        layout=("group", "member"), mesh_shape=(4, 2)).to_dict()))
    regressions, matched, unmatched = compare(bfs_base, cur, 0.25)
    assert not regressions and not matched
    assert unmatched == [("sssp/scale12/2x2_min", "missing from baseline")]

    # committed sssp baseline -> gates normally
    base = collect_rungs(_sssp_doc(sssp_plan, teps=500.0))
    regressions, matched, _ = compare(base, cur, 0.25)
    assert len(matched) == 1 and not regressions
    slow = collect_rungs(_sssp_doc(sssp_plan, teps=100.0), only_fresh=True)
    regressions, _, _ = compare(base, slow, 0.25)
    assert len(regressions) == 1

    # a kernel flip under the same rung name must not match
    bfs_named = dict(sssp_plan, kernel="bfs", exchange="hier_or")
    flipped = collect_rungs(_sssp_doc(bfs_named, teps=500.0), only_fresh=True)
    regressions, matched, unmatched = compare(base, flipped, 0.25)
    assert not matched and not regressions
    assert unmatched == [("sssp/scale12/2x2_min", "plan dict changed")]
