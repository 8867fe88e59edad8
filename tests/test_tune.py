"""Plan auto-tuner tests (DESIGN.md §11).

Covers: search-space enumeration, invalid-combo skipping (too-few-devices
on the single-device pytest process, planner non-pow2 member in a forced
6-device subprocess), winner determinism under a fixed seed with an
injected deterministic cost model, TUNED_PLANS.json round-trip +
schema-version rejection, the ``tuned_plan`` fallback when no entry
matches, and the ``Graph500Config.tuned`` / dry-run-cell consumers.
"""
import dataclasses
import json
import os
import sys
import textwrap

import pytest

from repro.core.plan import TraversalPlan
from repro.core.tune import (
    BUDGETS,
    SCHEMA_VERSION,
    TuneReport,
    TuneResult,
    enumerate_plans,
    load_table,
    save_tuned,
    sweep,
    tuned_exchange,
    tuned_plan,
)

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from repro.util import respawn_with_host_devices  # noqa: E402


# ---------------------------------------------------------------------------
# Search-space enumeration
# ---------------------------------------------------------------------------

def test_enumerate_small_budget_is_canonical_and_unique():
    plans = enumerate_plans(8, BUDGETS["small"])
    assert len(plans) == len(set(plans))          # frozen dataclass dedup
    layouts = {(p.layout, p.mesh_shape) for p in plans}
    assert ((), None) in layouts                  # single-device baseline
    assert (("root",), (8,)) in layouts
    assert (("group", "member"), (2, 4)) in layouts    # planner's split
    # the hand-picked BENCH rung is in the sweep, so the ranked table
    # always positions the winner against it
    assert (("root", "group", "member"), (2, 2, 2)) in layouts
    # the smoke budget sweeps the default exchange only, on vertex
    # layouts as everywhere else
    vertexy = [p for p in plans if "member" in p.layout]
    assert {p.exchange for p in vertexy} == {"hier_or"}
    assert all(p.exchange == "hier_or" for p in plans
               if "member" not in p.layout)
    # the partition axis sweeps BOTH owner maps on vertex-sharded
    # layouts and stays pinned to block everywhere else (word_cyclic on
    # a member-less layout is a validation error, never enumerated)
    for layout, shape in layouts:
        parts = {p.partition for p in plans
                 if (p.layout, p.mesh_shape) == (layout, shape)}
        if "member" in layout:
            assert parts == {"block", "word_cyclic"}, (layout, parts)
        else:
            assert parts == {"block"}, (layout, parts)


def test_enumerate_full_budget_crosses_axes():
    plans = enumerate_plans(8, BUDGETS["full"])
    vertex = [p for p in plans if "member" in p.layout]
    assert {p.exchange for p in vertex} == {"hier_or", "hier_gather", "flat"}
    assert {(p.alpha, p.beta) for p in plans} == set(BUDGETS["full"].alpha_beta)
    assert {p.n_chunks for p in plans} == set(BUDGETS["full"].n_chunks)
    # root-only layouts never multiply by the (dead) exchange axis
    rooty = [p for p in plans if p.layout == ("root",)]
    assert all(p.exchange == "hier_or" for p in rooty)


def test_enumerate_single_device_is_just_the_baseline():
    plans = enumerate_plans(1, BUDGETS["small"])
    assert [(p.layout, p.mesh_shape) for p in plans] == [((), None)]


# ---------------------------------------------------------------------------
# Invalid-combo skipping
# ---------------------------------------------------------------------------

def test_sweep_skips_too_few_devices_not_crashes():
    """A vertex plan needing more devices than visible (16x16 — beyond
    any CI leg) is recorded as skipped with compile_plan's ValueError
    text, never raised."""
    plans = [
        TraversalPlan(layout=(), batch_roots=True),
        TraversalPlan(layout=("group", "member"), mesh_shape=(16, 16)),
    ]
    report = sweep(8, budget="small", seed=3, n_roots=2, reps=1,
                   plans=plans, log=lambda s: None)
    assert [r.plan.layout for r in report.results] == [()]
    assert len(report.skipped) == 1
    skip = report.skipped[0]
    assert skip.status == "skipped" and "needs 256 devices" in skip.reason
    assert report.winner is not None and report.winner.identical


def test_sweep_skips_planner_nonpow2_member_on_6_devices():
    """6 visible devices: the enumerated set contains member=3 shapes
    (the planner's (2, 3) split); the sweep must record them as skipped
    via validation's pow2 ValueError and still rank the valid rest."""
    out = respawn_with_host_devices([sys.executable, "-c", textwrap.dedent("""
        from repro.core.tune import BUDGETS, enumerate_plans, sweep
        plans = enumerate_plans(6, BUDGETS["small"])
        assert any("member" in p.layout for p in plans)
        report = sweep(8, seed=3, n_roots=2, reps=1, plans=plans,
                       log=lambda s: None)
        pow2_skips = [r for r in report.skipped
                      if "power of two" in r.reason]
        assert pow2_skips, [r.reason for r in report.skipped]
        assert all(r.status == "skipped" for r in pow2_skips)
        assert report.winner is not None
        print("OK")
    """)], 6, pythonpath=(REPO_SRC,), capture=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# Winner determinism under a fixed seed
# ---------------------------------------------------------------------------

def _cost_model(compiled, roots, reps):
    """Deterministic stand-in for the wall clock: cost from the plan's
    declarative fields only."""
    p = compiled.plan
    return 1.0 + 0.1 * p.n_chunks / 64.0 + (0.5 if p.alpha < 10 else 0.0)


def test_winner_deterministic_under_fixed_seed():
    plans = [
        TraversalPlan(layout=(), batch_roots=True, n_chunks=32),
        TraversalPlan(layout=(), batch_roots=True, n_chunks=64),
        TraversalPlan(layout=(), batch_roots=True, alpha=8.0, beta=64.0),
        # same cost as the n_chunks=64 default plan -> exercises the
        # deterministic JSON tie-break
        TraversalPlan(layout=(), batch_roots=True, beta=32.0),
    ]
    reports = [sweep(8, seed=7, n_roots=2, reps=1, plans=list(plans),
                     measure=_cost_model, log=lambda s: None)
               for _ in range(2)]
    order0 = [r.plan for r in reports[0].results]
    order1 = [r.plan for r in reports[1].results]
    assert order0 == order1 and len(order0) == 4
    assert reports[0].winner.plan == reports[1].winner.plan
    assert reports[0].winner.plan.n_chunks == 32      # cheapest in the model
    # every accepted candidate passed the bitwise-parity acceptance
    assert all(r.identical for r in reports[0].results)


# ---------------------------------------------------------------------------
# TUNED_PLANS.json round-trip + schema versioning + fallback
# ---------------------------------------------------------------------------

def _report(scale=12, n_devices=8, backend="cpu", plan=None):
    plan = plan or TraversalPlan(layout=("root",), mesh_shape=(4,))
    return TuneReport(
        scale=scale, n_devices=n_devices, backend=backend,
        interpret_mode=True, budget="small", seed=1, n_roots=4, reps=2,
        results=[TuneResult(plan, "ok", wall_s=1.0, per_root_us=2.5e5,
                            harmonic_mean_teps=1e5, identical=True)])


def test_table_round_trip_and_lookup(tmp_path):
    path = str(tmp_path / "TUNED_PLANS.json")
    saved = save_tuned(_report(), path)
    assert saved == path
    doc = load_table(path)
    assert doc["schema_version"] == SCHEMA_VERSION
    got = tuned_plan(12, 8, "cpu", path=path)
    assert got == TraversalPlan(layout=("root",), mesh_shape=(4,))
    # second sweep at another key merges, not clobbers
    save_tuned(_report(scale=14,
                       plan=TraversalPlan(layout=(), batch_roots=True)),
               path)
    assert tuned_plan(12, 8, "cpu", path=path) is not None
    assert tuned_plan(14, 8, "cpu", path=path).layout == ()


def test_schema_version_rejection(tmp_path):
    path = str(tmp_path / "TUNED_PLANS.json")
    save_tuned(_report(), path)
    doc = json.load(open(path))
    doc["schema_version"] = SCHEMA_VERSION + 1
    json.dump(doc, open(path, "w"))
    with pytest.raises(ValueError, match="schema_version"):
        load_table(path)
    with pytest.raises(ValueError, match="schema_version"):
        tuned_plan(12, 8, "cpu", path=path)
    # a foreign-schema table is never clobbered by a new sweep
    with pytest.raises(ValueError, match="schema_version"):
        save_tuned(_report(scale=14), path)
    assert json.load(open(path))["schema_version"] == SCHEMA_VERSION + 1
    # from_dict itself rejects foreign plan fields
    with pytest.raises(ValueError, match="unknown TraversalPlan fields"):
        TraversalPlan.from_dict({"engine": "bitmap", "warp_speed": 9})


def test_v1_schema_rejected_with_actionable_message(tmp_path):
    """A pre-partition (v1) table must be rejected — its winners were
    ranked without the word_cyclic candidates — and the error must say
    what to do about it."""
    path = str(tmp_path / "TUNED_PLANS.json")
    save_tuned(_report(), path)
    doc = json.load(open(path))
    doc["schema_version"] = 1
    for entry in doc["entries"].values():
        entry["plan"].pop("partition", None)
    json.dump(doc, open(path, "w"))
    with pytest.raises(ValueError) as ei:
        load_table(path)
    msg = str(ei.value)
    assert "partition" in msg and "repro.core.tune" in msg
    with pytest.raises(ValueError, match="partition"):
        save_tuned(_report(scale=14), path)       # never clobbered either


def test_tuned_plan_fallback_when_no_entry_matches(tmp_path):
    path = str(tmp_path / "TUNED_PLANS.json")
    assert tuned_plan(12, 8, "cpu", path=path) is None      # no file at all
    save_tuned(_report(), path)
    assert tuned_plan(13, 8, "cpu", path=path) is None      # scale miss
    assert tuned_plan(12, 4, "cpu", path=path) is None      # device miss
    assert tuned_plan(12, 8, "tpu", path=path) is None      # backend miss
    # overrides: explicit fields win over the table
    got = tuned_plan(12, 8, "cpu", path=path,
                     overrides={"exchange": "flat", "alpha": 9.0})
    assert got.exchange == "flat" and got.alpha == 9.0
    assert got.mesh_shape == (4,)


def test_tuned_exchange_nearest_scale_and_default(tmp_path):
    path = str(tmp_path / "TUNED_PLANS.json")
    assert tuned_exchange(22, 256, path=path) == ("hier_or", "default")
    save_tuned(_report(plan=TraversalPlan(layout=("group", "member"),
                                          mesh_shape=(2, 4),
                                          exchange="hier_gather")), path)
    ex, src = tuned_exchange(22, 256, path=path)
    assert ex == "hier_gather" and src == "tuned:nearest_scale12"
    # exact (scale, n_devices) hit — with and without a backend pin
    ex, src = tuned_exchange(12, 8, "cpu", path=path)
    assert ex == "hier_gather" and src == "tuned:scale12/dev8/cpu"
    ex, src = tuned_exchange(12, 8, path=path)
    assert ex == "hier_gather" and src == "tuned:scale12/dev8/cpu"


# ---------------------------------------------------------------------------
# Consumers: Graph500Config.tuned + the dry-run cell variant
# ---------------------------------------------------------------------------

def test_pipeline_tuned_rung_consumes_table(tmp_path, monkeypatch):
    import jax

    from repro.core import Graph500Config

    path = str(tmp_path / "TUNED_PLANS.json")
    table_plan = TraversalPlan(layout=(), batch_roots=True, alpha=9.0,
                               beta=48.0, n_chunks=32)
    save_tuned(_report(scale=10, n_devices=len(jax.devices()),
                       backend=jax.default_backend(), plan=table_plan), path)
    monkeypatch.setenv("REPRO_TUNED_PLANS", path)

    cfg = Graph500Config.ladder("pre-g500-tuned", scale=10)
    assert cfg.tuned
    assert cfg.to_plan() == table_plan                  # table wins
    # explicit non-default knobs override the table entry
    cfg2 = Graph500Config.ladder("pre-g500-tuned", scale=10, alpha=11.0)
    assert cfg2.to_plan() == dataclasses.replace(table_plan, alpha=11.0)
    # no matching entry -> untuned derivation (single-device batch)
    cfg3 = Graph500Config.ladder("pre-g500-tuned", scale=9)
    assert cfg3.to_plan() == TraversalPlan(layout=(), batch_roots=True)
    # explicit layout or mesh_shape bypasses the table entirely
    cfg4 = Graph500Config.ladder("pre-g500-tuned", scale=10, layout=())
    assert cfg4.to_plan().alpha == 14.0
    cfg5 = Graph500Config.ladder("pre-g500-tuned", scale=10,
                                 mesh_shape=(1,))
    assert cfg5.to_plan().alpha == 14.0


def test_pipeline_tuned_rung_runs_end_to_end(monkeypatch, tmp_path):
    """pre-g500-tuned degrades gracefully with no table and validates."""
    from repro.core import Graph500Config, run

    monkeypatch.setenv("REPRO_TUNED_PLANS",
                       str(tmp_path / "missing.json"))
    cfg = Graph500Config.ladder("pre-g500-tuned", scale=9, n_roots=2)
    _, result = run(cfg)
    assert result.batched and result.all_valid
    assert result.harmonic_mean_teps > 0


def test_graph500_cell_tuned_variant(tmp_path, monkeypatch):
    """variant="tuned" resolves the exchange through the table and
    records the source in the cell note (shape-only, no devices)."""
    from repro.launch.input_specs import build_cell
    from repro.util import make_mesh

    path = str(tmp_path / "TUNED_PLANS.json")
    save_tuned(_report(plan=TraversalPlan(layout=("group", "member"),
                                          mesh_shape=(2, 4),
                                          exchange="hier_gather")), path)
    monkeypatch.setenv("REPRO_TUNED_PLANS", path)
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = build_cell("graph500", "bfs_s22", mesh, variant="tuned")
    assert "exchange=hier_gather" in plan.note
    assert "exchange_source=tuned:nearest_scale12" in plan.note


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_small_sweep_emits_table_and_persists(tmp_path, capsys):
    from repro.core.tune import main

    out_path = str(tmp_path / "TUNED_PLANS.json")
    # scale 8 keeps this cheap even on the 8-device CI leg, where the
    # small budget enumerates the full 7-candidate set
    rc = main(["--scale", "8", "--budget", "small", "--seed", "3",
               "--roots", "2", "--reps", "1", "--out", out_path])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "rank,layout,mesh" in printed          # ranked table header
    assert "\n1," in printed                      # a rank-1 winner row
    import jax
    got = tuned_plan(8, len(jax.devices()), jax.default_backend(),
                     path=out_path)
    assert got is not None and got.batch_roots
