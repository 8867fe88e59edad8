"""Paper Fig. 18: the version ladder — reference-3.0.0 / TH-2 / K / Pre-G500.

Two views are reported per rung:
  * measured CPU GTEPS (XLA + interpret-mode Pallas — absolute numbers are
    container-bound, see DESIGN.md §8);
  * the *work model*: algorithmic edges scanned per search, which is
    hardware-independent and shows the direction-optimization + heavy-core
    effect the paper's 3.15x rests on.

``BENCH_RUNGS`` (set by ``benchmarks/run.py --rungs``) filters the rung
list so CI smoke can run one rung; the speedup summary rows appear only
when both of their rungs ran.  ``json_payload()`` records each rung's
:class:`repro.core.plan.TraversalPlan` next to its TEPS.
"""
from __future__ import annotations

import os

import numpy as np

from benchmarks.common import FAST, row, rung_filter
from repro.core import Graph500Config, compile_plan
from repro.core import run as run_g500

RUNGS = ("reference-3.0.0", "th2", "k", "pre-g500", "pre-g500-batch")

_PAYLOAD: dict = {}

_BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_bfs.json")


def json_payload() -> dict:
    """Payload for BENCH_bfs.json: the rungs this run measured, plus the
    previously tracked rungs folded back in (run.py's module-granularity
    merge would otherwise drop every rung a --rungs filter skipped).
    ``rungs_from_this_run`` marks the fresh ones — the regression gate
    compares only those."""
    import json

    fresh = sorted(k for k in _PAYLOAD if k in RUNGS)
    if not fresh:
        return _PAYLOAD
    try:
        with open(_BENCH_JSON) as f:
            prev = json.load(f)["modules"]["version_ladder"]
    except (OSError, ValueError, KeyError):
        prev = {}
    for k, v in prev.items():
        if k in RUNGS and k not in _PAYLOAD and isinstance(v, dict):
            _PAYLOAD[k] = v
    _PAYLOAD["rungs_from_this_run"] = fresh
    return _PAYLOAD


def _wanted():
    want = rung_filter()
    if want is None:
        return list(RUNGS)
    return [r for r in RUNGS if r in want]


_SELECTED: set = set()


def selected_rungs() -> set:
    """Rung names this run executed (run.py's unknown-rung check)."""
    return set(_SELECTED)


def run():
    rows = []
    scale = 10 if FAST else 12
    teps = {}
    rungs = _wanted()
    _SELECTED.clear()
    _SELECTED.update(rungs)
    for rung in rungs:
        cfg = Graph500Config.ladder(rung, scale=scale, n_roots=2)
        built, result = run_g500(cfg)
        teps[rung] = result.harmonic_mean_teps
        plan = cfg.to_plan()
        # work model: scanned edges from per-level stats (one untimed
        # per-root traversal; per-root plans expose the stats arrays)
        stats_cfg = Graph500Config.ladder(rung, scale=scale, n_roots=2,
                                          batched=False, root_devices=None,
                                          layout=())
        res = compile_plan(stats_cfg.to_plan(), built).bfs(0)
        scanned = int(np.asarray(res.stats.scanned_edges).sum())
        m = int(np.asarray(result.edges)[0])
        rows.append(row(
            f"ladder/{rung}", result.mean_time_s * 1e6,
            f"GTEPS={teps[rung] / 1e9:.5f};scanned_edges={scanned};"
            f"work_ratio={scanned / max(2 * m, 1):.2f};valid={result.all_valid}"))
        from repro.kernels import ops as kops
        _PAYLOAD[rung] = {
            "plan": plan.to_dict(),
            "scale": scale,
            # per-rung stamp: the doc-level interpret_mode describes only
            # the run that last rewrote BENCH_bfs.json
            "interpret_mode": kops.interpret_mode(),
            "harmonic_mean_teps": teps[rung],
            "mean_time_us": result.mean_time_s * 1e6,
            "scanned_edges": scanned,
            "valid": result.all_valid,
        }
    if "pre-g500" in teps and "k" in teps:
        speedup = teps["pre-g500"] / max(teps["k"], 1e-9)
        rows.append(row(
            "ladder/speedup_pre-g500_vs_k", 0.0,
            f"speedup={speedup:.2f}x;paper_reports=3.15x_at_512cn;"
            "note=single-CPU-container — see EXPERIMENTS.md ladder discussion"))
    return rows
