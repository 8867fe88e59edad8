"""CI perf-regression gate over the BENCH_bfs.json trajectory.

Usage (the CI legs extract the committed baseline with ``git show``)::

    git show HEAD:BENCH_bfs.json > /tmp/bench_baseline.json
    PYTHONPATH=src python -m benchmarks.check_regression \\
        --baseline /tmp/bench_baseline.json --current BENCH_bfs.json

Compares the *smoke-run* rungs — the modules listed in the current
file's ``modules_from_this_run`` (and, for ``bfs_sharded``, only the
rungs in that scale's ``rungs_from_this_run``) — against the committed
baseline.  A rung pair only gates when its identity matches exactly:

  * rung name (module / scale / layer / rung),
  * the :class:`repro.core.plan.TraversalPlan` dict that produced the number,
  * interpret mode (a Mosaic-vs-interpret flip is a backend change, not
    a regression).

Matched pairs fail the job when their metric regresses past the
threshold.  The metric direction is rung-typed: throughput rungs
(``hmean_teps``, higher is better) fail on a >``--threshold`` drop
(default 0.25, i.e. >25% slowdown); latency rungs from the serving
bench (``p99_latency_s``, lower is better) fail on a
>``--latency-threshold`` increase (default 0.50 — tail latency on a
shared runner is noisier than throughput, so the gate is looser).  Zero
matched rungs is itself a failure: a renamed rung, a changed plan, or
an unknown ``--rungs`` filter must not let the gate pass vacuously.
First-run serve rungs simply report as unmatched (not gated) until a
baseline with them is committed.

Plan dicts are compared after **default-filling**: a baseline recorded
before a :class:`repro.core.plan.TraversalPlan` field existed (e.g. the v2
``partition`` axis) still matches a current rung that carries the
field at its default value — adding a plan axis must not zero-match
every committed baseline.  A field present on BOTH sides with
different values still mismatches.

Caveat: the comparison is *absolute* interpret-mode TEPS, so the
committed baseline should come from hardware comparable to the CI
runners — a systematically slower runner fails on machine speed alone.
If that happens, loosen via the ``REGRESSION_THRESHOLD`` env var (or
``--threshold``) and re-commit a baseline produced by a CI-artifact
BENCH_bfs.json so the trajectory is runner-calibrated.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_THRESHOLD = 0.25
DEFAULT_LATENCY_THRESHOLD = 0.50

# metric name -> (direction, unit label); direction "higher" regresses on
# a drop, "lower" on a rise
METRICS = {
    "hmean_teps": ("higher", "TEPS"),
    "p99_latency_s": ("lower", "s p99"),
}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _plan_defaults() -> dict:
    """The current TraversalPlan's field defaults (single source of truth
    for the default-fill — never a copy hardcoded here)."""
    from repro.core.plan import TraversalPlan

    return TraversalPlan().to_dict()


def normalize_plan(plan: dict, defaults: dict | None = None) -> dict:
    """Fill fields the rung's plan dict predates with their defaults, so
    old baselines keep matching when the plan schema grows a field."""
    return {**(_plan_defaults() if defaults is None else defaults), **plan}


def collect_rungs(doc: dict, only_fresh: bool = False) -> dict:
    """Flatten a BENCH_bfs.json doc into ``name -> (plan, interpret,
    metric, value)`` for every plan-carrying rung.

    Covered: ``bfs_sharded`` ladder rungs (root_parallel /
    vertex_sharded / composed / tuned, per scale), ``version_ladder``
    rungs, ``bfs_single`` batch64 harnesses (all ``hmean_teps``), and
    ``bfs_serve`` latency rungs (``p99_latency_s``).  Engine rows
    without a plan dict of their own never gate.  ``only_fresh``
    restricts to rungs the doc's own run produced
    (``modules_from_this_run`` + per-scale ``rungs_from_this_run``).
    """
    out: dict = {}
    modules = doc.get("modules", {})
    fresh_modules = set(doc.get("modules_from_this_run", modules))
    doc_interp = doc.get("interpret_mode")

    def add(name, rung, value_key="harmonic_mean_teps", interp=None,
            metric="hmean_teps"):
        plan = rung.get("plan")
        value = rung.get(value_key)
        if plan is None or value is None:
            return
        out[name] = {
            "plan": plan,
            "interpret_mode": doc_interp if interp is None else interp,
            "metric": metric,
            "value": float(value),
        }

    sharded = modules.get("bfs_sharded", {})
    if not only_fresh or "bfs_sharded" in fresh_modules:
        latest = str(sharded.get("latest_scale"))
        for scale, payload in sharded.get("by_scale", {}).items():
            # Only the latest run's scale and only its measured rungs
            # gate — a stale scale's ladder is a copy of the baseline
            # and would always compare 1.0, defeating the zero-match
            # vacuity check.
            if only_fresh and str(scale) != latest:
                continue
            fresh = set(payload.get("rungs_from_this_run") or [])
            interp = payload.get("interpret_mode")
            # First-run mp_* rungs are unmatched in the committed
            # baseline and therefore reported-not-gated (the same
            # policy PR 8 used for serve rungs) — they start gating
            # once a baseline BENCH_bfs.json records them.
            for layer in ("root_parallel", "vertex_sharded", "composed",
                          "tuned", "multiprocess"):
                rungs = payload.get(layer, {})
                if not isinstance(rungs, dict):
                    continue
                for name, rung in rungs.items():
                    if not isinstance(rung, dict):
                        continue
                    if only_fresh and name not in fresh:
                        continue
                    add(f"bfs_sharded/scale{scale}/{layer}/{name}", rung,
                        interp=interp)

    if not only_fresh or "version_ladder" in fresh_modules:
        ladder = modules.get("version_ladder", {})
        fresh_rungs = ladder.get("rungs_from_this_run")
        for name, rung in ladder.items():
            if not isinstance(rung, dict):
                continue
            if (only_fresh and fresh_rungs is not None
                    and name not in fresh_rungs):
                continue
            add(f"version_ladder/{name}", rung,
                interp=rung.get("interpret_mode"))

    if not only_fresh or "bfs_single" in fresh_modules:
        single = modules.get("bfs_single", {})
        fresh_scales = single.get("scales_from_this_run")
        for scale_key, payload in single.items():
            if not isinstance(payload, dict):
                continue
            if (only_fresh and fresh_scales is not None
                    and scale_key not in fresh_scales):
                continue
            batch = payload.get("batch64")
            if isinstance(batch, dict) and not batch.get("skipped"):
                add(f"bfs_single/{scale_key}/batch64", batch,
                    interp=payload.get("interpret_mode"))

    # Kernel-typed rungs (§16) gate separately under their own names —
    # an SSSP plan dict carries kernel="sssp", so a BFS baseline can
    # never silently match an SSSP rung (or vice versa) even if a rung
    # name collided.
    ssspm = modules.get("sssp", {})
    if not only_fresh or "sssp" in fresh_modules:
        latest = str(ssspm.get("latest_scale"))
        for scale, payload in ssspm.get("by_scale", {}).items():
            if only_fresh and str(scale) != latest:
                continue
            fresh = set(payload.get("rungs_from_this_run") or [])
            interp = payload.get("interpret_mode")
            for name, rung in payload.get("rungs", {}).items():
                if not isinstance(rung, dict):
                    continue
                if only_fresh and name not in fresh:
                    continue
                add(f"sssp/scale{scale}/{name}", rung, interp=interp)

    serve = modules.get("bfs_serve", {})
    if not only_fresh or "bfs_serve" in fresh_modules:
        latest = str(serve.get("latest_scale"))
        for scale, payload in serve.get("by_scale", {}).items():
            if only_fresh and str(scale) != latest:
                continue
            fresh = set(payload.get("rungs_from_this_run") or [])
            interp = payload.get("interpret_mode")
            for name, rung in payload.get("rungs", {}).items():
                if not isinstance(rung, dict):
                    continue
                if only_fresh and name not in fresh:
                    continue
                add(f"bfs_serve/scale{scale}/{name}/p99", rung,
                    value_key="latency_p99_s", interp=interp,
                    metric="p99_latency_s")
    return out


def compare(baseline: dict, current: dict, threshold: float,
            latency_threshold: float = DEFAULT_LATENCY_THRESHOLD) -> tuple:
    """Return (regressions, matched, unmatched) over the flattened rung
    maps.  A pair matches when name + default-filled plan dict +
    interpret mode + metric agree; a ``hmean_teps`` rung regresses when
    ``current < (1 - threshold) * baseline``, a ``p99_latency_s`` rung
    when ``current > (1 + latency_threshold) * baseline``."""
    defaults = _plan_defaults()
    regressions, matched, unmatched = [], [], []
    for name, cur in sorted(current.items()):
        base = baseline.get(name)
        base_metric = base.get("metric", "hmean_teps") if base else None
        cur_metric = cur.get("metric", "hmean_teps")
        plans_differ = base is not None and (
            normalize_plan(base["plan"], defaults)
            != normalize_plan(cur["plan"], defaults))
        if (base is None or plans_differ
                or base["interpret_mode"] != cur["interpret_mode"]
                or base_metric != cur_metric):
            why = ("missing from baseline" if base is None else
                   "plan dict changed" if plans_differ else
                   "metric changed" if base_metric != cur_metric else
                   "interpret mode changed")
            unmatched.append((name, why))
            continue
        direction, _ = METRICS.get(cur_metric, ("higher", cur_metric))
        ratio = cur["value"] / base["value"] if base["value"] > 0 else \
            float("inf")
        matched.append((name, ratio))
        if direction == "higher":
            if ratio < 1.0 - threshold:
                regressions.append((name, ratio, base["value"],
                                    cur["value"], cur_metric))
        elif ratio > 1.0 + latency_threshold:
            regressions.append((name, ratio, base["value"], cur["value"],
                                cur_metric))
    return regressions, matched, unmatched


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fail on >threshold harmonic-mean-TEPS slowdown vs "
                    "the committed BENCH_bfs.json baseline")
    ap.add_argument("--baseline", required=True,
                    help="committed BENCH_bfs.json (e.g. from `git show`)")
    ap.add_argument("--current", default="BENCH_bfs.json")
    ap.add_argument("--threshold", type=float,
                    default=float(os.environ.get("REGRESSION_THRESHOLD",
                                                 DEFAULT_THRESHOLD)),
                    help="fractional slowdown that fails (default 0.25)")
    ap.add_argument("--latency-threshold", type=float,
                    default=float(os.environ.get(
                        "LATENCY_REGRESSION_THRESHOLD",
                        DEFAULT_LATENCY_THRESHOLD)),
                    help="fractional p99-latency increase that fails "
                         "(default 0.50)")
    ap.add_argument("--all-rungs", action="store_true",
                    help="gate every rung in the current file, not just "
                         "the ones this run refreshed")
    args = ap.parse_args(argv)

    base = collect_rungs(_load(args.baseline))
    cur = collect_rungs(_load(args.current), only_fresh=not args.all_rungs)
    regressions, matched, unmatched = compare(base, cur, args.threshold,
                                              args.latency_threshold)

    bad = {name for name, *_ in regressions}
    for name, why in unmatched:
        print(f"# unmatched (not gated): {name} — {why}")
    for name, ratio in matched:
        if name not in bad:
            print(f"ok {name}: {ratio:.3f}x baseline")
    if not matched:
        print("FAIL: no rung matched the baseline (name + plan dict + "
              "interpret mode) — the gate would be vacuous", file=sys.stderr)
        return 1
    if regressions:
        for name, ratio, b, c, metric in regressions:
            direction, unit = METRICS.get(metric, ("higher", metric))
            bound = (1 - args.threshold if direction == "higher"
                     else 1 + args.latency_threshold)
            print(f"REGRESSION {name}: {b:.3g} -> {c:.3g} {unit} "
                  f"({ratio:.3f}x, threshold {bound:.2f}x)",
                  file=sys.stderr)
        return 1
    print(f"# gate passed: {len(matched)} rungs within "
          f"{args.threshold:.0%} of baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
