"""Benchmark harness: one module per paper figure/table.

Usage::

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run degree_census monitor_policies
    BENCH_FAST=0 PYTHONPATH=src python -m benchmarks.run   # full scales
    PYTHONPATH=src python -m benchmarks.run bfs_sharded --rungs 1,2x2x2

Prints ``name,us_per_call,derived`` CSV (one row per measurement).

``--rungs`` (comma list, exported to modules as ``BENCH_RUNGS``) filters
the ladder/mesh rungs inside rung-aware modules (``version_ladder``,
``bfs_sharded``) so CI smoke can run a single rung without executing the
full set.

Modules may additionally expose ``json_payload() -> dict``; the collected
payloads are written to ``BENCH_bfs.json`` at the repo root (plus run
metadata) so the perf trajectory is tracked in-tree from PR to PR.  Rung
entries record the :class:`repro.core.plan.TraversalPlan` that produced them
(as a dict) so every number names the engine configuration it measured.

The merge here is module-granularity (a partial run must not clobber the
other modules' trajectories); anything finer is module-owned: a module
whose payload nests partial runs (per scale, per rung) folds the
previously tracked entries back in itself and marks what THIS run
measured (``bfs_sharded``: ``by_scale`` + per-scale
``rungs_from_this_run``; ``bfs_single``: ``scales_from_this_run``) —
``benchmarks/check_regression.py`` gates only those fresh markers.
Rung-aware modules also expose ``selected_rungs()`` so an unknown
``--rungs`` name is an error, not an empty run.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
import traceback

MODULES = [
    "degree_census",      # Fig. 7
    "bfs_single",         # Fig. 10/11
    "bfs_sharded",        # mesh-sharded ladder (DESIGN.md §9)
    "bfs_serve",          # serving latency/throughput (DESIGN.md §14)
    "sssp",               # second kernel: δ-stepping rungs (DESIGN.md §16)
    "sorting_policies",   # Fig. 12/13
    "heavy_threshold",    # Fig. 14
    "monitor_policies",   # Fig. 15/16
    "version_ladder",     # Fig. 18
    "kernels_micro",      # kernel-level validation throughputs
]


BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                          "BENCH_bfs.json")


def _write_json(payloads: dict) -> None:
    if not payloads:
        return
    # Merge per-module into the existing file: a partial run (one CI leg,
    # a single-module local run) must not clobber the other modules'
    # tracked trajectory.
    modules = {}
    try:
        with open(BENCH_JSON) as f:
            modules = json.load(f).get("modules", {})
    except (OSError, ValueError):
        pass
    modules.update(payloads)
    doc = {
        "generated_unix": int(time.time()),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "bench_fast": os.environ.get("BENCH_FAST", "1") != "0",
        "bench_scales": os.environ.get("BENCH_SCALES", ""),
        "bench_rungs": os.environ.get("BENCH_RUNGS", ""),
        # The top-level metadata describes THIS run; merged-in modules
        # not listed here keep numbers from whatever run produced them.
        "modules_from_this_run": sorted(payloads),
        "modules": modules,
    }
    try:
        import jax
        doc["jax"] = jax.__version__
        doc["backend"] = jax.default_backend()
    except Exception:
        pass
    try:
        from repro.kernels import ops as kops
        doc["interpret_mode"] = kops.interpret_mode()
    except Exception:
        pass
    with open(BENCH_JSON, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {BENCH_JSON}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description="benchmark harness")
    ap.add_argument("modules", nargs="*",
                    help=f"modules to run (default: all of {MODULES})")
    ap.add_argument("--rungs", default=None,
                    help="comma list of rung names; rung-aware modules "
                         "run only these (exported as BENCH_RUNGS)")
    args = ap.parse_args()
    if args.rungs:
        os.environ["BENCH_RUNGS"] = args.rungs
    from repro.util import use_compile_cache
    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    want = args.modules or MODULES
    print("name,us_per_call,derived")
    failures = []
    payloads = {}
    selected_rungs: set = set()
    for name in want:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            rows = mod.run()
            for r in rows:
                print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
            if hasattr(mod, "json_payload"):
                payload = mod.json_payload()
                if payload:
                    payloads[name] = payload
            if hasattr(mod, "selected_rungs"):
                selected_rungs |= set(mod.selected_rungs())
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:
            failures.append(name)
            # one loud greppable line naming the module and the error
            # (validation failures arrive as RuntimeError naming the
            # rung, root and failed check), then the full traceback
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    _write_json(payloads)
    if args.rungs and not failures:
        # An unknown rung name must be an error, not an empty filter that
        # runs nothing and exits 0 — the CI perf gate would pass vacuously.
        requested = {r.strip() for r in args.rungs.split(",") if r.strip()}
        unknown = requested - selected_rungs
        if unknown:
            sys.exit(f"--rungs names matched no rung in the selected "
                     f"modules: {sorted(unknown)} (rungs that ran: "
                     f"{sorted(selected_rungs)})")
    if failures:
        sys.exit(f"benchmark modules failed: {failures}")


if __name__ == "__main__":
    main()
