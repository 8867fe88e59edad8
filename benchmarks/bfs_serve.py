"""BFS-as-a-service latency bench (DESIGN.md §14): replay a
deterministic query trace through the persistent serving engine and
report tail latency, sustained throughput, cache hit rate and
batch-occupancy histograms as a BENCH_bfs.json module next to the
hmean-TEPS ladders.

Two rungs exercise the two ends of the coalescing deadline/size
trade-off on the same engine (one graph build, one compile):

  * ``serve_steady`` — arrivals slow relative to service (Poisson at
    ``BENCH_SERVE_RATE`` qps virtual): batches launch on the deadline,
    mostly underfull; repeats of hot roots find the cache, so p50 is
    cache-hit-shaped and p99 is one batch service + wait.  This is the
    latency-regression rung the CI gate tracks.
  * ``serve_burst`` — the whole trace arrives in one burst (rate x1000):
    the coalescer packs full batches, nothing waits on the deadline, and
    the run measures sustained queries/sec and occupancy under load.

The replay clock is virtual (trace arrivals) crossed with REAL measured
per-batch service seconds, so the latency numbers move with engine
performance — which is exactly what makes p99 gateable.  Like
``bfs_sharded``, measurements run in a child process with 8 forced host
devices; the serving plan resolves through TUNED_PLANS.json for
(scale, devices, backend) and falls back to the single-device batched
plan (``rungs[*].plan`` records what actually ran).

Env knobs: ``BENCH_SERVE_SCALE`` (default 12 — the CI smoke scale),
``BENCH_SERVE_QUERIES`` (default 64), ``BENCH_SERVE_RATE`` (steady-rung
virtual qps, default 2.0), ``BENCH_SERVE_SEED`` (default 7),
``BENCH_RUNGS`` (rung filter set by ``benchmarks/run.py --rungs``).
"""
from __future__ import annotations

import json
import os
import sys

from benchmarks.common import eight_device_payload, row, rung_filter

_MARK = "BFS_SERVE_JSON:"
_PAYLOAD: dict = {}

RUNGS = ("serve_steady", "serve_burst")


def json_payload() -> dict:
    return _PAYLOAD


def _child() -> dict:
    import numpy as np
    import jax

    from repro.core.pipeline import Graph500Config, build
    from repro.data.query_trace import synth_trace
    from repro.kernels import ops as kops
    from repro.serve.engine import Engine, ServeConfig, resolve_serve_plan

    scale = int(os.environ.get("BENCH_SERVE_SCALE", "12"))
    n_queries = int(os.environ.get("BENCH_SERVE_QUERIES", "64"))
    rate = float(os.environ.get("BENCH_SERVE_RATE", "2.0"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "7"))
    want = rung_filter()
    matched = [r for r in RUNGS if want is None or r in want]
    out: dict = {
        "scale": scale,
        "n_devices_visible": len(jax.devices()),
        "interpret_mode": kops.interpret_mode(),
        "rungs": {},
        "rungs_matched": matched,
    }
    if not matched:
        return out

    built = build(Graph500Config(scale=scale, batched=True))
    plan = resolve_serve_plan(scale)
    cfg = ServeConfig(batch_size=8, max_wait_s=0.05, cache_capacity=128,
                      check="post", max_requeues=2)
    engine = Engine(built, plan=plan, config=cfg)
    degree = np.asarray(built.degree)

    # steady: slow arrivals, hot head -> cache hits + deadline launches;
    # burst: same queries all at once -> full batches, throughput
    cases = {
        "serve_steady": dict(rate_qps=rate, zipf_s=1.4),
        "serve_burst": dict(rate_qps=rate * 1000.0, zipf_s=1.1),
    }
    for name in matched:
        kw = cases[name]
        trace = synth_trace(seed, n_queries, built.n_vertices,
                            degree=degree, **kw)
        engine.reset_cache()    # rungs measure independent hit rates
        report = engine.serve(trace)
        s = report.summary()
        rung = {
            "plan": engine.plan.to_dict(),
            "n_queries": n_queries,
            "rate_qps_virtual": kw["rate_qps"],
            "zipf_s": kw["zipf_s"],
            "batch_size": cfg.batch_size,
            "max_wait_s": cfg.max_wait_s,
            "latency_p50_s": s["latency_p50_s"],
            "latency_p99_s": s["latency_p99_s"],
            "latency_p999_s": s["latency_p999_s"],
            "qps": s["qps"],
            "cache": s["cache"],
            "kinds": s["kinds"],
            "n_batches": s["n_batches"],
            "occupancy_mean": s["occupancy_mean"],
            "occupancy_hist": s["occupancy_hist"],
            "padding_fraction": s["padding_fraction"],
            "check_counts": s["check_counts"],
        }
        out["rungs"][name] = rung
        print(f"# {name}: p50={s['latency_p50_s']*1e3:.1f}ms "
              f"p99={s['latency_p99_s']*1e3:.1f}ms qps={s['qps']:.1f} "
              f"hit_rate={s['cache']['hit_rate']:.2f} "
              f"occ={s['occupancy_mean']:.2f}", file=sys.stderr)
    return out


def _fold_by_scale(payload: dict, repo: str) -> dict:
    """Nest under the scale and fold the tracked trajectory back in
    (same shape as bfs_sharded: other scales always survive; under a
    rung filter this scale's previously tracked rungs survive too;
    ``rungs_from_this_run`` marks what the gate compares)."""
    payload["rungs_from_this_run"] = sorted(payload["rungs"])
    scale_key = str(payload["scale"])
    try:
        with open(os.path.join(repo, "BENCH_bfs.json")) as f:
            prev = json.load(f)["modules"]["bfs_serve"]
    except (OSError, ValueError, KeyError):
        prev = {}
    by_scale = dict(prev.get("by_scale", {}))
    if rung_filter() is not None and scale_key in by_scale:
        merged = dict(by_scale[scale_key].get("rungs", {}))
        merged.update(payload["rungs"])
        payload["rungs"] = merged
    by_scale[scale_key] = payload
    return {"by_scale": by_scale, "latest_scale": payload["scale"]}


_SELECTED: set = set()


def selected_rungs() -> set:
    """Rung names this run consulted (run.py's unknown-rung check)."""
    return set(_SELECTED)


def run():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    payload = eight_device_payload("bfs_serve", _child, _MARK)
    _SELECTED.clear()
    _SELECTED.update(payload.get("rungs_matched", []))
    fresh = {name: dict(rung) for name, rung in payload["rungs"].items()}
    _PAYLOAD.update(_fold_by_scale(payload, repo))

    rows = []
    for name, rung in fresh.items():
        rows.append(row(
            f"bfs_serve/scale{payload['scale']}/{name}",
            rung["latency_p99_s"] * 1e6,
            f"p50_ms={rung['latency_p50_s']*1e3:.2f};"
            f"p999_ms={rung['latency_p999_s']*1e3:.2f};"
            f"qps={rung['qps']:.2f};"
            f"hit_rate={rung['cache']['hit_rate']:.3f};"
            f"occ={rung['occupancy_mean']:.3f};"
            f"n_batches={rung['n_batches']}"))
    return rows


if __name__ == "__main__":
    if "--child" in sys.argv:
        print(_MARK + json.dumps(_child()))
    else:
        from benchmarks.common import print_rows
        print_rows(run())
