"""Paper Fig. 10/11: single-node BFS performance + the resident-loop ladder.

Rungs measured (CPU wall clock; absolute GTEPS are NOT comparable to
Matrix-2000+ — the *relative ladder* is the reproduction target):

  reference-3.0.0 : sequential numpy queue BFS ("just make then run")
  xla             : edge-parallel relax engine under jit (thread-parallel)
  avla            : dense-core Pallas kernel, default tile (compiler-chosen
                    vector shape — interpret-mode Pallas on CPU)
  avls            : dense-core Pallas kernel, hand-tuned rows_per_tile (the
                    vector-length-specified mode)
  bitmap_engine   : the bitmap-resident loop — packed frontier/visited
                    across the whole while_loop, fused frontier_update
                    epilogue, chunked frontier-proportional top-down
  bitmap_nocore   : the resident loop without the dense core (isolates the
                    chunked top-down win from Pallas interpret overhead)
  batch64         : all 64 Graph500 search keys in ONE jitted program

Scales default to (10,) fast / (10, 12) full; set ``BENCH_SCALES=14`` (comma
list) to override — the CI smoke run uses that for the scale-14 check.

The module also fills a machine-readable payload (``json_payload()``) that
``benchmarks/run.py`` writes to ``BENCH_bfs.json`` at the repo root: engine
wall-clock + TEPS and the per-level breakdown (direction, frontier, scanned
edges, scanned chunks).
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax.numpy as jnp

from benchmarks.common import FAST, row, timed
from repro.core import (
    PreparedGraph, TraversalPlan, build_csr, build_heavy_core,
    chunk_edge_view, compile_plan, degree_reorder, edge_view, generate_edges,
    sample_roots, traversed_edges,
)
from repro.core.heavy import pack_bitmap
from repro.core.reference import reference_bfs
from repro.core.reorder import relabel_edges
from repro.kernels.frontier_spmv import core_spmv

_PAYLOAD: dict = {}

_BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_bfs.json")


def json_payload() -> dict:
    """Payload for BENCH_bfs.json: the scales this run measured, plus the
    previously tracked scales folded back in (run.py's module-granularity
    merge would otherwise drop them).  ``scales_from_this_run`` marks the
    fresh ones — the regression gate compares only those."""
    import json

    fresh = sorted(k for k in _PAYLOAD if k.startswith("scale"))
    if not fresh:
        return _PAYLOAD
    try:
        with open(_BENCH_JSON) as f:
            prev = json.load(f)["modules"]["bfs_single"]
    except (OSError, ValueError, KeyError):
        prev = {}
    for k, v in prev.items():
        if k.startswith("scale") and k not in _PAYLOAD:
            _PAYLOAD[k] = v
    _PAYLOAD["scales_from_this_run"] = fresh
    return _PAYLOAD


def _scales() -> tuple[int, ...]:
    env = os.environ.get("BENCH_SCALES")
    if env:
        return tuple(int(s) for s in env.split(",") if s.strip())
    return (10,) if FAST else (10, 12)


def run():
    rows = []
    for scale in _scales():
        edges = generate_edges(1, scale)
        g0 = build_csr(edges)
        r = degree_reorder(g0.degree)
        g = build_csr(relabel_edges(edges, r))
        ev = edge_view(g)
        chunks = chunk_edge_view(ev)
        threshold = 100 if scale >= 13 else 8
        core = build_heavy_core(g, threshold=threshold)
        ro, ci = np.asarray(g.row_offsets), np.asarray(g.col_indices)
        root = 0
        pg = PreparedGraph(ev=ev, degree=g.degree, core=core, chunks=chunks)
        pg_nocore = PreparedGraph(ev=ev, degree=g.degree, chunks=chunks)
        plan_xla = compile_plan(
            TraversalPlan(engine="reference", batch_roots=False), pg)
        plan_bm = compile_plan(
            TraversalPlan(engine="bitmap", batch_roots=False), pg)
        plan_nocore = compile_plan(
            TraversalPlan(engine="bitmap", batch_roots=False), pg_nocore)
        res = plan_xla.bfs(root)
        m = int(traversed_edges(g.degree, res))
        engines: dict[str, dict] = {}

        def record(name, t_s, extra=""):
            engines[name] = {"us_per_call": t_s * 1e6, "teps": m / t_s}
            rows.append(row(f"bfs_single/scale{scale}/{name}", t_s * 1e6,
                            f"GTEPS={m / t_s / 1e9:.5f}{extra}"))

        t0 = time.perf_counter()
        reference_bfs(ro, ci, root)
        record("reference-3.0.0", time.perf_counter() - t0)

        record("xla", timed(lambda: plan_xla.bfs(root).parent))

        for mode, rpt in (("avla", 8), ("avls", 32)):
            # kernel-tile mode enters through rows_per_tile; run the dense
            # core level directly to isolate the SVE-analogue effect.
            f_bm = pack_bitmap(jnp.zeros((core.k,), bool).at[0].set(True),
                               core.k // 32)
            t_k = timed(lambda: core_spmv(core.a_core, f_bm,
                                          rows_per_tile=rpt, interpret=True))
            bits = core.k * core.k
            rows.append(row(
                f"bfs_single/scale{scale}/{mode}(rows={rpt})", t_k * 1e6,
                f"core_bits_per_s={bits / t_k:.3g}"))

        note = ";note=interpret-mode Pallas (CPU) — see DESIGN.md §8"
        record("bitmap_engine", timed(lambda: plan_bm.bfs(root).parent),
               note)
        record("bitmap_nocore", timed(lambda: plan_nocore.bfs(root).parent))

        # --- Graph500-spec batched harness: 64 keys, one jitted program ---
        # Timed once by CompiledBFS.run (the fused program is too
        # expensive on interpret-mode CPU for repeat timing), and skipped
        # above BENCH_BATCH_SCALE_MAX: under vmap, chunk skipping becomes
        # masking, so the batch scans all edges for all roots every level
        # (fine on a real TPU backend; see ROADMAP open items).
        batch_scale_max = int(os.environ.get("BENCH_BATCH_SCALE_MAX", "14"))
        batch_payload: dict = {"skipped": True,
                               "reason": f"scale>{batch_scale_max} on "
                                         "interpret-mode backend"}
        if scale <= batch_scale_max:
            roots = np.asarray(sample_roots(1, edges, 64))
            roots = np.asarray(r.new_from_old)[roots]
            g500 = compile_plan(
                TraversalPlan(layout=(), batch_roots=True), pg).run(
                    roots, warmup=True, do_validate=False).run
            t_b = float(np.sum(g500.times_s))
            rows.append(row(
                f"bfs_single/scale{scale}/batch64", t_b * 1e6 / len(roots),
                f"hmean_GTEPS={g500.harmonic_mean_teps / 1e9:.5f};"
                f"batch_us={t_b * 1e6:.0f};n_roots={len(roots)}"))
            batch_payload = {
                "n_roots": int(len(roots)),
                "batch_us": t_b * 1e6,
                "harmonic_mean_teps": g500.harmonic_mean_teps,
                "plan": TraversalPlan(layout=(), batch_roots=True).to_dict(),
            }
        else:
            rows.append(row(
                f"bfs_single/scale{scale}/batch64", 0.0,
                f"SKIPPED:batched-harness-beyond-scale-{batch_scale_max}"
                "-on-interpret-backend"))

        # --- per-level breakdown for BENCH_bfs.json ----------------------
        res_bm = plan_bm.bfs(root)
        lv = int(res_bm.stats.levels)
        from repro.kernels import ops as kops
        _PAYLOAD[f"scale{scale}"] = {
            "scale": scale,
            # stamped per payload: run.py merges stale modules wholesale,
            # so the doc-level interpret_mode only describes the last run
            "interpret_mode": kops.interpret_mode(),
            "engine": "bitmap",
            "plan": TraversalPlan(engine="bitmap", layout=(),
                                  batch_roots=False).to_dict(),
            "heavy_threshold": threshold,
            "traversed_edges": m,
            "engines": engines,
            "batch64": batch_payload,
            "per_level": {
                "direction": np.asarray(res_bm.stats.direction)[:lv].tolist(),
                "frontier_size":
                    np.asarray(res_bm.stats.frontier_size)[:lv].tolist(),
                "scanned_edges":
                    np.asarray(res_bm.stats.scanned_edges)[:lv].tolist(),
                "scanned_chunks":
                    np.asarray(res_bm.stats.scanned_chunks)[:lv].tolist(),
                "total_chunks": int(res_bm.stats.total_chunks),
            },
            "speedup_bitmap_nocore_vs_reference_engine": (
                engines["xla"]["us_per_call"]
                / engines["bitmap_nocore"]["us_per_call"]),
        }
    return rows
