"""SSSP (δ-stepping) BENCH rungs — the second Graph500 kernel (§16).

Sibling of ``bfs_sharded``: every rung is a
:class:`repro.core.plan.TraversalPlan` with ``kernel="sssp"`` run through
``compile_plan`` on the weighted degree-sorted Kronecker graph, tracked
in BENCH_bfs.json under the ``sssp`` module with the same
hmean-GTEPS-style metric (``harmonic_mean_teps`` over the traversed
component's edges — SSSP relaxes every component edge at least once, so
the denominator is the same edge count the BFS rungs use and the
numbers are directly comparable across kernels).

Rungs (all asserted bitwise-equal to the host δ-stepping oracle before
timing — a wrong tree must never post a number):

  * ``single``    — single-device batched δ-stepping;
  * ``2x2_min``   — vertex-sharded over the 2x2 mesh, ``hier_min``
    two-phase hierarchical min exchange (two-phase OR on the changed-set
    delta leg);
  * ``2x2_flat``  — same mesh, flat one-phase min all-reduce (the
    wiring baseline ``hier_min`` must beat on real wire).

Multi-device rungs need 8 forced host devices, so the measurements run
in a child process (``--child``) exactly like ``bfs_sharded``.

Env knobs: ``BENCH_SSSP_SCALE`` (default 12 — the CI smoke scale),
``BENCH_SSSP_ROOTS`` (default 8), ``BENCH_RUNGS`` (comma list filter via
``benchmarks/run.py --rungs``).
"""
from __future__ import annotations

import json
import os
import sys
import time

from benchmarks.common import eight_device_payload, row, rung_filter

_MARK = "SSSP_JSON:"
_PAYLOAD: dict = {}
_SELECTED: set = set()

VERTEX_RUNGS = (("2x2_min", "hier_min"), ("2x2_flat", "flat"))


def json_payload() -> dict:
    return _PAYLOAD


def selected_rungs() -> set:
    return set(_SELECTED)


def _child() -> dict:
    import numpy as np
    import jax

    from repro.core import (
        PreparedGraph, TraversalPlan, build_csr, chunk_edge_view,
        compile_plan, degree_reorder, edge_view, generate_edges,
        sample_roots, sssp_oracle, with_edge_weights,
    )
    from repro.core.reorder import relabel_edges
    from repro.kernels import ops as kops

    scale = int(os.environ.get("BENCH_SSSP_SCALE", "12"))
    n_roots = int(os.environ.get("BENCH_SSSP_ROOTS", "8"))
    reps = int(os.environ.get("BENCH_SSSP_REPS", "2"))
    seed = 1
    want = rung_filter()
    matched: set = set()

    def wanted(name: str) -> bool:
        ok = want is None or name in want
        if ok:
            matched.add(name)
        return ok

    edges = generate_edges(seed, scale)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    ev = with_edge_weights(edge_view(g), seed=seed,
                           old_from_new=r.old_from_new)
    chunks = chunk_edge_view(ev)
    roots = np.asarray(sample_roots(seed, edges, n_roots))
    roots = np.asarray(r.new_from_old)[roots].astype(np.int32)
    pg = PreparedGraph(ev=ev, degree=g.degree, core=None, chunks=chunks)
    V = g.num_vertices

    # host δ-stepping oracle: the bitwise contract for every rung
    oracle_parent = np.empty((n_roots, V), np.int32)
    oracle_dist = np.empty((n_roots, V), np.int32)
    for i, root in enumerate(roots):
        par, dist = sssp_oracle(ev.src, ev.dst, ev.valid, ev.weight,
                                V, int(root))
        oracle_parent[i] = np.asarray(par)
        oracle_dist[i] = np.asarray(dist)

    out: dict = {
        "scale": scale,
        "n_roots": n_roots,
        "n_devices_visible": len(jax.devices()),
        "interpret_mode": kops.interpret_mode(),
        "kernel": "sssp",
        "rungs": {},
    }

    def run_rung(name, plan, mesh_name, layer):
        compiled = compile_plan(plan, pg)
        result = compiled.run(roots, check="post")
        run = result.run
        if not run.all_valid:
            detail = "; ".join(
                f"root {rt} failed {'+'.join(names)}"
                for rt, names in sorted(run.check_failures.items()))
            raise RuntimeError(
                f"sssp rung {name}: spec validation failed — "
                f"{detail or 'unknown check'}")
        par = np.asarray(result.parent)[:, :V]
        dist = np.asarray(result.level)[:, :V]
        if not (np.array_equal(par, oracle_parent)
                and np.array_equal(dist, oracle_dist)):
            raise AssertionError(
                f"sssp rung {name}: parent/dist diverge from the host "
                f"δ-stepping oracle — parity regression")
        wall = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            res = compiled.bfs(roots)
            jax.block_until_ready(res.parent)
            wall = min(wall, time.perf_counter() - t0)
        out["rungs"][name] = {
            "mesh": mesh_name,
            "layer": layer,
            "plan": plan.to_dict(),
            "wall_us": wall * 1e6,
            "per_root_us": wall / n_roots * 1e6,
            "harmonic_mean_teps": run.harmonic_mean_teps,
            "n_roots": n_roots,
            "validated": run.all_valid,
            "check_counts": run.check_counts,
            "oracle_identical": True,
        }
        print(f"# sssp {name}: wall={wall:.2f}s "
              f"hmean={run.harmonic_mean_teps:.3g}", file=sys.stderr)

    if wanted("single"):
        run_rung("single",
                 TraversalPlan(layout=(), batch_roots=True, kernel="sssp"),
                 "1", "single")
    for name, exchange in VERTEX_RUNGS:
        if not wanted(name):
            continue
        run_rung(name,
                 TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2),
                               exchange=exchange, batch_roots=True,
                               kernel="sssp"),
                 "2x2", "vertex_sharded")
    out["rungs_matched"] = sorted(matched)
    return out


def _fold_by_scale(payload: dict, repo: str) -> dict:
    """Nest under the scale and fold the previously tracked trajectory
    back in (same merge policy as ``bfs_sharded``)."""
    payload["rungs_from_this_run"] = sorted(payload["rungs"])
    scale_key = str(payload["scale"])
    try:
        with open(os.path.join(repo, "BENCH_bfs.json")) as f:
            prev = json.load(f)["modules"]["sssp"]
    except (OSError, ValueError, KeyError):
        prev = {}
    by_scale = dict(prev.get("by_scale", {}))
    if rung_filter() is not None and scale_key in by_scale:
        merged = dict(by_scale[scale_key].get("rungs", {}))
        merged.update(payload["rungs"])
        payload["rungs"] = merged
    by_scale[scale_key] = payload
    return {"by_scale": by_scale, "latest_scale": payload["scale"]}


def run():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    payload = eight_device_payload("sssp", _child, _MARK)
    _SELECTED.clear()
    _SELECTED.update(payload.get("rungs_matched", []))
    _PAYLOAD.update(_fold_by_scale(payload, repo))

    return [
        row(f"sssp/scale{payload['scale']}/{name}",
            rung["per_root_us"],
            f"layer={rung['layer']};"
            f"hmean_GTEPS={rung['harmonic_mean_teps'] / 1e9:.5f};"
            f"oracle_identical={rung['oracle_identical']};"
            f"n_roots={rung['n_roots']}")
        for name, rung in payload["rungs"].items()
    ]


if __name__ == "__main__":
    if "--child" in sys.argv:
        print(_MARK + json.dumps(_child()))
