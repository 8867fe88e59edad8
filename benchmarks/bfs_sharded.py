"""Mesh-sharded Graph500 ladder (DESIGN.md §9/§10): BENCH_bfs.json rungs
per mesh shape, every rung a :class:`repro.core.plan.TraversalPlan`.

Three harness layers over 8 forced host devices (the container is
XLA:CPU; relative rungs, not absolute GTEPS, are the tracked numbers):

  * root-parallel   — ``TraversalPlan(layout=("root",))`` over 1/2/4/8
    devices: the 64 search keys split with zero communication.  Rung "1"
    is the plain single-device batch plan (the PR-1 baseline).  Parents
    are asserted bitwise-identical to the baseline for every shape
    before timing.
  * vertex-sharded  — ``TraversalPlan(layout=("group", "member"))`` over
    meshes 2x1 / 2x2 / 4x2: one giant traversal spans the mesh, the
    per-level delta bitmaps combine through the T3 two-phase bitwise-OR
    collective (``exchange="hier_or"``).  Each mesh runs under BOTH
    vertex partitions — ``block`` (the plain ``2x2`` rung names) and
    ``word_cyclic`` (paper eq. (3); ``2x2_cyc``) — and every vertex
    rung records the per-shard edge-count skew (``edge_skew``:
    max / mean / max_over_mean of the dst-owner counts, the padding
    overhead the block layout pays after the degree sort).
  * composed        — ``TraversalPlan(layout=("root", "group", "member"))``
    over the 2x2x2 mesh: the root batch splits over its own mesh axis
    OUTSIDE the vertex-sharded SPMD program (layer 1 x layer 2).

Because the main benchmark process must keep seeing one device, the
measurements run in a child process carrying
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the child prints
a JSON payload the parent folds into ``BENCH_bfs.json``.  Each rung's
payload records its plan (``TraversalPlan.to_dict()``).

Env knobs: ``BENCH_SHARDED_SCALE`` (default 14 — the acceptance scale),
``BENCH_SHARDED_ROOTS`` (default 64), ``BENCH_SHARDED_VERTEX_ROOTS``
(default 16: the vertex-sharded SPMD batch multiplies every collective
by the root lane count, so the full 64 is a knob, not the default, on
the interpret-mode container), ``BENCH_RUNGS`` (comma list filtering
rung names, set by ``benchmarks/run.py --rungs``).

The module payload nests one ladder per scale (``by_scale``) so the
scale-12 CI smoke and the scale-14 acceptance ladder track side by side
in BENCH_bfs.json — ``benchmarks/check_regression.py`` gates each scale
against its own committed baseline.  The extra ``tuned`` rung runs the
persisted TUNED_PLANS.json winner for (scale, devices, backend) when the
table has one (DESIGN.md §11).
"""
from __future__ import annotations

import json
import os
import sys
import time

from benchmarks.common import eight_device_payload, row, rung_filter

_MARK = "BFS_SHARDED_JSON:"
_PAYLOAD: dict = {}

ROOT_SHAPES = (1, 2, 4, 8)
VERTEX_SHAPES = ((2, 1), (2, 2), (4, 2))
COMPOSED_SHAPES = ((2, 2, 2),)
# Multi-process rungs (DESIGN.md §15): REAL cross-process exchange via
# repro.launch.multiprocess — run only when named in BENCH_RUNGS or when
# BENCH_MP=1 (each one spawns a worker gang; too heavy for the default
# sweep).  ``mp_2x4`` = 2 processes x 4 devices each; same 8-device
# global mesh as the single-process "4x2"-family rungs but the
# inter-group leg crosses process wire, so ``exchange_seconds`` is
# measured transfer time, not memcpy.
MP_RUNGS = ("mp_2x4", "mp_4x2")


def json_payload() -> dict:
    return _PAYLOAD


def _child() -> dict:
    import numpy as np
    import jax

    from repro.core import (
        PreparedGraph, TraversalPlan, build_csr, build_heavy_core,
        chunk_edge_view, compile_plan, degree_reorder, edge_view,
        generate_edges, sample_roots,
    )
    from repro.core.reorder import relabel_edges
    from repro.kernels import ops as kops

    scale = int(os.environ.get("BENCH_SHARDED_SCALE", "14"))
    n_roots = int(os.environ.get("BENCH_SHARDED_ROOTS", "64"))
    n_vroots = int(os.environ.get("BENCH_SHARDED_VERTEX_ROOTS", "16"))
    reps = int(os.environ.get("BENCH_SHARDED_REPS", "2"))
    want = rung_filter()
    matched: set = set()

    def wanted(name: str) -> bool:
        ok = want is None or name in want
        if ok:
            matched.add(name)
        return ok

    edges = generate_edges(1, scale)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    ev = edge_view(g)
    chunks = chunk_edge_view(ev)
    threshold = 100 if scale >= 13 else 8
    core = build_heavy_core(g, threshold=threshold)
    roots = np.asarray(sample_roots(1, edges, n_roots))
    roots = np.asarray(r.new_from_old)[roots].astype(np.int32)
    pg = PreparedGraph(ev=ev, degree=g.degree, core=core, chunks=chunks)
    V = g.num_vertices

    out: dict = {
        "scale": scale,
        "n_roots": n_roots,
        "n_devices_visible": len(jax.devices()),
        "interpret_mode": kops.interpret_mode(),
        "exchange": "hier_or",
        "root_parallel": {},
        "vertex_sharded": {},
        "composed": {},
        "tuned": {},
        "mesh_ladder": {},
    }

    # ---- baseline + root-parallel ladder (layer 1) ---------------------
    # The single-device oracle batch is expensive (a full 64-root fused
    # traversal on the interpret-mode container), so it runs lazily: only
    # when a selected rung needs a parity check or the rel-vs-single
    # denominator.
    base_plan = TraversalPlan(layout=(), batch_roots=True)
    base = compile_plan(base_plan, pg)
    _base_parent: dict = {}

    def base_parent(n):
        if n not in _base_parent:
            _base_parent[n] = np.asarray(base.bfs(roots[:n]).parent)
        return _base_parent[n]

    base_per_root = None
    identical = True
    parity_checks = 0

    def timed_rung(fn, plan, layer, mesh_name, n, check_parent=None):
        """Compile+parity check, then min-over-reps wall clock."""
        nonlocal identical, parity_checks
        res = fn()
        jax.block_until_ready(res.parent)
        if check_parent is not None:
            p = np.asarray(res.parent)
            p = p[:, :V] if p.shape[1] > V else p
            same = bool(np.array_equal(p, check_parent))
            if not same:
                raise AssertionError(
                    f"{layer} mesh={mesh_name}: parents diverge from the "
                    f"single-device batch — parity regression")
            identical &= same
            parity_checks += 1
        # min over reps: the rung ratio is the tracked number and a single
        # 40 s wall sample is at the mercy of background load.
        wall = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            res = fn()
            jax.block_until_ready(res.parent)
            wall = min(wall, time.perf_counter() - t0)
        per_root = wall / n
        return res, {
            "mesh": mesh_name,
            "layer": layer,
            "plan": plan.to_dict(),
            "wall_us": wall * 1e6,
            "per_root_us": per_root * 1e6,
            "n_roots": n,
        }

    def teps_of(res, per_root_s):
        from repro.core.teps import batch_harmonic_mean_teps

        p = np.asarray(res.parent)
        p = p[:, :V] if p.shape[1] > V else p
        return batch_harmonic_mean_teps(g.degree, p, per_root_s)

    for n_dev in ROOT_SHAPES:
        name = str(n_dev)
        if not wanted(name):
            continue
        if n_dev == 1:
            plan, compiled = base_plan, base
        else:
            plan = TraversalPlan(layout=("root",), mesh_shape=(n_dev,))
            compiled = compile_plan(plan, pg)
        res, rung = timed_rung(lambda: compiled.bfs(roots), plan,
                               "root_parallel", name, n_roots,
                               check_parent=base_parent(n_roots))
        per_root = rung["per_root_us"] / 1e6
        if n_dev == 1:
            base_per_root = per_root
        rung["harmonic_mean_teps"] = teps_of(res, per_root)
        # absent (not NaN — invalid strict JSON) when rung "1" is filtered
        if base_per_root:
            rung["rel_per_root_vs_single"] = per_root / base_per_root
        out["root_parallel"][name] = rung
        print(f"# root_parallel mesh={n_dev}: wall={rung['wall_us']/1e6:.2f}s "
              f"rel={rung.get('rel_per_root_vs_single', float('nan')):.3f}",
              file=sys.stderr)
    # None (not True) when the rung filter skipped every parity check —
    # "no comparison ran" must not read as "verified identical".
    out["parents_bitwise_identical"] = identical if parity_checks else None

    # ---- vertex-sharded ladder (layer 2) -------------------------------
    # The acceptance shapes are pinned; the topology planner's answer for
    # all visible devices (member sized to the router group) rides along
    # as its own rung so the eq.-5-derived shape is measured, not assumed.
    from repro.comms.topology import plan_device_mesh
    from repro.core.distributed_bfs import shard_edge_skew
    planned = plan_device_mesh(len(jax.devices()))
    shapes = list(VERTEX_SHAPES)
    if planned not in shapes:
        shapes.append(planned)
    out["planned_shape"] = f"{planned[0]}x{planned[1]}"
    vroots = roots[:n_vroots]
    # both partitions cover the same shape set — including the planner's
    # eq.-5 shape, so the block-vs-cyclic skew comparison exists for it
    cases = [(s, p) for p in ("block", "word_cyclic") for s in shapes]
    for shape, partition in cases:
        name = (f"{shape[0]}x{shape[1]}"
                + ("_cyc" if partition == "word_cyclic" else ""))
        if not wanted(name):
            continue
        plan = TraversalPlan(layout=("group", "member"), mesh_shape=shape,
                             exchange="hier_or", partition=partition)
        compiled = compile_plan(plan, pg)    # shards the graph internally
        skew = shard_edge_skew(compiled.graph.sharded)
        result = compiled.run(vroots, check="post")
        run = result.run
        if not run.all_valid:
            # fail LOUDLY, naming the rung, root and check — a silently
            # wrong tree must never post a TEPS number (DESIGN.md §13)
            detail = "; ".join(
                f"root {r} failed {'+'.join(names)}"
                for r, names in sorted(run.check_failures.items()))
            raise RuntimeError(
                f"vertex-sharded rung {name} (mesh={shape} "
                f"partition={partition} exchange=hier_or): spec "
                f"validation failed — {detail or 'unknown check'}")
        out["vertex_sharded"][name] = {
            "mesh": f"{shape[0]}x{shape[1]}",
            "layer": "vertex_sharded",
            "plan": plan.to_dict(),
            "wall_us": float(np.sum(run.times_s)) * 1e6,
            "per_root_us": float(np.mean(run.times_s)) * 1e6,
            "harmonic_mean_teps": run.harmonic_mean_teps,
            "n_roots": len(vroots),
            "validated": run.all_valid,
            "check_counts": run.check_counts,
            "edge_skew": skew,
        }
        print(f"# vertex_sharded mesh={name}: "
              f"wall={float(np.sum(run.times_s)):.2f}s "
              f"skew={skew['max_over_mean']:.2f}", file=sys.stderr)

    # ---- composed 3-axis ladder (layer 1 x layer 2) --------------------
    for shape in COMPOSED_SHAPES:
        name = f"{shape[0]}x{shape[1]}x{shape[2]}"
        if not wanted(name):
            continue
        plan = TraversalPlan(layout=("root", "group", "member"),
                             mesh_shape=shape, exchange="hier_or")
        compiled = compile_plan(plan, pg)
        res, rung = timed_rung(
            lambda: compiled.bfs(vroots), plan, "composed", name,
            len(vroots), check_parent=base_parent(len(vroots)))
        rung["harmonic_mean_teps"] = teps_of(res, rung["per_root_us"] / 1e6)
        out["composed"][name] = rung
        print(f"# composed mesh={name}: wall={rung['wall_us']/1e6:.2f}s",
              file=sys.stderr)

    # ---- tuned rung: the persisted TUNED_PLANS.json winner -------------
    if wanted("tuned"):
        from repro.core.tune import tuned_plan
        tp = tuned_plan(scale)
        if tp is None:
            note = (
                f"no TUNED_PLANS.json entry for (scale={scale}, "
                f"devices={len(jax.devices())}, backend="
                f"{jax.default_backend()}) — run python -m repro.core.tune")
            if want is not None:
                # Explicitly requested via --rungs (the CI smoke): a
                # missing table entry must fail, not silently pass the
                # unknown-rung and regression-gate vacuity checks.
                raise RuntimeError(f"tuned rung requested but {note}")
            out["tuned_note"] = note
            print(f"# tuned rung skipped: {note}", file=sys.stderr)
        else:
            compiled = compile_plan(tp, pg)
            t_roots = vroots if "member" in tp.layout else roots
            res, rung = timed_rung(
                lambda: compiled.bfs(t_roots), tp, "tuned", "tuned",
                len(t_roots), check_parent=base_parent(len(t_roots)))
            rung["harmonic_mean_teps"] = teps_of(res,
                                                 rung["per_root_us"] / 1e6)
            if base_per_root:
                rung["rel_per_root_vs_single"] = (
                    rung["per_root_us"] / 1e6 / base_per_root)
            out["tuned"]["tuned"] = rung
            print(f"# tuned plan={tp.to_dict()}: "
                  f"wall={rung['wall_us']/1e6:.2f}s", file=sys.stderr)

    # ---- acceptance view: one rung per mesh shape ----------------------
    for src_key in ("root_parallel", "vertex_sharded", "composed", "tuned"):
        for name, rung in out[src_key].items():
            if src_key == "root_parallel" and name not in ("1", "2"):
                continue
            out["mesh_ladder"][name] = rung
    out["rungs_matched"] = sorted(matched)
    return out


def _fold_by_scale(payload: dict, repo: str) -> dict:
    """Nest the child payload under its scale and fold the previously
    tracked trajectory back in (run.py's module-granularity merge would
    otherwise drop it): other scales' ladders are always preserved, and
    under a BENCH_RUNGS filter the same scale's previously tracked rungs
    survive too.  Rungs measured by THIS run are listed per scale in
    ``rungs_from_this_run`` — the regression gate compares only those."""
    fresh = sorted(
        set(payload["root_parallel"]) | set(payload["vertex_sharded"])
        | set(payload["composed"]) | set(payload["tuned"])
        | set(payload.get("multiprocess", {})))
    payload["rungs_from_this_run"] = fresh
    scale_key = str(payload["scale"])
    try:
        with open(os.path.join(repo, "BENCH_bfs.json")) as f:
            prev = json.load(f)["modules"]["bfs_sharded"]
    except (OSError, ValueError, KeyError):
        prev = {}
    by_scale = dict(prev.get("by_scale", {}))
    if "by_scale" not in prev and prev.get("scale") is not None:
        # pre-PR-4 flat layout: keep it as its own scale's ladder
        by_scale[str(prev["scale"])] = prev
    if rung_filter() is not None and scale_key in by_scale:
        old = by_scale[scale_key]
        for key in ("root_parallel", "vertex_sharded", "composed", "tuned",
                    "multiprocess", "mesh_ladder"):
            merged = dict(old.get(key, {}))
            merged.update(payload.get(key, {}))
            payload[key] = merged
    by_scale[scale_key] = payload
    return {"by_scale": by_scale, "latest_scale": payload["scale"]}


_SELECTED: set = set()


def selected_rungs() -> set:
    """Rung names this run actually consulted (for run.py's unknown-rung
    check); filled by :func:`run`."""
    return set(_SELECTED)


def _parse_mp_rung(name: str):
    """``mp_<P>x<D>[<exchange suffix>][_cyc]`` → (procs, dpp, exchange,
    partition); raises on anything else (run.py's unknown-rung check)."""
    from repro.launch.multiprocess import EXCHANGE_SUFFIX

    body = name[len("mp_"):]
    partition = "block"
    if body.endswith("_cyc"):
        partition, body = "word_cyclic", body[:-len("_cyc")]
    exchange = "hier_or"
    for e, suf in EXCHANGE_SUFFIX.items():
        if suf and body.endswith(suf):
            exchange, body = e, body[:-len(suf)]
            break
    procs, dpp = (int(x) for x in body.split("x"))
    return procs, dpp, exchange, partition


def _run_mp_rungs(scale: int) -> dict:
    """The multiprocess section: one launcher gang per (procs x dpp)
    grouping of the selected ``mp_*`` rungs (exchange/partition variants
    of the same topology share one gang — one graph build, one
    rendezvous)."""
    want = rung_filter()
    if want is not None:
        names = sorted(n for n in want if n.startswith("mp_"))
    elif os.environ.get("BENCH_MP") == "1":
        names = list(MP_RUNGS)
    else:
        return {}
    if not names:
        return {}
    from repro.launch.multiprocess import launch, rung_name

    n_roots = int(os.environ.get("BENCH_MP_ROOTS", "8"))
    reps = int(os.environ.get("BENCH_MP_REPS", "3"))
    log_base = os.environ.get("BENCH_MP_LOG_DIR")  # CI uploads on failure
    by_topo: dict = {}
    for name in names:
        procs, dpp, exchange, partition = _parse_mp_rung(name)
        by_topo.setdefault((procs, dpp), []).append((exchange, partition))
    out: dict = {}
    for (procs, dpp), cases in sorted(by_topo.items()):
        exchanges = ",".join(sorted({e for e, _ in cases}))
        partitions = ",".join(sorted({p for _, p in cases}))
        payload = launch(procs, dpp, scale=scale, n_roots=n_roots,
                         exchanges=exchanges, partitions=partitions,
                         reps=reps,
                         log_dir=(os.path.join(log_base, f"{procs}x{dpp}")
                                  if log_base else None))
        for exchange, partition in cases:
            out[rung_name(procs, dpp, exchange, partition)] = (
                payload["rungs"][rung_name(procs, dpp, exchange, partition)])
    return out


def run():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    payload = eight_device_payload("bfs_sharded", _child, _MARK)
    # mp rungs run from THIS process — the launcher owns the worker
    # gang's device views; the 8-device child never sees them
    payload["multiprocess"] = _run_mp_rungs(payload["scale"])
    _SELECTED.clear()
    _SELECTED.update(payload.get("rungs_matched", []))
    _SELECTED.update(payload["multiprocess"])
    _PAYLOAD.update(_fold_by_scale(payload, repo))

    rows = []
    for name, rung in payload["multiprocess"].items():
        exch = rung.get("exchange_seconds") or {}
        rows.append(row(
            f"bfs_sharded/scale{payload['scale']}/{name}",
            rung["per_root_us"],
            f"layer=multiprocess;procs={rung['procs']};"
            f"hmean_GTEPS={rung['harmonic_mean_teps'] / 1e9:.5f};"
            f"identical={rung['identical']};"
            f"exchange_s={exch.get('total_seconds', float('nan')):.4f}"))
    for name, rung in payload["mesh_ladder"].items():
        rows.append(row(
            f"bfs_sharded/scale{payload['scale']}/mesh{name}",
            rung["per_root_us"],
            f"layer={rung['layer']};"
            f"hmean_GTEPS={rung['harmonic_mean_teps'] / 1e9:.5f};"
            f"wall_us={rung['wall_us']:.0f};n_roots={rung['n_roots']}"))
    for n_dev, rung in payload["root_parallel"].items():
        rows.append(row(
            f"bfs_sharded/scale{payload['scale']}/root_parallel{n_dev}",
            rung["per_root_us"],
            f"rel_vs_single="
            f"{rung.get('rel_per_root_vs_single', float('nan')):.3f};"
            f"identical={payload['parents_bitwise_identical']}"))
    return rows


if __name__ == "__main__":
    if "--child" in sys.argv:
        print(_MARK + json.dumps(_child()))
    else:
        from benchmarks.common import print_rows
        print_rows(run())
