"""Graph500 kernel 3 offline: per-root shortest-path searches over the
spec's keys.

Set-up builds the weighted graph through ``pipeline.build`` with
``kernel="sssp"`` and the configured plan rung through ``compile_plan``,
and runs one warm-up search.  The window runs ``CompiledBFS.bfs`` on the
search keys in turn, back to back; each search's time runs from the end
of the previous search's ``block_until_ready`` to the end of its own, so
the times tile the window.  Each search's counters (``BFSStats``) stay on
the device until the window ends.  Afterwards the reference graph gives
each search its Graph500 edge count (the edges of the key's component,
as in kernel 2) and checks a seeded sample of the searches' distances
and parent trees against exact shortest distances.
"""
from __future__ import annotations

import gc
import json
import time

import numpy as np

from bench import gen, reference_sssp
from bench.drivers import Outcome, Reservoir, peak_bytes
from bench.reference import HostGraph, harmonic_mean, traversed_edges


def program(config: dict, seed: int, n_keys: int):
    """The system under test: the built graph and its compiled plan.
    Raises when the program's distances are not in the reference's
    unit."""
    from repro.core import pipeline
    from repro.core.graph_build import WEIGHT_UNIT
    from repro.core.plan import compile_plan

    if WEIGHT_UNIT != reference_sssp.UNIT:
        raise ValueError(f"the program's distance unit {WEIGHT_UNIT} is not "
                         f"the reference's {reference_sssp.UNIT}")
    cfg = pipeline.Graph500Config.ladder(
        config["rung"], scale=config["scale"],
        edge_factor=config["edge_factor"], seed=seed, n_roots=n_keys,
        degree_sort=config["degree_sort"],
        heavy_threshold=config["heavy_threshold"], kernel=config["kernel"])
    built = pipeline.build(cfg)
    return built, compile_plan(cfg.to_plan(), built)


def run(cell, seed: int, seconds: float, tracer, t_start: float) -> Outcome:
    import jax

    config, traffic = cell.config, cell.traffic
    scale = int(config["scale"])
    src, dst = gen.kronecker_edges(seed, scale, int(config["edge_factor"]))
    keys_orig = cell.generator().make(traffic, seed, src, dst, 1 << scale)

    built, compiled = program(config, seed, len(keys_orig))
    keys = np.asarray(built.reorder.new_from_old)[keys_orig]
    compiled.bfs(int(keys[0])).parent.block_until_ready()

    rng = np.random.default_rng(seed)
    sample = Reservoir(rng, int(traffic["check_sample"]))
    key_of, times, stats = [], [], []
    with tracer.window():
        t0 = t_prev = time.perf_counter()
        while t_prev - t0 < seconds:
            i = len(times) % len(keys)
            with tracer.span("search"):
                res = compiled.bfs(int(keys[i]))
                res.parent.block_until_ready()
            t = time.perf_counter()
            times.append(t - t_prev)
            t_prev = t
            key_of.append(i)
            stats.append((res.stats.levels, res.stats.scanned_edges))
            sample.offer((i, res.parent, res.level))
            del res
    window_s = t_prev - t0
    peak = peak_bytes(jax.devices()[0])
    rounds = [int(n) for n, _ in stats]
    scanned = [int(np.sum(np.asarray(se, np.int64))) for _, se in stats]
    checked = [(i, np.asarray(p), np.asarray(d)) for i, p, d in sample.items]
    del sample, stats, compiled, built
    gc.collect()

    g = HostGraph(src, dst, 1 << scale)
    del src, dst
    w = reference_sssp.slot_weights(g, seed)
    ref_keys = g.new_from_old[keys_orig]
    label = g.components()
    m = {i: traversed_edges(g.degree, np.where(label == label[ref_keys[i]],
                                                 0, -1))
         for i in set(key_of)}
    teps = [m[i] / dt for i, dt in zip(key_of, times)]

    roots = sorted({i for i, _, _ in checked})
    ref = dict(zip(roots, reference_sssp.shortest_distances(
        g, w, [ref_keys[i] for i in roots])))
    dist_mismatch = parent_bad = wrong = 0
    for i, parent, dist in checked:
        d = reference_sssp.compare(g, w, int(ref_keys[i]), parent, dist,
                                   ref[i])
        dist_mismatch += d["dist_mismatch"]
        parent_bad += d["parent_bad"]
        wrong += int(d["dist_mismatch"] + d["parent_bad"] > 0)
    checks = {
        "dist_mismatch": (dist_mismatch, 0),
        "parent_bad": (parent_bad, 0),
        "key_mismatch": (int(np.count_nonzero(keys != ref_keys)), 0),
    }
    shapes = {"sssp": {"rounds": sum(rounds), "scanned_edges": sum(scanned),
                       "valid_slots": int(g.keys.size)}}
    notes = [f"searches {len(times)} in {window_s:.3f} s, checked "
             f"{len(checked)}, distinct keys {len(m)}, "
             f"V={g.n} nnz={g.keys.size}",
             "per search [key, ms, edges, rounds, scanned]: " + json.dumps(
                 [[i, round(1e3 * dt, 3), m[i], r, sc] for i, dt, r, sc
                  in zip(key_of, times, rounds, scanned)])]
    return Outcome(
        setup_s=t0 - t_start, attempted=len(times), failed=wrong,
        metrics={"bfs_teps": harmonic_mean(teps),
                 "peak_hbm_gb": None if peak is None else peak / 1e9},
        checks=checks, memory_peak_bytes=peak, shapes=shapes, notes=notes)
