"""Graph500 kernel 2 offline: per-root searches over the spec's keys.

Set-up builds the graph through ``pipeline.build`` and the configured
plan rung through ``compile_plan``, and runs one warm-up search.  The
window runs ``CompiledBFS.bfs`` on the search keys in turn, back to back;
each search's time runs from the end of the previous search's
``block_until_ready`` to the end of its own, so the times tile the window.
Afterwards the reference graph gives each search its Graph500 edge count
(the edges of the key's component) and checks a seeded sample of the
searches' parent trees and levels.
"""
from __future__ import annotations

import gc
import json
import time

import numpy as np

from bench import gen
from bench.drivers import Outcome, Reservoir, peak_bytes
from bench.reference import HostGraph, compare, harmonic_mean, traversed_edges


def program(config: dict, seed: int, n_keys: int):
    """The system under test: the built graph and its compiled plan."""
    from repro.core import pipeline
    from repro.core.plan import compile_plan

    cfg = pipeline.Graph500Config.ladder(
        config["rung"], scale=config["scale"],
        edge_factor=config["edge_factor"], seed=seed, n_roots=n_keys,
        degree_sort=config["degree_sort"],
        heavy_threshold=config["heavy_threshold"])
    built = pipeline.build(cfg)
    return built, compile_plan(cfg.to_plan(), built)


def kernel_shapes(built) -> dict:
    from repro.core.heavy import padded_bitmap_words

    shapes = {"frontier_update": {"w": padded_bitmap_words(built.n_vertices)}}
    if built.core is not None:
        k, w = built.core.a_core.shape
        shapes["core_spmv"] = {"k": int(k), "w": int(w)}
    return shapes


def run(cell, seed: int, seconds: float, tracer, t_start: float) -> Outcome:
    import jax

    config, traffic = cell.config, cell.traffic
    scale = int(config["scale"])
    src, dst = gen.kronecker_edges(seed, scale, int(config["edge_factor"]))
    keys_orig = cell.generator().make(traffic, seed, src, dst, 1 << scale)

    built, compiled = program(config, seed, len(keys_orig))
    keys = np.asarray(built.reorder.new_from_old)[keys_orig]
    compiled.bfs(int(keys[0])).parent.block_until_ready()
    shapes = kernel_shapes(built)

    rng = np.random.default_rng(seed)
    sample = Reservoir(rng, int(traffic["check_sample"]))
    key_of, times = [], []
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
            sample.offer((i, res.parent, res.level))
            del res
    window_s = t_prev - t0
    peak = peak_bytes(jax.devices()[0])
    checked = [(i, np.asarray(p), np.asarray(l)) for i, p, l in sample.items]
    del sample, compiled, built
    gc.collect()

    g = HostGraph(src, dst, 1 << scale)
    del src, dst
    ref_keys = g.new_from_old[keys_orig]
    label = g.components()
    m = {i: traversed_edges(g.degree, np.where(label == label[ref_keys[i]],
                                                 0, -1))
         for i in set(key_of)}
    teps = [m[i] / dt for i, dt in zip(key_of, times)]

    level_mismatch = parent_bad = wrong = 0
    for i, parent, level in checked:
        root = int(ref_keys[i])
        d = compare(g, root, parent, level, g.bfs_levels(root))
        level_mismatch += d["level_mismatch"]
        parent_bad += d["parent_bad"]
        wrong += int(d["level_mismatch"] + d["parent_bad"] > 0)
    checks = {
        "level_mismatch": (level_mismatch, 0),
        "parent_bad": (parent_bad, 0),
        "key_mismatch": (int(np.count_nonzero(keys != ref_keys)), 0),
    }
    notes = [f"searches {len(times)} in {window_s:.3f} s, checked "
             f"{len(checked)}, distinct keys {len(m)}, "
             f"V={g.n} nnz={g.keys.size}",
             "per search [key, ms, edges]: " + json.dumps(
                 [[i, round(1e3 * dt, 3), m[i]]
                  for i, dt in zip(key_of, times)])]
    return Outcome(
        setup_s=t0 - t_start, attempted=len(times), failed=wrong,
        metrics={"bfs_teps": harmonic_mean(teps),
                 "peak_hbm_gb": None if peak is None else peak / 1e9},
        checks=checks, memory_peak_bytes=peak, shapes=shapes, notes=notes)
