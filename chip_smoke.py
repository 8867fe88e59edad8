"""Smoke run of the Graph500 main path on a TPU, through the entry points
a user calls.

    python chip_smoke.py               # one chip: offline harness + serving
    python chip_smoke.py --four-chips  # four chips: vertex-sharded plan only

One chip, offline phase: ``pipeline.build`` + ``pipeline.run`` of the
``pre-g500`` rung (per-root bitmap engine, dense heavy core, compiled
Pallas kernels) at scale 20, edgefactor 16, 64 search keys, every root
spec-validated.  Memory would allow scale 21 on one v5e (16 GB): the
scale-21 per-root program needs 1.5 GB of arguments and 0.3 GB of
temporaries, the check phase 2.9 GB.  Time does not: at scale 21 the 64
searches and their checks took 757 s on a v5e, and the whole run must
stay well inside 1200 s.

One chip, serving phase: ``pipeline.serve`` with the default
``ServeConfig`` batch of 8 at scale 18, answering a short Poisson x
Zipf trace.  Memory would allow scale 21 (the batch program needs
6.2 GB there), but under ``vmap`` every level runs both directions for
all 8 roots, and one scale-20 batch takes minutes.  Every answer is
spec-checked and must equal the offline per-root engine's parents for
the same root.

Four chips: ``compile_plan`` of the ("group", "member") plan on a 2x2
mesh (``hier_or`` exchange, ``word_cyclic`` partition) at the offline
scale for 8 roots, against the one-chip per-root engine on the same
roots; the parents must be bitwise equal.

No recovery path runs (no retries, no fallback, no re-queue), so a
fault fails the run.  Earlier lines report, per phase, the graph size,
``memory_analysis()`` bytes, compile seconds, a harmonic-mean TEPS
smoke reading (one run, not a benchmark) and the check counts.  The
last line is one JSON object naming the device; it is printed only
when every check passed on a TPU.  The script exits non-zero, with no
JSON line, when JAX finds no TPU or when it runs outside a checkout.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OFFLINE_SCALE = 20
SERVE_SCALE = 18
SERVE_QUERIES = 24
GB = 1e9


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def expect(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def memory_line(name: str, compiled_exe, device) -> None:
    """Print a compiled program's device bytes and fail when they exceed
    the device's memory."""
    ma = compiled_exe.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    say(f"  {name}: arguments {ma.argument_size_in_bytes / GB:.3f} GB, "
        f"outputs {ma.output_size_in_bytes / GB:.3f} GB, "
        f"temporaries {ma.temp_size_in_bytes / GB:.3f} GB "
        f"(total {total / GB:.3f} GB per device)")
    stats = device.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if limit:
        expect(total <= limit,
               f"{name} needs {total / GB:.3f} GB, the device has "
               f"{limit / GB:.3f} GB")


def peak_line(device) -> None:
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        say(f"  device peak so far: {stats['peak_bytes_in_use'] / GB:.3f} GB "
            f"of {stats.get('bytes_limit', 0) / GB:.3f} GB")


def graph_line(built) -> None:
    core = built.core
    k = f"K={core.k} (heavy {int(core.k_heavy)})" if core is not None else "K=0"
    say(f"  V={built.n_vertices} nnz={built.nnz} {k} "
        f"build {built.construction_s:.1f} s")


def check_run(name: str, run, n_roots: int) -> None:
    say(f"  {name}: check_counts {run.check_counts}, validated "
        f"{sum(run.validated)}/{n_roots}, quarantined {run.quarantined}")
    say(f"  {name}: harmonic-mean TEPS {run.harmonic_mean_teps:.6g} "
        f"(smoke reading from one run, not a benchmark result)")
    expect(len(run.validated) == n_roots and all(run.validated),
           f"{name}: not every root validated: {run.check_failures}")
    expect(not run.quarantined, f"{name}: quarantined {run.quarantined}")
    expect(not any(run.check_counts.values()),
           f"{name}: checks failed: {run.check_counts}")


def compile_program(name: str, compiled, roots, device):
    """AOT-compile the plan's traversal program for ``roots``; print its
    compile seconds and device bytes."""
    t0 = time.perf_counter()
    exe = compiled.lower(roots).compile()
    say(f"  {name}: compiled in {time.perf_counter() - t0:.1f} s")
    memory_line(name, exe, device)
    return exe


def check_phase_memory(ev, n_roots: int, device) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.validate import validate_batch

    v = ev.num_vertices
    rows = jax.ShapeDtypeStruct((n_roots, v), jnp.int32)
    roots = jax.ShapeDtypeStruct((n_roots,), jnp.int32)
    memory_line(f"check phase ({n_roots} roots)",
                validate_batch.lower(ev, rows, rows, roots).compile(), device)


def offline_phase(scale: int, seed: int, device) -> None:
    import numpy as np

    from repro.core import pipeline
    from repro.core.plan import compile_plan

    say(f"offline phase: pre-g500, scale {scale}, edgefactor 16, 64 roots")
    cfg = pipeline.Graph500Config.ladder(
        "pre-g500", scale=scale, edge_factor=16, seed=seed, n_roots=64,
        check="post", retries=0, fallback=False)
    built = pipeline.build(cfg)
    graph_line(built)
    compiled = compile_plan(cfg.to_plan(), built)
    exe = compile_program("per-root program", compiled, np.int32(0), device)
    expect("tpu_custom_call" in exe.as_text(),
           "per-root program holds no compiled Pallas kernel")
    del exe, compiled
    check_phase_memory(built.ev, cfg.n_roots, device)
    t0 = time.perf_counter()
    _, run = pipeline.run(cfg, built)
    say(f"  pipeline.run: {time.perf_counter() - t0:.1f} s wall")
    check_run("offline", run, cfg.n_roots)
    peak_line(device)


def serve_phase(scale: int, seed: int, n_queries: int, device) -> None:
    import numpy as np

    from repro.core import pipeline
    from repro.core.plan import compile_plan
    from repro.core.validate import failure_report, validate_batch
    from repro.data.query_trace import synth_trace
    from repro.serve.engine import ServeConfig

    say(f"serve phase: pre-g500, scale {scale}, batch 8, {n_queries} queries")
    cfg = pipeline.Graph500Config.ladder(
        "pre-g500", scale=scale, edge_factor=16, seed=seed, check="post",
        retries=0, fallback=False)
    built = pipeline.build(cfg)
    graph_line(built)
    t0 = time.perf_counter()
    serve_cfg = ServeConfig(retries=0, max_requeues=0,
                            fallback_on_requeue=False)
    built, engine = pipeline.serve(cfg, serve_cfg, built=built)
    say(f"  engine up (compile + warm-up batch) in "
        f"{time.perf_counter() - t0:.1f} s")
    memory_line("serve batch program",
                engine.compiled.lower(
                    np.zeros(serve_cfg.batch_size, np.int32)).compile(),
                device)

    trace = synth_trace(seed, n_queries, built.n_vertices,
                        degree=np.asarray(built.degree))
    t0 = time.perf_counter()
    report = engine.serve(trace)
    summary = report.summary()
    counts = summary.get("check_counts", {})
    say(f"  served {len(report.answers)} queries in "
        f"{time.perf_counter() - t0:.1f} s wall: kinds {summary['kinds']}, "
        f"{len(report.batches)} batches, check_counts {counts}")
    expect(len(report.answers) == n_queries,
           f"{len(report.answers)} answers for {n_queries} queries")
    failed = [a.qid for a in report.answers if a.parent is None]
    expect(not failed, f"queries without an answer: {failed}")
    expect(not any(counts.values()), f"serving checks failed: {counts}")

    answers = sorted(report.answers, key=lambda a: a.qid)
    roots = np.asarray([a.root for a in answers], np.int32)
    parents = np.stack([a.parent for a in answers])
    levels = np.stack([a.level for a in answers])
    counts, failures = failure_report(
        validate_batch(built.ev, parents, levels, roots))
    say(f"  answers re-checked against the spec: {counts}")
    expect(not failures, f"answers failing the spec checks: {failures}")

    distinct = sorted(set(int(r) for r in roots))
    offline = compile_plan(cfg.to_plan(), built).run(distinct, check="post")
    check_run("offline per-root engine", offline.run, len(distinct))
    want = dict(zip(distinct, offline.parent))
    diff = [a.qid for a in answers
            if not np.array_equal(a.parent, want[int(a.root)])]
    say(f"  {len(distinct)} distinct roots; answers equal to the offline "
        f"per-root engine: {len(answers) - len(diff)}/{len(answers)}")
    expect(not diff, f"answers differ from the offline engine: {diff}")
    peak_line(device)


def four_chip_phase(scale: int, seed: int, devices) -> None:
    import numpy as np

    from repro.core import pipeline
    from repro.core.plan import TraversalPlan, compile_plan

    expect(len(devices) >= 4, f"four chips wanted, JAX sees {len(devices)}")
    say(f"four-chip phase: ('group', 'member') 2x2, hier_or, word_cyclic, "
        f"scale {scale}, 8 roots")
    cfg = pipeline.Graph500Config.ladder(
        "pre-g500", scale=scale, edge_factor=16, seed=seed, n_roots=8)
    built = pipeline.build(cfg)
    graph_line(built)
    roots = np.asarray(pipeline.search_keys(cfg, built))

    plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2),
                         exchange="hier_or", partition="word_cyclic",
                         batch_roots=False)
    t0 = time.perf_counter()
    sharded = compile_plan(plan, built)
    say(f"  compile_plan (host partition of the graph): "
        f"{time.perf_counter() - t0:.1f} s wall")
    mesh_devices = set(sharded.mesh.devices.flat)
    expect(len(mesh_devices) == 4, f"mesh spans {len(mesh_devices)} devices")
    exe = compile_program("vertex-sharded program", sharded,
                          np.int32(roots[0]), devices[0])
    hlo = exe.as_text()
    ops = {op: hlo.count(op) for op in
           ("all-to-all", "all-gather", "all-reduce", "tpu_custom_call")}
    say(f"  ops in the compiled program: {ops}")
    expect(ops["all-to-all"] + ops["all-gather"] + ops["all-reduce"] > 0,
           "the vertex-sharded program has no collective")
    del exe, hlo
    out = sharded.bfs(int(roots[0]))
    placed = out.parent.sharding.device_set
    say(f"  mesh devices {sorted(d.id for d in mesh_devices)}; parent "
        f"output spans {len(placed)} devices")
    expect(placed == mesh_devices,
           f"parent output lives on {len(placed)} devices, not the mesh's 4")

    single = compile_plan(cfg.to_plan(), built)
    res_sharded = sharded.run(roots, check="post")
    check_run("four chips", res_sharded.run, len(roots))
    res_single = single.run(roots, check="off")
    same = [bool(np.array_equal(a, b))
            for a, b in zip(res_sharded.parent, res_single.parent)]
    say(f"  parents bitwise equal to the one-chip engine: "
        f"{sum(same)}/{len(same)} roots")
    expect(all(same), "sharded parents differ from the one-chip engine")
    for d in devices[:4]:
        peak_line(d)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip vertex-sharded phase")
    ap.add_argument("--seed", type=int, default=42,
                    help="seed of the graph, the search keys and the trace")
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke.py: no src/repro next to {__file__}; run it from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    from repro.util import use_compile_cache

    cache = use_compile_cache(HERE)
    cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 3
    say(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache} ({cached} entries at start)")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chip_phase(OFFLINE_SCALE, args.seed, devices)
        else:
            offline_phase(OFFLINE_SCALE, args.seed, dev)
            gc.collect()
            serve_phase(SERVE_SCALE, args.seed, SERVE_QUERIES, dev)
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
