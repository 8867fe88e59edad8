"""Run one benchmark cell once on the chip.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The run refuses to start (non-zero exit,
no result) unless JAX finds a TPU with as many chips as the cell asks
for, keeps JAX's compilation cache in ``.jax_cache/`` of the checkout
(or where ``JAX_COMPILATION_CACHE_DIR`` says), sets up and warms only the
cell's own shapes, measures for ``--seconds``, and then checks its
answers against the plain reference.  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each compared number with its limit, which also close
standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fail(msg: str, code: int) -> int:
    print(f"bench.run: {msg}", file=sys.stderr)
    return code


class Context:
    """What a per-layer reader reads: the trace summary (None when the
    run was not traced), the driver's kernel shapes, and the device's
    peaks."""

    def __init__(self, outcome, summary, peaks: dict):
        self.trace = summary
        self.shapes = outcome.shapes
        self.peaks = peaks


def execute(cell, seed: int, seconds: float, trace: bool, devices,
            peaks: dict, t_start: float) -> dict:
    """Set up, measure and check one run of ``cell``; the result line."""
    from bench.registry import reader
    from bench.trace import Tracer

    tracer = Tracer(trace)
    outcome = cell.driver().run(cell, seed, seconds, tracer, t_start)
    values = dict(outcome.metrics, setup_s=outcome.setup_s)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    if trace:
        summary = tracer.reduce()
        ctx = Context(outcome, summary, peaks)
        metrics = {}
        for m in cell.per_layer:
            value = reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None:
            device.update(busy_s=summary.busy_s(),
                          window_s=summary.window_s())
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}
    result.update(metrics=metrics, device=device)
    if trace and summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in outcome.checks.items()}
    result["notes"] = outcome.notes + [
        f"programs lowered inside the window: {tracer.lowerings}"]
    return result


def emit(result: dict) -> None:
    notes = result.pop("notes", [])
    checks = result.pop("checks")
    result["checks"] = checks          # the last key of the line
    for line in notes:
        print(line, file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return fail(f"no src/repro in {ROOT}: run from a checkout of the "
                    f"repository", 2)
    sys.path.insert(0, src)
    from bench import registry

    try:
        cell = registry.load_cell(args.workload)
    except registry.UnknownName as e:
        return fail(str(e), 2)

    # The benchmark makes its inputs on the host's CPU backend (bench/gen.py).
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform!r})", 3)
    if len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips, JAX sees "
                    f"{len(devices)}", 3)
    devices = devices[:cell.chips]
    try:
        peaks = registry.peaks(devices[0].device_kind)
    except registry.UnknownName as e:
        return fail(str(e), 3)
    from repro.util import use_compile_cache

    use_compile_cache(ROOT)
    # Cache every program, not only those that compile for over a second,
    # so that only a checkout's first run of a cell compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    emit(execute(cell, args.seed, args.seconds, bool(args.trace), devices,
                 peaks, t_start))
    return 0


if __name__ == "__main__":
    sys.exit(main())
