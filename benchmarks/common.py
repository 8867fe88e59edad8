"""Shared benchmark utilities."""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

FAST = os.environ.get("BENCH_FAST", "1") != "0"


def timed(fn, *args, repeats: int = 3, warmup: int = 1):
    """Median wall time of fn(*args) in seconds (block_until_ready aware)."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def rung_filter() -> set[str] | None:
    """Parse BENCH_RUNGS (set by ``benchmarks/run.py --rungs``).

    Returns the selected rung names, or None for "run everything" — the
    one copy shared by every rung-aware module.
    """
    env = os.environ.get("BENCH_RUNGS", "").strip()
    if not env:
        return None
    return {r.strip() for r in env.split(",") if r.strip()}


def row(name: str, us_per_call: float, derived: str) -> dict:
    return {"name": name, "us_per_call": us_per_call, "derived": derived}


def print_rows(rows):
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")


def eight_device_payload(module: str, child, mark: str) -> dict:
    """The JSON payload of a benchmark body written for 8 devices.

    On the CPU, ``benchmarks.<module> --child`` runs in a child process
    with 8 forced host devices (this process's JAX already has its own
    device view) and prints its payload after ``mark``.  On an
    accelerator this process holds the chips, so ``child()`` runs here,
    on ``jax.devices()``.
    """
    import jax

    if jax.default_backend() != "cpu":
        return json.loads(json.dumps(child()))  # the child's JSON types
    from repro.util import respawn_with_host_devices

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = respawn_with_host_devices(
        [sys.executable, "-m", f"benchmarks.{module}", "--child"], 8,
        pythonpath=(os.path.join(repo, "src"), repo),
        capture=True, cwd=repo, timeout=7200)
    if proc.returncode != 0:
        raise RuntimeError(f"{module} benchmark child failed:\n"
                           f"{proc.stderr[-4000:]}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith(mark):
            return json.loads(line[len(mark):])
    raise RuntimeError(f"no payload marker in child stdout:\n"
                       f"{proc.stdout[-2000:]}")
