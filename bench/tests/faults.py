"""Shared helpers for the fault tests: a cell cut to a CPU test size,
driven through ``bench.run.execute`` with the chip look skipped."""
import time

import jax

from bench import registry
from bench.run import execute

SEED = 2**31 + 11


UNITS = {"setup_s": "s", "bfs_teps": "TEPS", "peak_hbm_gb": "GB"}


def small(config: str, traffic: str, scale: int, metrics, **params):
    """A one-chip cell of ``config`` under ``traffic`` at ``scale``,
    reporting the end-to-end ``metrics``; ``params`` override the mix's."""
    return registry.Cell(
        name=f"{config}.{traffic}", chips=1,
        config=dict(registry.config(config), scale=scale),
        traffic=dict(registry.traffic(traffic), **params),
        end_to_end=[{"name": m, "unit": UNITS[m]} for m in metrics],
        per_layer=[])


def drive(cell, seconds: float = 1.0, trace: bool = False) -> dict:
    return execute(cell, SEED, seconds, trace, jax.devices(),
                   registry.peaks("TPU v5 lite"), time.perf_counter())
