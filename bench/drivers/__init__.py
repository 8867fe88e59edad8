"""Cell drivers: each sets up one kind of configuration, measures its
window and checks its answers.  ``bench/configs/<c>.json`` names its
driver; every driver exposes ``run(cell, seed, seconds, tracer,
t_start) -> Outcome``."""
from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Outcome:
    """What one run measured and how its answers compared.

    ``metrics`` holds end-to-end values by metric name (the run keeps the
    ones its cell reports); ``checks`` maps each compared number to
    ``(value, limit)``; ``shapes`` are what per-layer readers read
    besides the trace.
    """

    setup_s: float
    attempted: int
    failed: int
    metrics: dict
    checks: dict
    memory_peak_bytes: int | None
    shapes: dict = field(default_factory=dict)
    notes: list = field(default_factory=list)

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class Reservoir:
    """Seeded uniform sample of at most ``capacity`` items from a stream
    of unknown length (algorithm R); ``items`` are in stream order."""

    def __init__(self, rng, capacity: int):
        self.rng = rng
        self.capacity = capacity
        self.slots: list = []
        self.seen = 0

    def offer(self, item) -> None:
        i, self.seen = self.seen, self.seen + 1
        if i < self.capacity:
            self.slots.append((i, item))
            return
        j = int(self.rng.integers(0, i + 1))
        if j < self.capacity:
            self.slots[j] = (i, item)

    @property
    def items(self) -> list:
        return [item for _, item in sorted(self.slots, key=lambda s: s[0])]
