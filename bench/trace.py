"""Profiler trace capture and its reduction to per-layer numbers.

A traced run records the measured window with ``jax.profiler`` and reads
the ``.xplane.pb`` back with ``jax.profiler.ProfileData``.  Device
operations come from the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane; host spans are the benchmark's own ``TraceAnnotation``s
(``window``, ``search``) on the host plane.  Everything
below :func:`load` works on plain ``(name, start_ns, end_ns)`` tuples,
so the arithmetic is tested on a small recorded trace kept in
``bench/tests/data``.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
SPANS = ("window", "search")
# Ops whose interval holds other ops: they count towards busy time, but
# the breakdown lists the ops that do the work.
CONTAINERS = ("while", "cond", "conditional", "call")


def op_name(event: str) -> str:
    """The HLO instruction name of a device event: ``fusion.9`` of
    ``%fusion.9 = s32[...] fusion(...)``."""
    return event.split(" = ", 1)[0].lstrip("%")


def op_kind(event: str) -> str:
    """The instruction name without its numeric suffix: ``fusion``."""
    name = op_name(event)
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_lowerings = [0]


def _count_lowering(event, duration, **kw):
    if event == LOWERING:
        _lowerings[0] += 1


class Tracer:
    """Host spans always; a profiler trace of the window when enabled.
    Counts the programs JAX lowers (to compile, or to find in its cache)
    inside the window: a warm window lowers none."""

    _listening = False

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None
        self.summary = None
        self.lowerings = 0
        if not Tracer._listening:
            import jax

            jax.monitoring.register_event_duration_secs_listener(
                _count_lowering)
            Tracer._listening = True

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        before = _lowerings[0]
        try:
            if not self.enabled:
                with self.span(WINDOW):
                    yield
                return
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                with self.span(WINDOW):
                    yield
            finally:
                jax.profiler.stop_trace()
        finally:
            self.lowerings = _lowerings[0] - before

    def reduce(self) -> "Summary | None":
        """Read the recorded trace (once) and delete its files."""
        if self.dir is None:
            return None
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            self.summary = load(files[0]) if files else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
        return self.summary


@dataclass
class Summary:
    """Device ops per chip and host spans, all in nanoseconds on the
    profiler's clock."""

    ops: dict = field(default_factory=dict)      # plane -> [(name, t0, t1)]
    spans: list = field(default_factory=list)    # [(name, t0, t1)]

    def window(self):
        w = [(a, b) for n, a, b in self.spans if n == WINDOW]
        return (min(a for a, _ in w), max(b for _, b in w)) if w else None

    def window_s(self) -> float:
        w = self.window()
        return (w[1] - w[0]) / 1e9 if w else 0.0

    def busy_s(self) -> float:
        """Seconds in which an op ran, inside the window, averaged over
        the chips."""
        w = self.window()
        if w is None or not self.ops:
            return 0.0
        total = sum(covered(clip(ops, *w)) for ops in self.ops.values())
        return total / len(self.ops) / 1e9

    def idle_share(self) -> float | None:
        """1 - busy / window, or None when nothing was traced."""
        ws = self.window_s()
        busy = self.busy_s()
        if ws <= 0 or busy <= 0:
            return None
        return 1.0 - busy / ws

    def op_seconds(self, kind: str) -> tuple[float, int]:
        """Summed device seconds and count of the ops in the window whose
        instruction name is ``kind`` with any numeric suffix, averaged over
        the chips."""
        w = self.window()
        if w is None or not self.ops:
            return 0.0, 0
        secs, n = 0.0, 0
        for ops in self.ops.values():
            for name, a, b in clip(ops, *w):
                if op_kind(name) == kind:
                    secs += (b - a) / 1e9
                    n += 1
        return secs / len(self.ops), n // len(self.ops)

    def breakdown(self, top: int = 10) -> dict:
        """The device ops (by instruction name, loops and conditionals
        left out) that took most time, and the longest idle gaps named by
        the host span that covers most of each."""
        w = self.window()
        if w is None or not self.ops:
            return {"device_ops": [], "idle_gaps": []}
        by_name: dict = {}
        gaps = []
        for ops in self.ops.values():
            ops = clip(ops, *w)
            for name, a, b in ops:
                if op_kind(name) not in CONTAINERS:
                    name = op_name(name)
                    by_name[name] = by_name.get(name, 0) + (b - a)
            gaps.extend(idle_gaps(ops, *w))
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n, t / 1e9 / len(self.ops)] for n, t in ranked],
            "idle_gaps": [[host_activity(self.spans, a, b), (b - a) / 1e9]
                          for a, b in longest],
        }


def load(path: str) -> Summary:
    """Device ops and host spans of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    out = Summary()
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    out.ops.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out.spans.extend((e.name, e.start_ns, e.end_ns)
                                 for e in line.events if e.name in SPANS)
    return out


def clip(intervals, t0, t1):
    """The intervals cut to ``[t0, t1]``, empty ones dropped."""
    return [(n, max(a, t0), min(b, t1)) for n, a, b in intervals
            if min(b, t1) > max(a, t0)]


def union(intervals):
    """Merged ``(start, end)`` pairs covering the same time."""
    out = []
    for a, b in sorted((a, b) for _, a, b in intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def covered(intervals) -> float:
    """Nanoseconds covered by the union of the intervals."""
    return sum(b - a for a, b in union(intervals))


def idle_gaps(intervals, t0, t1):
    """The ``(start, end)`` stretches of ``[t0, t1]`` no interval covers."""
    gaps, t = [], t0
    for a, b in union(intervals):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < t1:
        gaps.append((t, t1))
    return gaps


def host_activity(spans, a, b) -> str:
    """What the host was doing in ``[a, b]``: the name of the host span
    (other than the window) that covers most of it, or ``"host"`` when
    the stretch outside every span, the harness's own loop, is longer."""
    inside = [(n, max(a, s0), min(b, s1)) for n, s0, s1 in spans
              if n != WINDOW and min(b, s1) > max(a, s0)]
    share = {"host": (b - a) - covered(inside)}
    for name, s0, s1 in inside:
        share[name] = share.get(name, 0) + (s1 - s0)
    return max(share, key=share.get)
