"""The trace reduction's arithmetic, on a small trace recorded on the
CPU (``data/cpu_trace.json``, made by ``data/record_trace.py``) and on
hand-made overlapping intervals."""
import json
import os

import numpy as np
import pytest

from bench import trace
from bench.trace import Summary

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.json")


def recorded() -> Summary:
    with open(DATA) as f:
        d = json.load(f)
    return Summary(ops={"cpu": [tuple(o) for o in d["ops"]]},
                   spans=[tuple(s) for s in d["spans"]])


def brute_busy(ops, t0, t1, step=1000.0):
    """Covered time on a grid of ``step`` ns, for intervals whose ends
    lie on the grid."""
    grid = np.arange(t0, t1, step)
    hit = np.zeros(grid.size, bool)
    for _, a, b in ops:
        hit |= (grid >= a) & (grid < b)
    return hit.sum() * step


def test_recorded_trace_busy_idle_and_ops():
    s = recorded()
    t0, t1 = s.window()
    assert (t0, t1) == (0.0, max(b for n, _, b in s.spans if n == "window"))
    ops = s.ops["cpu"]
    busy = sum(b - a for _, a, b in ops)          # these do not overlap
    assert s.busy_s() == pytest.approx(busy / 1e9)
    assert s.window_s() == pytest.approx((t1 - t0) / 1e9)
    assert s.idle_share() == pytest.approx(1 - busy / (t1 - t0))
    secs, n = s.op_seconds("sort")
    assert n == 3
    assert secs == pytest.approx(
        sum(b - a for name, a, b in ops if name.startswith("sort")) / 1e9)


def test_recorded_trace_breakdown():
    s = recorded()
    bd = s.breakdown()
    assert [n for n, _ in bd["device_ops"]] == ["sort.0", "broadcast_add_fusion"]
    gaps = trace.idle_gaps(s.ops["cpu"], *s.window())
    assert sum(b - a for a, b in gaps) / 1e9 == pytest.approx(
        s.window_s() - s.busy_s())
    assert len(bd["idle_gaps"]) == min(10, len(gaps))
    secs = [g for _, g in bd["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    # The gaps between searches are the harness's own loop; the gap at a
    # search's start is dispatch inside that search's span.
    names = {n for n, _ in bd["idle_gaps"]}
    assert names <= {"search", "host"} and "host" in names


def test_overlapping_ops_are_counted_once():
    ops = [("a", 0, 10_000), ("b", 5_000, 20_000), ("a", 30_000, 40_000),
           ("c", 35_000, 36_000), ("d", 60_000, 90_000)]
    s = Summary(ops={"chip0": ops, "chip1": [("a", 0, 80_000)]},
                spans=[("window", 0, 80_000), ("search", 20_000, 35_000)])
    # chip0: [0,20k] + [30k,40k] + [60k,80k] (clipped) = 50k; chip1: 80k.
    assert brute_busy(ops, 0, 80_000) == 50_000
    assert s.busy_s() == pytest.approx((50_000 + 80_000) / 2 / 1e9)
    assert s.idle_share() == pytest.approx(1 - 65_000 / 80_000)
    secs, n = s.op_seconds("a")
    assert n == 1 and secs == pytest.approx((20_000 + 80_000) / 2 / 1e9)
    bd = s.breakdown()
    assert bd["idle_gaps"] == [["search", 10e-6], ["host", 20e-6]][::-1] \
        or bd["idle_gaps"] == [["host", 20e-6], ["search", 10e-6]]


def test_device_op_names():
    event = ("%core_spmv.1 = s32[61440,1]{1,0:T(8,128)S(1)} custom-call("
             "u32[61440,1920]{1,0:T(8,128)} %get-tuple-element.111)")
    user = "%fusion.93 = (s32[61440]) fusion(s32[61440,1] %core_spmv.1)"
    assert trace.op_name(event) == "core_spmv.1"
    assert trace.op_kind(event) == "core_spmv"
    assert trace.op_kind(user) == "fusion"
    assert trace.op_kind("%while.19 = (s32[4]) while(...)") == "while"
    assert trace.op_kind("sort.0") == "sort"
    s = Summary(ops={"tpu0": [(event, 0, 30), (user, 30, 31),
                              ("%while.2 = () while()", 0, 40)]},
                spans=[("window", 0, 40)])
    assert s.op_seconds("core_spmv") == (30e-9, 1)
    assert [n for n, _ in s.breakdown()["device_ops"]] == ["core_spmv.1",
                                                           "fusion.93"]
    assert s.busy_s() == 40e-9


def test_union_and_gaps():
    iv = [("x", 5, 7), ("x", 1, 3), ("x", 2, 4), ("x", 7, 9)]
    assert trace.union(iv) == [(1, 4), (5, 9)]
    assert trace.covered(iv) == 7
    assert trace.idle_gaps(iv, 0, 12) == [(0, 1), (4, 5), (9, 12)]
    assert trace.clip(iv, 3, 6) == [("x", 5, 6), ("x", 3, 4)]


def test_nothing_traced_reads_nothing():
    s = Summary()
    assert s.idle_share() is None and s.busy_s() == 0.0
    assert s.breakdown() == {"device_ops": [], "idle_gaps": []}
