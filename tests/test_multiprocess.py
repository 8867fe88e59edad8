"""Multi-process launcher tests (DESIGN.md §15).

The launcher spawns REAL ``jax.distributed`` worker processes over
localhost TCP, so these tests exercise the full rendezvous → global
mesh → cross-process exchange → payload-collection path:

  * bitwise parity — a 2-proc × 2-device gang must produce parents
    identical to the in-worker single-device oracle AND to a
    single-process run faking the same 4-device view (both partitions,
    ``hier_or`` + ``hier_gather``, one gang);
  * clean shutdown — a worker that dies at bring-up must fail the
    launch AND take the surviving ranks down with it (no orphans);
  * fault detection across the process boundary — a §13 ``FaultSpec``
    exchange fault injected into the cross-process wire must be caught
    by the check machinery, not silently validated.

Scale is small (the graph build and interpret-mode traversal run once
per worker) but every byte of the inter-group leg crosses a process
boundary — this is the one place in the suite where the exchange is
not a memcpy.
"""
import json
import os
import sys
import textwrap
import time

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from repro.util import respawn_with_host_devices  # noqa: E402
from repro.launch.multiprocess import (  # noqa: E402
    free_port,
    launch,
    parse_inject,
    rung_name,
)

SCALE = 8
ROOTS = 4


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


def test_rung_name_roundtrip():
    assert rung_name(2, 4, "hier_or", "block") == "mp_2x4"
    assert rung_name(4, 2, "hier_gather", "word_cyclic") == \
        "mp_4x2_gather_cyc"
    assert rung_name(2, 2, "flat", "block") == "mp_2x2_flat"


def test_parse_inject():
    spec = parse_inject("exchange/zero/1/persistent")
    assert (spec.site, spec.kind, spec.level, spec.persistent) == \
        ("exchange", "zero", 1, True)
    assert parse_inject(None) is None
    with pytest.raises(ValueError):
        parse_inject("exchange")


def test_free_port_is_bindable():
    import socket

    port = free_port()
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", port))


def test_two_proc_parity_both_partitions(tmp_path):
    """2 procs x 2 devices, hier_or + hier_gather x block +
    word_cyclic in ONE gang: every rung bitwise-identical to the
    in-worker single-device oracle, and to a single-process run faking
    the same 4-device global view (the tentpole acceptance, scaled to
    test budget)."""
    payload = launch(
        2, 2, scale=SCALE, n_roots=ROOTS, reps=1,
        exchanges="hier_or,hier_gather",
        partitions="block,word_cyclic",
        log_dir=str(tmp_path / "logs"))
    assert payload["parents_bitwise_identical"] is True
    expected = {rung_name(2, 2, e, p)
                for e in ("hier_or", "hier_gather")
                for p in ("block", "word_cyclic")}
    assert set(payload["rungs"]) == expected
    for name, rung in payload["rungs"].items():
        assert rung["identical"] is True, name
        assert rung["parent_sha256"] == payload["oracle_sha256"], name
        assert rung["validated"] is True, name
        # the exchange leg is measured, one entry per BFS level
        exch = rung["exchange_seconds"]
        assert exch["levels"] == len(exch["per_level"]) > 0
        assert exch["total_seconds"] > 0.0
        assert all(lv["seconds"] > 0.0 for lv in exch["per_level"])

    # the same plan on ONE process faking the 4-device view must land on
    # the same bits (the launcher changed the runtime, not the program)
    out = respawn_with_host_devices([sys.executable, "-c", textwrap.dedent(
        f"""
        import numpy as np
        from repro.core.plan import TraversalPlan, compile_plan
        from repro.core.tune import _build_inputs
        from repro.launch.multiprocess import parent_digest

        pg, degree, roots, v = _build_inputs({SCALE}, 1, 16, {ROOTS})
        plan = TraversalPlan(layout=("group", "member"), mesh_shape=(2, 2))
        res = compile_plan(plan, pg).run(roots, check="post")
        print("SP_SHA=" + parent_digest(res.parent[:, :v]))
        """)], 4, pythonpath=(REPO_SRC,), capture=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    sp_sha = [ln for ln in out.stdout.splitlines()
              if ln.startswith("SP_SHA=")][0][len("SP_SHA="):]
    assert sp_sha == payload["rungs"]["mp_2x2"]["parent_sha256"]


def test_worker_crash_kills_gang_no_orphans(tmp_path):
    """Rank 1 dying at bring-up must fail the launch, surface the dead
    rank's log, and leave NO surviving worker processes behind."""
    log_dir = tmp_path / "logs"
    os.environ["REPRO_MP_CRASH_RANK"] = "1"
    try:
        with pytest.raises(RuntimeError, match="exit 17"):
            launch(2, 2, scale=SCALE, n_roots=2, log_dir=str(log_dir),
                   timeout_s=600.0)
    finally:
        del os.environ["REPRO_MP_CRASH_RANK"]
    pids = []
    for rank in range(2):
        with open(log_dir / f"rank{rank}.pid") as f:
            pids.append(int(f.read()))
    deadline = time.time() + 10.0
    while time.time() < deadline and any(_pid_alive(p) for p in pids):
        time.sleep(0.1)
    alive = [p for p in pids if _pid_alive(p)]
    assert not alive, f"orphaned worker pids after failed launch: {alive}"


def test_exchange_fault_detected_across_processes(tmp_path):
    """A §13 exchange fault injected into the REAL cross-process wire:
    the run must complete with the fault *detected* by check="full"
    (nonzero check counts / quarantined roots), never silently
    validated."""
    payload = launch(
        2, 2, scale=SCALE, n_roots=2, check="full",
        inject="exchange/zero/1/persistent",
        log_dir=str(tmp_path / "logs"))
    rung = payload["rungs"]["mp_2x2"]
    g500 = rung["g500"]
    caught = (sum(rung["check_counts"].values()) > 0
              or bool(g500["check_failures"]) or g500["quarantined"])
    assert caught, (
        f"persistent exchange fault crossed the process boundary "
        f"undetected: check_counts={rung['check_counts']} "
        f"g500={json.dumps({k: g500[k] for k in ('check_counts', 'check_failures', 'quarantined')})}")
    assert rung["validated"] is False
