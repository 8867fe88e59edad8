"""Multi-process distributed runtime: real cross-process exchange on
any CI box (DESIGN.md §15).

One process faking 8 host devices turns the per-level frontier
exchange — the whole point of the paper's group-based monitor
communication (T3, Fig. 16) — into a memcpy.  This launcher makes the
exchange real without TPUs:

  * **parent** — picks a localhost rendezvous port, spawns one JAX
    process per "node" (``--procs N``, each seeing ``--devices-per-proc
    D`` forced host devices via :func:`repro.util.
    respawn_with_host_devices`), captures one log file per rank, and
    enforces a hard deadline: a dead or hung worker kills the whole
    gang — no orphans, no silent 6-hour CI cancels.
  * **workers** — ``jax.distributed.initialize`` over localhost TCP
    (gloo CPU collectives), then the EXISTING ``compile_plan`` /
    :class:`~repro.core.plan.CompiledBFS` shard_map programs run
    unchanged over the global N×D mesh.  The plan API aligns the
    ``group`` axis to the process boundary (``core/plan.py``
    process-mesh resolution), so the inter-group monitor leg of the
    two-phase collectives is exactly the leg that crosses processes.
  * **rank 0** — collects the :class:`~repro.core.teps.Graph500Run`
    bookkeeping, the bitwise-parity verdict against the in-process
    single-device oracle and the measured per-level exchange-leg
    wall-clock (:func:`time_exchange_per_level`), and prints one JSON
    payload the parent returns.

Acceptance is bitwise: parents from an N-proc × D-device run must equal
the single-process fake-device run and the single-device oracle for
every partition and every exchange (the worker asserts it; a fault
injected via ``--inject`` is the one sanctioned divergence and must be
*detected* by the §13 check machinery instead).

CLI (the CI multiprocess smoke)::

    PYTHONPATH=src python -m repro.launch.multiprocess \\
        --procs 2 --devices-per-proc 4 --scale 12 --roots 8

    # both partitions, two exchanges, fault injection, bench payload
    PYTHONPATH=src python -m repro.launch.multiprocess \\
        --procs 4 --devices-per-proc 2 --scale 12 --roots 8 \\
        --exchanges hier_or,hier_gather --partitions block,word_cyclic
    PYTHONPATH=src python -m repro.launch.multiprocess \\
        --procs 2 --devices-per-proc 2 --scale 10 \\
        --inject exchange/zero/1/persistent --check full
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import socket
import sys
import tempfile
import time
from typing import Optional

_MARK = "MP_BFS_JSON:"

_SRC_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: rung-name suffix per exchange wiring (matches benchmarks/bfs_sharded)
EXCHANGE_SUFFIX = {"hier_or": "", "hier_gather": "_gather",
                   "hier_min": "_min", "flat": "_flat"}


def rung_name(procs: int, dpp: int, exchange: str, partition: str,
              kernel: str = "bfs") -> str:
    """Canonical multiprocess rung name: ``mp_<procs>x<dpp>`` plus the
    exchange/partition suffixes the sharded ladder already uses (and a
    kernel prefix for non-BFS kernels)."""
    prefix = "" if kernel == "bfs" else f"{kernel}_"
    return (prefix + f"mp_{procs}x{dpp}" + EXCHANGE_SUFFIX[exchange]
            + ("_cyc" if partition == "word_cyclic" else ""))


def free_port() -> int:
    """An OS-assigned free localhost TCP port for the rendezvous."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def parent_digest(parent) -> str:
    """Bitwise fingerprint of a parent batch — the cross-process parity
    tests compare this against single-process runs without shipping the
    arrays."""
    import numpy as np

    a = np.ascontiguousarray(np.asarray(parent, dtype=np.int32))
    return hashlib.sha256(a.tobytes()).hexdigest()


def parse_inject(spec: Optional[str]):
    """``site/kind[/level[/persistent]]`` → :class:`FaultSpec` (or None)."""
    if not spec:
        return None
    from repro.core.faults import FaultSpec

    parts = spec.split("/")
    if len(parts) < 2:
        raise ValueError(f"--inject wants site/kind[/level[/persistent]], "
                         f"got {spec!r}")
    kw = dict(site=parts[0], kind=parts[1])
    if len(parts) > 2:
        kw["level"] = int(parts[2])
    if len(parts) > 3:
        kw["persistent"] = parts[3] == "persistent"
    return FaultSpec(**kw)


# ---------------------------------------------------------------------------
# Measured per-level exchange-leg timing
# ---------------------------------------------------------------------------

def time_exchange_per_level(compiled, level_row, *, reps: int = 3) -> dict:
    """Measured wall-clock of the per-level delta-exchange leg.

    The SPMD traversal runs its whole level loop inside one jitted call,
    so the exchange cost cannot be clocked in situ — but the completed
    ``level`` array recovers each level's delta bitmap exactly (the
    delta exchanged at loop step ``t`` is the set of vertices with
    ``level == t``).  This replays each level's REAL payload through the real
    exchange program (:func:`repro.core.hybrid_bfs._exchange_delta` in a
    ``shard_map`` over the compiled plan's mesh — cross-process wire
    under the multiprocess runtime) and reports min-over-``reps``
    seconds per level.  All ranks must call this in lockstep (the timed
    call is a collective).
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.hybrid_bfs import _exchange_delta, _shard_index

    sg = compiled.graph.sharded
    plan = compiled.plan
    if sg is None:
        raise ValueError("exchange timing needs a vertex-sharded plan "
                         "(no ShardedGraph on this CompiledBFS)")
    w_loc, n_dev = sg.w_loc, sg.n_devices
    w_pad = n_dev * w_loc
    mesh = compiled.mesh
    role = dict(zip(plan.layout, compiled._axis_names))
    group_axis, member_axis = role["group"], role["member"]

    def local(delta):
        dev = _shard_index(group_axis, member_axis)
        return _exchange_delta(
            delta[0], dev, w_loc, n_dev, exchange=plan.exchange,
            group_axis=group_axis, member_axis=member_axis,
            partition=plan.partition)

    va = (group_axis, member_axis)
    prog = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=P(va), out_specs=P(),
        check_vma=False))

    level_row = np.asarray(level_row).reshape(-1)

    def words_of(mask_verts):
        words = np.zeros(w_pad, np.uint32)
        np.bitwise_or.at(words, mask_verts // 32,
                         np.uint32(1) << (mask_verts % 32).astype(np.uint32))
        return words

    def shard_view(words):
        # owner map (DESIGN.md §9): block = contiguous w_loc words per
        # device; word_cyclic = global word j belongs to device j % P
        if plan.partition == "word_cyclic":
            return words.reshape(w_loc, n_dev).T.copy()
        return words.reshape(n_dev, w_loc)

    depth = int(level_row.max()) if level_row.size else 0
    per_level = []
    total = 0.0
    warm = None
    for t in range(1, depth + 1):
        verts = np.flatnonzero(level_row == t)
        delta = jnp.asarray(shard_view(words_of(verts)))
        if warm is None:
            jax.block_until_ready(prog(delta))   # compile once
            warm = True
        best = float("inf")
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            jax.block_until_ready(prog(delta))
            best = min(best, time.perf_counter() - t0)
        per_level.append({"level": t, "frontier": int(verts.size),
                          "seconds": best})
        total += best
    return {"exchange": plan.exchange, "partition": plan.partition,
            "reps": reps, "levels": depth, "total_seconds": total,
            "per_level": per_level}


# ---------------------------------------------------------------------------
# Worker
# ---------------------------------------------------------------------------

def _serialize_run(run) -> dict:
    """JSON-ready Graph500Run (inverse: :func:`_deserialize_run`)."""
    return {
        "teps": list(run.teps), "times_s": list(run.times_s),
        "edges": list(run.edges), "validated": list(run.validated),
        "batched": run.batched, "retries": run.retries,
        "fallbacks": run.fallbacks, "quarantined": list(run.quarantined),
        "check_counts": dict(run.check_counts),
        "check_failures": {str(k): v
                           for k, v in run.check_failures.items()},
    }


def _deserialize_run(d: dict):
    from repro.core.teps import Graph500Run

    run = Graph500Run(
        teps=list(d["teps"]), times_s=list(d["times_s"]),
        edges=list(d["edges"]), validated=list(d["validated"]),
        batched=d["batched"])
    run.retries = d["retries"]
    run.fallbacks = d["fallbacks"]
    run.quarantined = list(d["quarantined"])
    run.check_counts = dict(d["check_counts"])
    run.check_failures = {int(k): list(v)
                          for k, v in d["check_failures"].items()}
    return run


def _worker(args) -> int:
    # Test hook: a rank forced to die at bring-up, for the launcher's
    # no-orphans shutdown test (tests/test_multiprocess.py).
    crash = os.environ.get("REPRO_MP_CRASH_RANK")
    if crash is not None and int(crash) == args.rank:
        print(f"rank {args.rank}: crashing on purpose "
              f"(REPRO_MP_CRASH_RANK)", file=sys.stderr, flush=True)
        return 17

    import jax

    jax.distributed.initialize(coordinator_address=args.coordinator,
                               num_processes=args.procs,
                               process_id=args.rank)
    import numpy as np

    rank = args.rank
    dpp = args.devices_per_proc
    total = args.procs * dpp

    def log(msg):
        print(f"# rank {rank}: {msg}", file=sys.stderr, flush=True)

    if jax.local_device_count() != dpp or jax.device_count() != total:
        print(f"rank {rank}: device view "
              f"local={jax.local_device_count()} global="
              f"{jax.device_count()}, wanted {dpp}/{total} — workers must "
              f"be spawned via the launcher (respawn_with_host_devices "
              f"sets XLA_FLAGS)", file=sys.stderr, flush=True)
        return 2
    log(f"initialized: {jax.process_count()} processes x {dpp} devices "
        f"= {jax.device_count()} global")

    from repro.core.plan import TraversalPlan, compile_plan, mesh_process_count
    from repro.core.tune import _build_inputs
    from repro.kernels import ops as kops

    fault = parse_inject(args.inject)
    kernel = args.kernel
    pg, degree, roots, v = _build_inputs(args.scale, args.seed,
                                         args.edge_factor, args.roots,
                                         kernel=kernel)

    # In-process single-device oracle: runs on this rank's local device,
    # no mesh.  Every rank computes it (deterministic), every rank
    # asserts against it — the acceptance bar is bitwise.  For SSSP the
    # level plane carries distances, so parity covers both arrays.
    oracle = compile_plan(
        TraversalPlan(layout=(), batch_roots=True, kernel=kernel), pg)
    oracle_res = oracle.bfs(roots)
    oracle_parent = np.asarray(oracle_res.parent)[:, :v]
    oracle_level = np.asarray(oracle_res.level)[:, :v]
    log(f"single-device {kernel} oracle solved")

    shape = (args.procs, dpp)
    exchanges = [e.strip() for e in args.exchanges.split(",") if e.strip()]
    if kernel == "sssp":
        # the generic default wiring maps onto the kernel's min family
        exchanges = ["hier_min" if e == "hier_or" else e for e in exchanges]
    partitions = [p.strip() for p in args.partitions.split(",") if p.strip()]
    rungs: dict = {}
    all_identical = True
    for partition in partitions:
        for exchange in exchanges:
            name = rung_name(args.procs, dpp, exchange, partition, kernel)
            plan = TraversalPlan(layout=("group", "member"),
                                 mesh_shape=shape, exchange=exchange,
                                 partition=partition, kernel=kernel)
            compiled = compile_plan(plan, pg, fault=fault)
            assert mesh_process_count(compiled.mesh) == args.procs, \
                "mesh does not span the worker processes"
            result = compiled.run(roots, check=args.check,
                                  retries=args.retries,
                                  fallback=args.fallback)
            run = result.run
            identical = bool(
                np.array_equal(result.parent[:, :v], oracle_parent)
                and (kernel != "sssp"
                     or np.array_equal(result.level[:, :v], oracle_level)))
            all_identical &= identical
            if fault is None and not identical:
                raise AssertionError(
                    f"{name}: results diverge from the single-device "
                    f"oracle across the process boundary — parity "
                    f"regression (procs={args.procs} x {dpp} devices)")
            if fault is not None and not run.check_counts:
                raise AssertionError(
                    f"{name}: fault injected but no check ran — use "
                    f"--check post|full")
            # The exchange-leg replay reconstructs per-level BFS deltas
            # from the level array; SSSP rounds pop δ-buckets, not
            # levels, so it does not apply to that kernel.
            exch_s = (time_exchange_per_level(compiled, result.level[0],
                                              reps=args.reps)
                      if fault is None and kernel == "bfs" else None)
            rungs[name] = {
                "mesh": f"{args.procs}x{dpp}",
                "layer": "multiprocess",
                "kernel": kernel,
                "procs": args.procs,
                "devices_per_proc": dpp,
                "plan": plan.to_dict(),
                "wall_us": float(np.sum(run.times_s)) * 1e6,
                "per_root_us": float(np.mean(run.times_s)) * 1e6,
                "harmonic_mean_teps": run.harmonic_mean_teps,
                "n_roots": len(roots),
                "identical": identical,
                "parent_sha256": parent_digest(result.parent[:, :v]),
                "validated": run.all_valid,
                "check_counts": run.check_counts,
                "exchange_seconds": exch_s,
                "g500": _serialize_run(run),
            }
            it = (f"exch_s={exch_s['total_seconds']:.4f}" if exch_s
                  else f"check_counts={run.check_counts}")
            log(f"{name}: identical={identical} "
                f"hmean={run.harmonic_mean_teps:.3g} {it}")

    payload = {
        "procs": args.procs,
        "devices_per_proc": dpp,
        "kernel": kernel,
        "scale": args.scale,
        "seed": args.seed,
        "n_roots": len(roots),
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "interpret_mode": kops.interpret_mode(),
        "check": args.check,
        "inject": args.inject or None,
        "parents_bitwise_identical": all_identical,
        "oracle_sha256": parent_digest(oracle_parent),
        "rungs": rungs,
    }
    if rank == 0:
        print(_MARK + json.dumps(payload), flush=True)
    return 0


# ---------------------------------------------------------------------------
# Parent: spawn, babysit, collect
# ---------------------------------------------------------------------------

def _kill_all(workers) -> None:
    for p in workers:
        if p.poll() is None:
            p.terminate()
    deadline = time.time() + 5.0
    for p in workers:
        while p.poll() is None and time.time() < deadline:
            time.sleep(0.05)
        if p.poll() is None:
            p.kill()
    for p in workers:
        try:
            p.wait(timeout=5.0)
        except Exception:
            pass


def _log_tail(path: str, n: int = 2000) -> str:
    try:
        with open(path) as f:
            return f.read()[-n:]
    except OSError:
        return "<no log>"


def launch(procs: int, devices_per_proc: int, *, scale: int = 12,
           n_roots: int = 8, seed: int = 1, edge_factor: int = 16,
           exchanges: str = "hier_or", partitions: str = "block",
           check: str = "post", retries: int = 0, fallback: bool = False,
           inject: Optional[str] = None, reps: int = 3,
           log_dir: Optional[str] = None,
           timeout_s: float = 1800.0, kernel: str = "bfs") -> dict:
    """Spawn the worker gang, wait, and return rank 0's JSON payload.

    One log file and one pid file per rank land in ``log_dir`` (a fresh
    temp dir by default) — the CI multiprocess leg uploads them on
    failure so a hang is debuggable.  Any rank exiting nonzero, or the
    ``timeout_s`` deadline passing, kills every surviving rank
    (terminate, then kill) before raising — the launcher never leaves
    orphans behind.
    """
    log_dir = log_dir or tempfile.mkdtemp(prefix="repro_mp_")
    os.makedirs(log_dir, exist_ok=True)
    port = free_port()
    from repro.util import respawn_with_host_devices

    common = [
        sys.executable, "-m", "repro.launch.multiprocess", "--worker",
        "--coordinator", f"127.0.0.1:{port}",
        "--procs", str(procs), "--devices-per-proc", str(devices_per_proc),
        "--scale", str(scale), "--roots", str(n_roots),
        "--seed", str(seed), "--edge-factor", str(edge_factor),
        "--exchanges", exchanges, "--partitions", partitions,
        "--check", check, "--retries", str(retries), "--reps", str(reps),
        "--kernel", kernel,
    ]
    if fallback:
        common.append("--fallback")
    if inject:
        common += ["--inject", inject]

    workers, logs, log_files = [], [], []
    try:
        for rank in range(procs):
            log_path = os.path.join(log_dir, f"rank{rank}.log")
            lf = open(log_path, "w")
            p = respawn_with_host_devices(
                common + ["--rank", str(rank)], devices_per_proc,
                pythonpath=(_SRC_ROOT,), background=True,
                stdout=lf, stderr=lf)
            with open(os.path.join(log_dir, f"rank{rank}.pid"), "w") as f:
                f.write(str(p.pid))
            workers.append(p)
            logs.append(log_path)
            log_files.append(lf)

        deadline = time.time() + timeout_s
        while True:
            codes = [p.poll() for p in workers]
            bad = [(i, rc) for i, rc in enumerate(codes)
                   if rc is not None and rc != 0]
            if bad:
                _kill_all(workers)
                tails = "\n".join(f"--- rank {i} (exit {rc}) ---\n"
                                  f"{_log_tail(logs[i])}" for i, rc in bad)
                raise RuntimeError(
                    f"multiprocess worker(s) failed "
                    f"({procs}x{devices_per_proc}, logs in {log_dir}):\n"
                    f"{tails}")
            if all(rc == 0 for rc in codes):
                break
            if time.time() > deadline:
                alive = [i for i, rc in enumerate(codes) if rc is None]
                _kill_all(workers)
                raise RuntimeError(
                    f"multiprocess launch timed out after {timeout_s:.0f}s "
                    f"(ranks still running: {alive}; logs in {log_dir}):\n"
                    f"{_log_tail(logs[alive[0]] if alive else logs[0])}")
            time.sleep(0.2)
    finally:
        # belt and braces: whatever path exits this block, nothing we
        # spawned survives it
        _kill_all(workers)
        for lf in log_files:
            lf.close()

    payload = None
    with open(logs[0]) as f:
        for line in f:
            if line.startswith(_MARK):
                payload = json.loads(line[len(_MARK):])
    if payload is None:
        raise RuntimeError(f"rank 0 exited 0 but printed no payload "
                           f"marker (log: {logs[0]}):\n"
                           f"{_log_tail(logs[0])}")
    payload["log_dir"] = log_dir
    return payload


def run_config(cfg, built=None):
    """:class:`~repro.core.pipeline.Graph500Config` adapter: execute the
    config's traversal on ``cfg.procs`` real processes and return
    ``(built, Graph500Run)`` exactly like ``pipeline.run`` — the parent
    builds the graph for the caller, the workers rebuild it themselves
    (same seed, same bits) and return rank 0's bookkeeping.
    """
    from repro.core import pipeline

    built = built or pipeline.build(cfg)
    dpp = cfg.devices_per_proc or 1
    exchange = cfg.exchange
    if cfg.kernel == "sssp" and exchange == "hier_or":
        exchange = "hier_min"   # the kernel's default wiring (§16)
    payload = launch(
        cfg.procs, dpp, scale=cfg.scale, n_roots=cfg.n_roots,
        seed=cfg.seed, edge_factor=cfg.edge_factor,
        exchanges=exchange, partitions=cfg.partition,
        check=cfg.check, retries=cfg.retries, fallback=cfg.fallback,
        kernel=cfg.kernel)
    name = rung_name(cfg.procs, dpp, exchange, cfg.partition, cfg.kernel)
    return built, _deserialize_run(payload["rungs"][name]["g500"])


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        description="multi-process distributed BFS launcher (DESIGN.md §15)")
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--devices-per-proc", type=int, default=2)
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--roots", type=int, default=8)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--edge-factor", type=int, default=16)
    ap.add_argument("--kernel", default="bfs", choices=("bfs", "sssp"),
                    help="traversal kernel (DESIGN.md §16)")
    ap.add_argument("--exchanges", default="hier_or",
                    help="comma list of exchange wirings to run")
    ap.add_argument("--partitions", default="block",
                    help="comma list of vertex partitions to run")
    ap.add_argument("--check", default="post",
                    choices=("off", "post", "full"))
    ap.add_argument("--retries", type=int, default=0)
    ap.add_argument("--fallback", action="store_true")
    ap.add_argument("--inject", default=None,
                    help="FaultSpec site/kind[/level[/persistent]] "
                         "(DESIGN.md §13)")
    ap.add_argument("--reps", type=int, default=3,
                    help="min-over-reps for the exchange-leg timing")
    ap.add_argument("--log-dir", default=None,
                    help="per-rank log/pid directory (default: a temp dir)")
    ap.add_argument("--timeout", type=float, default=1800.0,
                    help="hard wall-clock deadline for the worker gang")
    # worker-only plumbing (set by the parent, not by hand)
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--rank", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.worker:
        return _worker(args)

    payload = launch(
        args.procs, args.devices_per_proc, scale=args.scale,
        n_roots=args.roots, seed=args.seed, edge_factor=args.edge_factor,
        exchanges=args.exchanges, partitions=args.partitions,
        check=args.check, retries=args.retries, fallback=args.fallback,
        inject=args.inject, reps=args.reps, log_dir=args.log_dir,
        timeout_s=args.timeout, kernel=args.kernel)
    for name, rung in payload["rungs"].items():
        exch = rung.get("exchange_seconds")
        extra = (f"exchange_total={exch['total_seconds']:.4f}s "
                 f"levels={exch['levels']}" if exch
                 else f"check_counts={rung['check_counts']}")
        print(f"# {name}: identical={rung['identical']} "
              f"hmean_TEPS={rung['harmonic_mean_teps']:.3g} "
              f"{extra}", file=sys.stderr)
    print(_MARK + json.dumps(payload), flush=True)
    if args.inject is None and not payload["parents_bitwise_identical"]:
        print("# FAIL: parents not bitwise-identical to the oracle",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
