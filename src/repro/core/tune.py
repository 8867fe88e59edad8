"""Plan auto-tuner: sweep the ``compile_plan`` search space, persist
per-scale winners (DESIGN.md §11).

The paper tunes its hybrid-BFS knobs — direction-switch α/β, chunking,
monitor exchange wiring, mesh layout — by hand per machine scale
(§4.2/§4.3), and Buluç–Madduri (arXiv:1104.4518) show the winning
layout/partition flips with scale and machine shape.  PR 3's frozen
:class:`~repro.core.plan.BFSPlan` turned exactly those knobs into
orthogonal declarative axes, so tuning is a loop over
:func:`~repro.core.plan.compile_plan`:

  1. **enumerate** — :func:`enumerate_plans` builds the candidate set for
     the visible device count under a :class:`TuneBudget` (``small`` /
     ``medium`` / ``full``): layouts × mesh-shape factorizations ×
     exchange wirings × vertex partitions (``block`` / ``word_cyclic``,
     on vertex-sharded layouts) × an α/β grid × ``n_chunks``
     (10/160/696 candidates at 8 devices).
  2. **compile**  — each candidate goes through ``compile_plan``; invalid
     combinations (too few devices, planner non-pow2 member, …) raise
     the ValueErrors plan validation already defines and are recorded as
     *skipped*, never crashes.
  3. **accept**   — a candidate's parents must be bitwise-identical to
     the single-device bitmap engine on the shared Kronecker inputs
     before it is timed (the scatter-min parent convention makes the
     tree direction-invariant, so ONE oracle covers every α/β point);
     divergence marks the candidate *rejected*.
  4. **time**     — min-of-``reps`` wall clock of the batched traversal;
     the ranked :class:`TuneResult` table orders accepted candidates by
     per-root time (deterministic tie-break on the plan's JSON).
  5. **persist**  — :func:`save_tuned` merges the winner into a
     schema-versioned ``TUNED_PLANS.json`` keyed by
     ``(scale, n_devices, backend)``; :func:`tuned_plan` is the lookup
     that :class:`repro.core.pipeline.Graph500Config`,
     ``benchmarks/bfs_sharded.py`` and the examples consume (explicit
     plan fields always override the table, and a miss returns ``None``
     so callers keep their defaults).

CLI (the CI tune smoke)::

    PYTHONPATH=src python -m repro.core.tune --budget small --scale 12 \\
        --devices 8

``--devices N`` re-execs the sweep in a child process with
``--xla_force_host_platform_device_count=N`` so the caller's JAX process
keeps its own device view.  The run fails (exit 1) unless the winner
table is non-empty and the winner passed the bitwise-parity acceptance.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from repro.core.plan import BFSPlan, PreparedGraph, compile_plan

# v2: BFSPlan grew the `partition` axis (block vs word_cyclic vertex
# ownership of the sharded engine); v1 winners predate it and must be
# re-swept, not silently reinterpreted.
SCHEMA_VERSION = 2

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_TABLE = os.path.join(_REPO_ROOT, "TUNED_PLANS.json")


def table_path(path: Optional[str] = None) -> str:
    """Resolve the tuned-plan table path: explicit arg, then the
    ``REPRO_TUNED_PLANS`` env override, then ``TUNED_PLANS.json`` at the
    repo root."""
    return path or os.environ.get("REPRO_TUNED_PLANS") or DEFAULT_TABLE


# ---------------------------------------------------------------------------
# Budgets + search-space enumeration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TuneBudget:
    """How much of the plan space a sweep explores (and how carefully it
    times).  ``small`` is the CI smoke: canonical layouts only, default
    α/β, 2 reps.  ``full`` crosses every axis."""

    name: str
    exchanges: tuple = ("hier_or",)
    partitions: tuple = ("block", "word_cyclic")
    alpha_beta: tuple = ((14.0, 24.0),)
    n_chunks: tuple = (64,)
    all_factorizations: bool = False
    n_roots: int = 4
    reps: int = 2


BUDGETS = {
    # the CI smoke sweeps the wire-codec variants (DESIGN.md §12) next to
    # the raw hier_or so their bitwise-parity acceptance runs on every PR
    "small": TuneBudget(
        "small", exchanges=("hier_or", "hier_or_packed", "hier_or_sieve")),
    "medium": TuneBudget(
        "medium",
        exchanges=("hier_or", "hier_gather", "hier_or_packed",
                   "hier_or_sieve"),
        alpha_beta=((8.0, 64.0), (14.0, 24.0)), n_chunks=(16, 64),
        all_factorizations=True, n_roots=8, reps=2),
    "full": TuneBudget(
        "full",
        exchanges=("hier_or", "hier_gather", "flat", "hier_or_packed",
                   "hier_or_sieve"),
        alpha_beta=((8.0, 24.0), (8.0, 64.0), (14.0, 24.0), (14.0, 64.0)),
        n_chunks=(16, 64, 256), all_factorizations=True, n_roots=16, reps=3),
}


def _pow2s_upto(n: int) -> list:
    return [1 << i for i in range(n.bit_length()) if (1 << i) <= n]


def _layout_shapes(n_devices: int, budget: TuneBudget) -> list:
    """(layout, mesh_shape) candidates for ``n_devices`` visible devices.

    ``small`` keeps the canonical points: the single-device baseline, the
    root-parallel ladder over power-of-two device counts, the topology
    planner's (group, member) split, and the composed 3-axis shapes with
    a 2-way root split.  ``all_factorizations`` (medium/full) adds every
    factorization of the full device count onto each layout — including
    the invalid ones (non-pow2 member); the sweep records those as
    skipped rather than pre-filtering, so the ValueErrors validation
    raises are exercised, not duplicated here.
    """
    out = [((), None)]
    for r in _pow2s_upto(n_devices):
        if r > 1:
            out.append((("root",), (r,)))
    if n_devices > 1:
        from repro.comms.topology import plan_device_mesh
        planned = plan_device_mesh(n_devices)
        shapes = {planned}
        if budget.all_factorizations:
            shapes |= {(g, n_devices // g)
                       for g in range(1, n_devices + 1) if n_devices % g == 0}
        for g, m in sorted(shapes):
            if g * m > 1:
                out.append((("group", "member"), (g, m)))
        composed = set()
        for r in ([2] if not budget.all_factorizations
                  else [d for d in range(2, n_devices) if n_devices % d == 0]):
            rest = n_devices // r
            if rest < 2:
                continue
            groups = ({g for g in range(1, rest + 1) if rest % g == 0}
                      if budget.all_factorizations
                      else {plan_device_mesh(rest)[0], rest // 2 or 1})
            for g in groups:
                if rest % g == 0:
                    composed.add((r, g, rest // g))
        for shape in sorted(composed):
            out.append((("root", "group", "member"), shape))
    return out


def enumerate_plans(n_devices: int, budget: TuneBudget) -> list:
    """The declarative candidate set: layouts × exchange × partition ×
    α/β × n_chunks, deduplicated (exchange and partition only vary where
    a member axis exists — both are dead on single-device and
    root-parallel layouts, and a non-block partition there is a
    validation error)."""
    plans: dict = {}
    for (layout, shape) in _layout_shapes(n_devices, budget):
        vertexy = "member" in layout
        exchanges = budget.exchanges if vertexy else ("hier_or",)
        partitions = budget.partitions if vertexy else ("block",)
        for exchange, partition, (alpha, beta), n_chunks in itertools.product(
                exchanges, partitions, budget.alpha_beta, budget.n_chunks):
            p = BFSPlan(layout=layout, mesh_shape=shape, exchange=exchange,
                        partition=partition, alpha=alpha, beta=beta,
                        n_chunks=n_chunks, batch_roots=True)
            plans[p] = None
    return list(plans)


# ---------------------------------------------------------------------------
# Sweep
# ---------------------------------------------------------------------------

@dataclass
class TuneResult:
    """One candidate's outcome. ``status``: ``ok`` (accepted + timed),
    ``skipped`` (compile_plan ValueError), ``rejected`` (parents diverged
    from the single-device oracle — never ranked), ``failed`` (the
    measurement itself raised — recorded with the exception string so
    one crashing candidate never kills the sweep)."""

    plan: BFSPlan
    status: str
    reason: str = ""
    wall_s: float = math.inf
    per_root_us: float = math.inf
    harmonic_mean_teps: float = 0.0
    identical: Optional[bool] = None

    def to_dict(self) -> dict:
        d = {"plan": self.plan.to_dict(), "status": self.status}
        if self.status == "ok":
            d.update(per_root_us=self.per_root_us, wall_us=self.wall_s * 1e6,
                     harmonic_mean_teps=self.harmonic_mean_teps,
                     identical=self.identical)
        else:
            d["reason"] = self.reason
        return d


@dataclass
class TuneReport:
    """Ranked sweep output: ``results`` holds accepted candidates fastest
    first; ``skipped`` the invalid/rejected ones with their reasons."""

    scale: int
    n_devices: int
    backend: str
    interpret_mode: bool
    budget: str
    seed: int
    n_roots: int
    reps: int
    results: list = field(default_factory=list)
    skipped: list = field(default_factory=list)

    @property
    def winner(self) -> Optional[TuneResult]:
        return self.results[0] if self.results else None

    def table(self) -> str:
        """The ranked winner table, one row per candidate."""
        lines = [f"# tune scale={self.scale} devices={self.n_devices} "
                 f"backend={self.backend} budget={self.budget} "
                 f"roots={self.n_roots} reps={self.reps} "
                 f"interpret={self.interpret_mode}",
                 "rank,layout,mesh,exchange,partition,alpha,beta,n_chunks,"
                 "per_root_us,hmean_teps,rel_vs_best,identical"]
        best = self.results[0].per_root_us if self.results else None
        for i, r in enumerate(self.results):
            p = r.plan
            mesh = "x".join(map(str, p.mesh_shape)) if p.mesh_shape else "1"
            layout = "*".join(p.layout) if p.layout else "single"
            lines.append(
                f"{i + 1},{layout},{mesh},{p.exchange},{p.partition},"
                f"{p.alpha:g},{p.beta:g},{p.n_chunks},{r.per_root_us:.0f},"
                f"{r.harmonic_mean_teps:.3g},{r.per_root_us / best:.3f},"
                f"{r.identical}")
        for r in self.skipped:
            p = r.plan
            mesh = "x".join(map(str, p.mesh_shape)) if p.mesh_shape else "1"
            lines.append(f"-,{'*'.join(p.layout) or 'single'},{mesh},"
                         f"{p.exchange},{p.partition},,,,"
                         f"{r.status}:{r.reason[:60]},,,")
        return "\n".join(lines)


def _plan_sort_key(plan: BFSPlan) -> str:
    return json.dumps(plan.to_dict(), sort_keys=True)


def _build_inputs(scale: int, seed: int, edge_factor: int, n_roots: int,
                  kernel: str = "bfs"):
    """Shared Kronecker inputs: one degree-sorted graph + root sample
    reused by every candidate (and by the oracle).  Under
    ``kernel="sssp"`` the edge view carries kernel 3's weights, drawn
    over the original ids as ``pipeline.build`` draws them."""
    from repro.core.graph_build import build_csr
    from repro.core.heavy import build_heavy_core
    from repro.core.kronecker import generate_edges, sample_roots
    from repro.core.reorder import degree_reorder, relabel_edges
    from repro.core.bfs_steps import edge_view, with_edge_weights

    edges = generate_edges(seed, scale, edge_factor)
    g0 = build_csr(edges)
    r = degree_reorder(g0.degree)
    g = build_csr(relabel_edges(edges, r))
    core = build_heavy_core(g, threshold=100 if scale >= 13 else 8)
    ev = edge_view(g)
    if kernel == "sssp":
        ev = with_edge_weights(ev, seed=seed, old_from_new=r.old_from_new)
    roots = np.asarray(sample_roots(seed, edges, n_roots))
    roots = np.asarray(r.new_from_old)[roots].astype(np.int32)
    pg = PreparedGraph(ev=ev, degree=g.degree, core=core)
    return pg, g.degree, roots, g.num_vertices


def _default_measure(compiled, roots, reps: int) -> float:
    """min-of-``reps`` wall clock of the batched traversal (the compile +
    parity pass already warmed the executable)."""
    import jax

    wall = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled.bfs(roots).parent)
        wall = min(wall, time.perf_counter() - t0)
    return wall


def sweep(
    scale: int,
    *,
    budget="small",
    seed: int = 1,
    edge_factor: int = 16,
    n_roots: Optional[int] = None,
    reps: Optional[int] = None,
    plans: Optional[list] = None,
    measure: Optional[Callable] = None,
    log: Callable = lambda s: print(s, file=sys.stderr, flush=True),
) -> TuneReport:
    """Run the sweep on this process's visible devices and return the
    ranked report.

    ``plans`` overrides the enumerated candidate set; ``measure`` swaps
    the wall-clock timer for a deterministic cost model
    (``measure(compiled, roots, reps) -> seconds``) — the determinism
    tests inject one, and everything else (graph build, parity oracle,
    ranking, tie-breaks) is already seed-deterministic.
    """
    import jax
    from repro.core.teps import batch_harmonic_mean_teps
    from repro.kernels import ops as kops

    if isinstance(budget, str):
        budget = BUDGETS[budget]
    n_roots = budget.n_roots if n_roots is None else n_roots
    reps = budget.reps if reps is None else reps
    measure = measure or _default_measure
    n_devices = len(jax.devices())

    pg, degree, roots, v = _build_inputs(scale, seed, edge_factor, n_roots)
    if plans is None:
        plans = enumerate_plans(n_devices, budget)
    report = TuneReport(
        scale=scale, n_devices=n_devices, backend=jax.default_backend(),
        interpret_mode=kops.interpret_mode(), budget=budget.name, seed=seed,
        n_roots=n_roots, reps=reps)

    # The acceptance oracle: the single-device bitmap engine on the same
    # inputs.  One oracle covers every candidate because the scatter-min
    # parent convention makes the tree direction-invariant (DESIGN.md §3)
    # — α/β only move the switch level, never the winning parent.
    oracle = compile_plan(BFSPlan(layout=(), batch_roots=True), pg)
    oracle_parent = np.asarray(oracle.bfs(roots).parent)

    for plan in plans:
        key = _plan_sort_key(plan)
        try:
            compiled = compile_plan(plan, pg)
        except ValueError as e:
            report.skipped.append(TuneResult(plan, "skipped", reason=str(e)))
            log(f"# skip {key}: {e}")
            continue
        res = compiled.bfs(roots)           # parity pass doubles as warmup
        parent = np.asarray(res.parent)[:, :v]
        if not np.array_equal(parent, oracle_parent):
            report.skipped.append(TuneResult(
                plan, "rejected",
                reason="parents diverge from the single-device bitmap "
                       "engine — acceptance rule (DESIGN.md §11)"))
            log(f"# REJECT {key}: parents diverge")
            continue
        try:
            wall = measure(compiled, roots, reps)
        except Exception as e:   # a crashing candidate must not kill the sweep
            report.skipped.append(TuneResult(
                plan, "failed",
                reason=f"measurement raised {type(e).__name__}: {e}"))
            log(f"# FAIL {key}: {type(e).__name__}: {e}")
            continue
        per_root = wall / len(roots)
        hmean = batch_harmonic_mean_teps(degree, parent, per_root)
        report.results.append(TuneResult(
            plan, "ok", wall_s=wall, per_root_us=per_root * 1e6,
            harmonic_mean_teps=hmean, identical=True))
        log(f"# ok   {key}: per_root={per_root * 1e6:.0f}us")
    report.results.sort(
        key=lambda r: (r.per_root_us, _plan_sort_key(r.plan)))
    return report


# ---------------------------------------------------------------------------
# Persistence: TUNED_PLANS.json
# ---------------------------------------------------------------------------

def _entry_key(scale: int, n_devices: int, backend: str) -> str:
    return f"scale{scale}/dev{n_devices}/{backend}"


def load_table(path: Optional[str] = None) -> Optional[dict]:
    """Load the tuned-plan table, or None when the file doesn't exist.
    A schema_version other than :data:`SCHEMA_VERSION` is a ValueError —
    a future-format table must be re-tuned, not half-read."""
    path = table_path(path)
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        return None
    got = doc.get("schema_version")
    if got != SCHEMA_VERSION:
        hint = ("its plans predate the BFSPlan `partition` axis (v2) and "
                "the sweep must re-rank both partitions"
                if isinstance(got, int) and got < SCHEMA_VERSION
                else "it was written by a newer plan schema")
        raise ValueError(
            f"{path}: schema_version {got!r} != supported {SCHEMA_VERSION} — "
            f"{hint}; delete the file (or entry) and re-run "
            f"`python -m repro.core.tune --budget small --scale <N> "
            f"--devices <P>` to regenerate")
    return doc


def save_tuned(report: TuneReport, path: Optional[str] = None,
               top: int = 8) -> str:
    """Merge the report's winner into the versioned table (other keys'
    entries are preserved) and return the path written.  A
    foreign-schema table propagates ``load_table``'s ValueError rather
    than being clobbered — delete the file to regenerate deliberately."""
    if report.winner is None:
        raise ValueError("cannot persist a sweep with no accepted winner")
    path = table_path(path)
    doc = load_table(path)
    if doc is None:
        doc = {"schema_version": SCHEMA_VERSION, "entries": {}}
    key = _entry_key(report.scale, report.n_devices, report.backend)
    doc["entries"][key] = {
        "scale": report.scale,
        "n_devices": report.n_devices,
        "backend": report.backend,
        "interpret_mode": report.interpret_mode,
        "budget": report.budget,
        "seed": report.seed,
        "n_roots": report.n_roots,
        "reps": report.reps,
        "created_unix": int(time.time()),
        "plan": report.winner.plan.to_dict(),
        "per_root_us": report.winner.per_root_us,
        "harmonic_mean_teps": report.winner.harmonic_mean_teps,
        "identical": report.winner.identical,
        "ranked": [r.to_dict() for r in report.results[:top]],
        "n_skipped": len(report.skipped),
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def tuned_plan(
    scale: int,
    n_devices: Optional[int] = None,
    backend: Optional[str] = None,
    *,
    path: Optional[str] = None,
    overrides: Optional[dict] = None,
    kernel: Optional[str] = None,
) -> Optional[BFSPlan]:
    """Look up the persisted winner for ``(scale, n_devices, backend)``.

    ``n_devices``/``backend`` default to this process's JAX view.  Returns
    ``None`` when the table is missing or holds no matching entry —
    callers fall back to their own defaults.  ``overrides`` replaces
    explicit plan fields on top of the table entry (explicit always wins
    over tuned).  ``kernel`` retargets the winner at another kernel via
    :func:`repro.core.kernels.rekernel_plan` — committed tables predate
    the kernel axis, so ``from_dict`` default-fills ``kernel="bfs"`` and
    the tuned layout/partition carry over with the target kernel's
    exchange family."""
    doc = load_table(path)
    if doc is None:
        return None
    if n_devices is None or backend is None:
        import jax
        n_devices = len(jax.devices()) if n_devices is None else n_devices
        backend = jax.default_backend() if backend is None else backend
    entry = doc["entries"].get(_entry_key(scale, n_devices, backend))
    if entry is None:
        return None
    plan = BFSPlan.from_dict(entry["plan"])
    if overrides:
        plan = dataclasses.replace(plan, **overrides)
    if kernel is not None:
        from repro.core.kernels import rekernel_plan
        plan = rekernel_plan(plan, kernel)
    return plan


def tuned_exchange(scale: int, n_devices: Optional[int] = None,
                   backend: Optional[str] = None,
                   path: Optional[str] = None) -> tuple:
    """Best-effort exchange wiring for dry-run cost cells: an exact
    ``(scale, n_devices)`` entry if present (matching ``backend`` too
    when given — the dry-run cells model hypothetical machines, so they
    omit it), else the nearest-scale entry in the table (the 256/512-chip
    dry-run meshes are never tuned directly), else the ``hier_or``
    default.  Returns ``(exchange, source_tag)``."""
    try:
        doc = load_table(path)
    except ValueError:
        doc = None
    if doc is None or not doc.get("entries"):
        return "hier_or", "default"
    entries = sorted(doc["entries"].items())
    if n_devices is not None:
        exact = [(k, e) for k, e in entries
                 if e["scale"] == scale and e["n_devices"] == n_devices
                 and (backend is None or e["backend"] == backend)]
        if exact:
            key, entry = exact[0]
            return entry["plan"].get("exchange", "hier_or"), f"tuned:{key}"
    key, entry = min(entries, key=lambda kv: (abs(kv[1]["scale"] - scale),
                                              kv[1]["scale"], kv[0]))
    return (entry["plan"].get("exchange", "hier_or"),
            f"tuned:nearest_scale{entry['scale']}")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _respawn_with_devices(n: int, args) -> int:
    """Re-exec the sweep in a child with ``n`` forced host devices (the
    parent's JAX is already initialized with its own device view)."""
    from repro.util import respawn_with_host_devices

    child = [sys.executable, "-m", "repro.core.tune",
             "--scale", str(args.scale), "--budget", args.budget,
             "--seed", str(args.seed)]
    for flag, val in (("--roots", args.roots), ("--reps", args.reps),
                      ("--out", args.out)):
        if val is not None:
            child += [flag, str(val)]
    if args.no_save:
        child.append("--no-save")
    return respawn_with_host_devices(child, n).returncode


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        description="BFSPlan auto-tuner (DESIGN.md §11)")
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--budget", choices=sorted(BUDGETS), default="small")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--roots", type=int, default=None,
                    help="override the budget's root-sample size")
    ap.add_argument("--reps", type=int, default=None,
                    help="override the budget's min-of-k rep count")
    ap.add_argument("--devices", type=int, default=None,
                    help="re-exec with this many forced host devices")
    ap.add_argument("--out", default=None,
                    help=f"table to update (default {DEFAULT_TABLE}, "
                         f"REPRO_TUNED_PLANS overrides)")
    ap.add_argument("--no-save", action="store_true",
                    help="print the ranked table without persisting")
    args = ap.parse_args(argv)

    import jax
    if args.devices is not None and args.devices != len(jax.devices()):
        return _respawn_with_devices(args.devices, args)

    report = sweep(args.scale, budget=args.budget, seed=args.seed,
                   n_roots=args.roots, reps=args.reps)
    print(report.table(), flush=True)
    if report.winner is None:
        print("# FAIL: no candidate was accepted (empty winner table)",
              file=sys.stderr)
        return 1
    if not report.winner.identical:
        print("# FAIL: winner is not bitwise-identical to the "
              "single-device engine", file=sys.stderr)
        return 1
    if not args.no_save:
        path = save_tuned(report, args.out)
        print(f"# wrote {path} "
              f"[{_entry_key(report.scale, report.n_devices, report.backend)}]",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
