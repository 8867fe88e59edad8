"""Graph500 TEPS accounting (spec §Output) + the timed 64-root harnesses.

``m`` counts undirected input edges inside the traversed component —
computed as half the visited-degree sum over the *deduped* symmetric
structure (divergence from the reference, which counts multiplicity;
noted in DESIGN.md §8 — multiplicities are generator noise, not traversal
work).

Per the spec the headline figure is the **harmonic mean** TEPS across the
64 search keys.  :func:`run_graph500` times one jitted BFS per root
(closest to the reference code's search loop); a ``batch_roots`` plan's
``CompiledBFS.run`` times all roots under ONE jitted program, and the
per-search time is the batch wall-clock divided by the number of roots
(noted in DESIGN.md §8) — the harmonic-mean TEPS then measures exactly
what the list measures: total traversal throughput over the 64 searches.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bfs_steps import DEFAULT_CHUNKS, EdgeView
from repro.core.hybrid_bfs import BFSResult


def traversed_edges(degree: jax.Array, result: BFSResult) -> jax.Array:
    visited = result.parent >= 0
    return jnp.sum(jnp.where(visited, degree, 0)) // 2


def batch_harmonic_mean_teps(degree, parents, per_root_s: float) -> float:
    """Harmonic-mean TEPS of a ``[R, V]`` parent batch at a uniform
    per-root wall time (the fused-batch accounting of DESIGN.md §8) —
    the one copy shared by the plan tuner and the sharded benchmark
    ladder."""
    m = np.asarray(jax.vmap(
        lambda p: traversed_edges(
            degree, BFSResult(parent=p, level=None, stats=None))
    )(jnp.asarray(parents)))
    t = m / per_root_s
    t = t[t > 0]
    return float(len(t) / np.sum(1.0 / t)) if len(t) else 0.0


@dataclass
class Graph500Run:
    teps: list[float] = field(default_factory=list)
    times_s: list[float] = field(default_factory=list)
    edges: list[int] = field(default_factory=list)
    validated: list[bool] = field(default_factory=list)
    batched: bool = False   # True when produced by the one-jit batch harness
    # Checked-execution bookkeeping (DESIGN.md §13).  ``check_counts``
    # maps check name -> number of roots failing it at detection time
    # (zeros included when checks ran; empty when check="off");
    # ``check_failures`` maps failing root id -> failed check names.
    # ``retries`` / ``fallbacks`` count roots re-solved per recovery
    # stage; ``quarantined`` lists root ids still failing afterwards
    # (their TEPS is forced to 0.0, excluding them from the hmean).
    retries: int = 0
    fallbacks: int = 0
    quarantined: list[int] = field(default_factory=list)
    check_counts: dict[str, int] = field(default_factory=dict)
    check_failures: dict[int, list[str]] = field(default_factory=dict)

    @property
    def harmonic_mean_teps(self) -> float:
        t = np.asarray(self.teps)
        t = t[t > 0]
        return float(len(t) / np.sum(1.0 / t)) if len(t) else 0.0

    @property
    def mean_time_s(self) -> float:
        return float(np.mean(self.times_s)) if self.times_s else 0.0

    @property
    def all_valid(self) -> bool:
        return all(self.validated) if self.validated else False


def run_graph500(
    ev: EdgeView,
    degree: jax.Array,
    roots,
    *,
    core=None,
    engine: str = "reference",
    alpha: float = 14.0,
    beta: float = 24.0,
    do_validate: bool = True,
    warmup: bool = True,
    n_chunks: int = DEFAULT_CHUNKS,
) -> Graph500Run:
    """Timed BFS over the given roots (Graph500 step 3 + 4), one at a time.

    A per-root plan run: ``TraversalPlan(engine=engine, layout=(),
    batch_roots=False)`` — the chunked edge view is built once at compile
    time (graph construction is untimed per spec) and each search is
    timed separately, closest to the reference driver loop.
    """
    from repro.core.plan import PreparedGraph, TraversalPlan, compile_plan

    p = TraversalPlan(engine=engine, layout=(), batch_roots=False,
                alpha=alpha, beta=beta, n_chunks=n_chunks)
    compiled = compile_plan(
        p, PreparedGraph(ev=ev, degree=degree, core=core))
    run = compiled.run(roots, warmup=warmup, do_validate=do_validate).run
    if not do_validate:
        run.validated = [True] * len(run.teps)
    return run
