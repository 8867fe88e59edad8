"""End-to-end Graph500 pipeline (steps 1-4) with the paper's option ladder.

The four rungs of Fig. 18, as config knobs:

  reference-3.0.0  : no sort, no core, reference engine
  TH-2             : degree sort (T2a), reference engine
  K                : degree sort + hybrid switch tuning
  Pre-G500         : degree sort + heavy core (T2b) + bitmap-resident
                     Pallas engine (T1) [+ monitor comm (T3) in the
                     distributed runner]

Extra rung beyond the paper's figure:

  pre-g500-batch   : the resident engine with all search keys vmapped
                     into ONE jitted program (``batched=True``).

Every rung is executed by constructing a :class:`repro.core.plan.TraversalPlan`
(:meth:`Graph500Config.to_plan`) and running it through
:func:`repro.core.plan.compile_plan` — the mesh rungs are just layouts:

  pre-g500-mesh    : ``layout=("root",)`` — roots split over all visible
                     devices (layer 1, zero comms);
  pre-g500-mesh3   : ``layout=("root", "group", "member")`` — the
                     composed 3-axis plan (root batch over its own mesh
                     axis outside the vertex-sharded SPMD program).

Graph500 kernel 3 is ``kernel="sssp"`` on the same build and plan path:
``build`` attaches the spec's weights, uniform over the multiples of
2**-20 in (0, 1) and drawn over the original generator ids
(``graph_build.edge_weights``), and the per-root δ-stepping engine
returns each vertex's distance as an integer count of
``graph_build.WEIGHT_UNIT``.  The grid is fine enough that a weight
behaves as a draw from [0, 1), and coarse enough that every distance is
an exact sum, so the shortest-path tree is exact and checkable.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

from repro.core import kronecker
from repro.core.bfs_steps import EdgeView, edge_view, with_edge_weights
from repro.core.graph_build import build_csr
from repro.core.heavy import HeavyCore, build_heavy_core
from repro.core.plan import TraversalPlan, compile_plan
from repro.core.reorder import Reordering, degree_reorder, relabel_edges
from repro.core.teps import Graph500Run


@dataclass(frozen=True)
class Graph500Config:
    scale: int = 12
    edge_factor: int = 16
    seed: int = 42
    n_roots: int = 8
    degree_sort: bool = True
    heavy_threshold: Optional[int] = 100   # None disables the dense core
    engine: str = "bitmap"                 # "reference" | "bitmap"
    alpha: float = 14.0
    beta: float = 24.0
    batched: bool = False                  # one jitted program for all roots
    # Mesh sharding (DESIGN.md §9): root_devices > 0 shard_maps the batch
    # over a ("root",) mesh of that many devices (layer 1, zero comms).
    # 0 means "all visible devices".
    root_devices: Optional[int] = None
    # Explicit plan layout/mesh (DESIGN.md §10) — overrides root_devices.
    # None derives them from root_devices; () forces single device.
    layout: Optional[tuple] = None
    mesh_shape: Optional[tuple] = None
    exchange: str = "hier_or"
    # Vertex-ownership map of the sharded engine (DESIGN.md §9):
    # "block" contiguous words, "word_cyclic" the paper's eq.-(3) cyclic
    # ownership at word granularity.  Only meaningful on vertex-sharded
    # layouts (a 'member' axis).
    partition: str = "block"
    # Auto-tuned plan (DESIGN.md §11): start from the TUNED_PLANS.json
    # winner for (scale, visible devices, backend).  An explicit
    # layout / mesh_shape / root_devices bypasses the table entirely;
    # non-default engine/exchange/alpha/beta knobs override those fields
    # on the tuned plan; with no matching entry the config falls back to
    # the untuned derivation.
    tuned: bool = False
    # Checked execution + recovery (DESIGN.md §13): the verification
    # mode ("off" | "post" | "full"), the per-root retry budget, and
    # whether still-failing roots re-run on the degraded single-device
    # fallback plan before quarantine.
    check: str = "post"
    retries: int = 0
    fallback: bool = False
    # Multi-process runtime (DESIGN.md §15): procs > 1 hands ``run`` to
    # ``repro.launch.multiprocess`` — one real JAX process per "node"
    # over localhost TCP, the group axis pinned to the process boundary,
    # so the inter-group exchange leg crosses real process wire.
    # ``devices_per_proc`` sizes each worker's forced-host-device view
    # (None → 1).  Only ``run`` honors these; ``serve`` stays
    # single-process.
    procs: int = 1
    devices_per_proc: Optional[int] = None
    # Graph500 kernel (DESIGN.md §16): "bfs" (kernel 2) or "sssp"
    # (kernel 3).  Under "sssp" the build step attaches the spec's weight
    # plane (seeded from cfg.seed, see ``build``) and the plan runs the
    # δ-stepping engine with the min-combine exchange family.
    kernel: str = "bfs"

    @staticmethod
    def ladder(rung: str, **kw) -> "Graph500Config":
        presets = {
            "reference-3.0.0": dict(degree_sort=False, heavy_threshold=None,
                                    engine="reference"),
            "th2": dict(degree_sort=True, heavy_threshold=None,
                        engine="reference"),
            "k": dict(degree_sort=True, heavy_threshold=None,
                      engine="reference", alpha=8.0, beta=64.0),
            "pre-g500": dict(degree_sort=True, heavy_threshold=100,
                             engine="bitmap"),
            "pre-g500-batch": dict(degree_sort=True, heavy_threshold=100,
                                   engine="bitmap", batched=True),
            # layer-1 mesh rung: all visible devices unless root_devices set
            "pre-g500-mesh": dict(degree_sort=True, heavy_threshold=100,
                                  engine="bitmap", batched=True,
                                  root_devices=0),
            # composed layer-1 x layer-2 rung: root batch over its own
            # mesh axis outside the vertex-sharded SPMD program; mesh
            # shape from plan_device_mesh unless mesh_shape is given.
            "pre-g500-mesh3": dict(degree_sort=True, heavy_threshold=100,
                                   engine="bitmap", batched=True,
                                   layout=("root", "group", "member")),
            # auto-tuned rung: the TUNED_PLANS.json winner for this
            # (scale, devices, backend), untuned pre-g500-batch when the
            # table has no matching entry.
            "pre-g500-tuned": dict(degree_sort=True, heavy_threshold=100,
                                   engine="bitmap", batched=True,
                                   tuned=True),
        }
        return Graph500Config(**{**presets[rung], **kw})

    def to_plan(self) -> TraversalPlan:
        """Lower the config knobs onto the declarative plan axes.

        With ``tuned=True`` the plan starts from the TUNED_PLANS.json
        winner: any explicit layout / mesh_shape / root_devices bypasses
        the table entirely, non-default engine/exchange/alpha/beta knobs
        replace those fields, and the table's ``batch_roots`` is kept
        (tuned winners are batched plans).
        """
        if (self.tuned and self.layout is None and self.mesh_shape is None
                and self.root_devices is None):
            from repro.core.tune import tuned_plan

            defaults = Graph500Config()
            overrides = {
                f: getattr(self, f)
                for f in ("engine", "exchange", "partition", "alpha", "beta")
                if getattr(self, f) != getattr(defaults, f)
            }
            base = tuned_plan(self.scale, overrides=overrides,
                              kernel=self.kernel)
            if base is not None:
                return base
        if self.layout is not None:
            layout, mesh_shape = tuple(self.layout), self.mesh_shape
        elif self.root_devices is not None:
            if not self.batched:
                raise ValueError(
                    "root_devices requires batched=True (the mesh shards "
                    "the batched harness's root vector)")
            layout = ("root",)
            mesh_shape = ((self.root_devices,)
                          if self.root_devices else None)
        else:
            layout, mesh_shape = (), None
        return TraversalPlan(
            engine=self.engine, layout=layout, mesh_shape=mesh_shape,
            exchange=self.exchange, partition=self.partition,
            alpha=self.alpha, beta=self.beta,
            batch_roots=self.batched, kernel=self.kernel,
        )


@dataclass
class BuiltGraph:
    ev: EdgeView
    degree: jnp.ndarray
    core: Optional[HeavyCore]
    reorder: Optional[Reordering]
    construction_s: float
    n_vertices: int
    nnz: int


def build(cfg: Graph500Config) -> BuiltGraph:
    """Steps 1-2 (untimed for TEPS, but we record construction time).
    Under ``kernel="sssp"`` the edge view carries kernel 3's weights."""
    t0 = time.perf_counter()
    edges = kronecker.generate_edges(cfg.seed, cfg.scale, cfg.edge_factor)
    g = build_csr(edges)
    reord = None
    if cfg.degree_sort:
        reord = degree_reorder(g.degree)
        edges = relabel_edges(edges, reord)
        g = build_csr(edges)
    core = None
    if cfg.heavy_threshold is not None:
        core = build_heavy_core(g, threshold=cfg.heavy_threshold)
    ev = edge_view(g)
    if cfg.kernel == "sssp":
        # Kernel 3's weights: uniform over the multiples of 2**-20 in
        # (0, 1), drawn over the *original* generator ids, so the problem
        # is the same whatever the relabelling; the engine's distances
        # come back in that unit (graph_build.WEIGHT_UNIT).
        ev = with_edge_weights(
            ev, seed=cfg.seed,
            old_from_new=None if reord is None else reord.old_from_new)
    ev.src.block_until_ready()
    return BuiltGraph(
        ev=ev, degree=g.degree, core=core, reorder=reord,
        construction_s=time.perf_counter() - t0,
        n_vertices=g.num_vertices, nnz=int(g.nnz),
    )


def run(cfg: Graph500Config, built: BuiltGraph | None = None) -> tuple[BuiltGraph, Graph500Run]:
    """Steps 3-4: compile the config's plan and run the timed harness.

    ``cfg.procs > 1`` delegates to the multi-process launcher: the
    traversal runs on ``procs`` real JAX processes (rank 0's
    :class:`Graph500Run` comes back through the launcher payload)
    instead of in this process's device view.
    """
    if cfg.procs > 1:
        from repro.launch.multiprocess import run_config

        return run_config(cfg, built)
    built = built or build(cfg)
    roots = search_keys(cfg, built)
    compiled = compile_plan(cfg.to_plan(), built)
    return built, compiled.run(roots, check=cfg.check, retries=cfg.retries,
                               fallback=cfg.fallback).run


def search_keys(cfg: Graph500Config, built: BuiltGraph) -> jnp.ndarray:
    """The config's ``n_roots`` Graph500 search keys, in ``built``'s
    (possibly degree-sorted) vertex ids."""
    edges = kronecker.generate_edges(cfg.seed, cfg.scale, cfg.edge_factor)
    roots = kronecker.sample_roots(cfg.seed, edges, cfg.n_roots)
    if built.reorder is not None:
        roots = built.reorder.new_from_old[roots]
    return roots


def serve(cfg: Graph500Config, serve_cfg=None,
          built: BuiltGraph | None = None, fault=None):
    """Stand up the persistent serving engine on this config's graph and
    plan (DESIGN.md §14): build once, compile once, returns
    ``(built, engine)`` — feed traces to ``engine.serve``.

    ``serve_cfg`` is a :class:`repro.serve.engine.ServeConfig` (defaults
    apply when None).  The traversal plan comes from :meth:`Graph500Config
    .to_plan` — so ``tuned=True`` resolves TUNED_PLANS.json exactly like
    the offline path — with ``batch_roots`` forced on by the engine.
    ``cfg.check``/``cfg.retries`` seed the serving-side defaults unless
    ``serve_cfg`` overrides them.
    """
    from repro.serve.engine import Engine, ServeConfig

    built = built or build(cfg)
    if serve_cfg is None:
        serve_cfg = ServeConfig(check=cfg.check, retries=cfg.retries)
    engine = Engine(built, plan=cfg.to_plan(), config=serve_cfg, fault=fault)
    return built, engine
