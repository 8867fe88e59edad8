"""Declarative spec→plan→runner API for the Graph500 engines (DESIGN.md §10).

The paper's pipeline is ONE configurable system — hybrid
direction-optimizing BFS (T1/T2), degree-sorted heavy-vertex handling,
group-based monitor communication (T3) — and Buluç–Madduri
(arXiv:1104.4518) shows the partitionings are points in one design space
selected per run.  This module makes that the API:

  1. **spec** — :class:`TraversalPlan`, a frozen dataclass naming the
     *kernel* (``"bfs"`` / ``"sssp"`` — the traversal-lifecycle contract
     of DESIGN.md §16 and ``core.kernels``), the engine, the mesh *layout*
     (which of the three axes ``root`` / ``group`` / ``member`` exist
     and their sizes), the delta-exchange strategy, the direction-switch
     α/β and the chunking knobs.  Kernel, sharding layout, exchange
     wiring and root batching are orthogonal declarative axes — not
     separate entry points.
  2. **plan** — :func:`compile_plan` validates the spec against the
     available devices and :func:`repro.comms.topology.plan_device_mesh`,
     builds (or checks) the device mesh, prepares the graph inputs
     (chunked edge view / dst-owned shard partition) and closes over ONE
     jitted / ``shard_map``'d callable.  Every invalid combination is a
     ``ValueError`` here, never a shard_map trace error.
  3. **runner** — :meth:`CompiledBFS.run` executes the Graph500 timed
     harness (warmup outside the timed region, spec validation per root,
     harmonic-mean TEPS) and returns a uniform :class:`Graph500Result`
     whatever the layout.

Layouts (all bitwise-locked to the single-device bitmap engine):

  ``()``                          one device; ``batch_roots`` selects the
                                  fused 64-root program vs per-root runs.
  ``("root",)``                   layer 1 — roots split over a 1-D mesh,
                                  graph replicated, zero communication.
  ``("group", "member")``         layer 2 — one traversal vertex-sharded
                                  over the monitor-group mesh, per-level
                                  delta bitmaps OR-combined via the T3
                                  two-phase collective.
  ``("root", "group", "member")`` layer 1 × layer 2 composed: the root
                                  vector splits over its own mesh axis
                                  OUTSIDE the vertex-sharded SPMD program
                                  — each root-slice of devices runs the
                                  full layer-2 traversal for its roots.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.bfs_steps import (
    DEFAULT_CHUNKS,
    ChunkedEdgeView,
    EdgeView,
    chunk_edge_view,
)
from repro.core.distributed_bfs import (
    PARTITIONS,
    ShardedGraph,
    partition_permutation,
    shard_graph,
)
from repro.core.heavy import HeavyCore
from repro.core.hybrid_bfs import (
    ENGINES,
    MAX_LEVELS,
    SHARD_EXCHANGES,
    BFSResult,
    _axis_names_tuple as _axis_tuple,
    _run_batch,
    _run_bitmap,
    _run_bitmap_impl,
    _run_bitmap_sharded,
    _run_reference,
)
from repro.core.hybrid_bfs import SENTINEL_OK
from repro.core.kernels import kernel_spec, validate_result_batch
from repro.core.sssp_steps import (
    _run_sssp,
    _run_sssp_batch,
    _run_sssp_impl,
    _run_sssp_sharded,
    bucket_width,
    sssp_max_rounds,
)
from repro.core.teps import Graph500Run, traversed_edges
from repro.core.validate import failure_report
from repro.kernels import ops as kops
from repro.util import make_mesh

VALID_LAYOUTS = (
    (),
    ("root",),
    ("group", "member"),
    ("root", "group", "member"),
)


# ---------------------------------------------------------------------------
# 1. Spec
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TraversalPlan:
    """Frozen declarative spec of one Graph500 traversal execution.

    Field → paper-technique mapping (full table in DESIGN.md §10):

      ``kernel``      which Graph500 kernel runs under the plan:
                      ``"bfs"`` (default) or ``"sssp"`` (δ-stepping over
                      seeded uniform weights — DESIGN.md §16).  The
                      kernel picks the state carrier / relax rule /
                      exchange combine / validation contract from
                      ``core.kernels``; every other axis is shared.
      ``engine``      Fig. 18 ladder rung (reference / bitmap-T1)
      ``layout``      which mesh axes exist — §4.2 partitioning choice
      ``mesh_shape``  per-axis sizes; ``None`` infers from the visible
                      devices (the (group, member) split comes from the
                      eq.-5 interconnect model via ``plan_device_mesh``)
      ``exchange``    §4.3 monitor wiring of the per-level delta combine:
                      ``hier_or`` / ``hier_gather`` / ``flat``
      ``partition``   vertex-ownership map of the sharded engine:
                      ``block`` (contiguous word blocks) vs
                      ``word_cyclic`` (eq. (3) cyclic ownership at
                      uint32-word granularity — load-balances the
                      degree-sorted heavy prefix)
      ``alpha/beta``  eq. (1)/(2) direction-switch thresholds
      ``max_levels``  traversal bound (static loop trip limit)
      ``n_chunks``    frontier-proportional top-down granularity (§3)
      ``batch_roots`` all search keys in ONE program (vmap) vs one
                      program per root
    """

    engine: str = "bitmap"
    layout: tuple = ()
    mesh_shape: Optional[tuple] = None
    exchange: str = "hier_or"
    partition: str = "block"
    alpha: float = 14.0
    beta: float = 24.0
    max_levels: int = MAX_LEVELS
    n_chunks: int = DEFAULT_CHUNKS
    batch_roots: bool = True
    kernel: str = "bfs"     # LAST field: positional constructions predate it

    def __post_init__(self):
        object.__setattr__(self, "layout", tuple(self.layout))
        if self.mesh_shape is not None:
            object.__setattr__(
                self, "mesh_shape", tuple(int(s) for s in self.mesh_shape))
        # The generic default exchange is the OR-family one; a plan that
        # kept it while selecting the min-combine kernel means "the
        # default wiring for this kernel" — normalize rather than error
        # (explicit OR-family variants still fail in validate_plan).
        if self.kernel == "sssp" and self.exchange == "hier_or":
            object.__setattr__(self, "exchange", "hier_min")

    def to_dict(self) -> dict:
        """JSON-ready dict (recorded in BENCH_bfs.json rung metadata)."""
        d = dataclasses.asdict(self)
        d["layout"] = list(self.layout)
        d["mesh_shape"] = (list(self.mesh_shape)
                           if self.mesh_shape is not None else None)
        return d

    @staticmethod
    def from_dict(d: dict) -> "TraversalPlan":
        """Inverse of :meth:`to_dict` (TUNED_PLANS.json / BENCH_bfs.json
        rung metadata back to a spec).  Unknown keys are rejected so a
        table written by a future plan schema fails loudly; missing keys
        default-fill, so pre-§16 tables (no ``kernel`` field) load as
        BFS plans unchanged."""
        fields = {f.name for f in dataclasses.fields(TraversalPlan)}
        unknown = set(d) - fields
        if unknown:
            raise ValueError(f"unknown TraversalPlan fields "
                             f"{sorted(unknown)}; "
                             f"expected a subset of {sorted(fields)}")
        return TraversalPlan(**d)


@dataclass
class PreparedGraph:
    """Graph-side inputs for :func:`compile_plan`.

    ``compile_plan`` accepts either this or any object exposing the same
    attributes (``pipeline.BuiltGraph`` qualifies).  Missing derived
    structures are built on demand: the chunked edge view for
    single-device / root-parallel layouts, the dst-owned shard partition
    (:func:`repro.core.distributed_bfs.shard_graph`) for vertex-sharded
    layouts.
    """

    ev: Optional[EdgeView] = None
    degree: Optional[jax.Array] = None
    core: Optional[HeavyCore] = None
    chunks: Optional[ChunkedEdgeView] = None
    sharded: Optional[ShardedGraph] = None


class ShardedRun(NamedTuple):
    """Raw vertex-sharded output: padded global parent/level (+ levels)."""

    parent: jax.Array   # [..., V_pad] int32, -1 unvisited
    level: jax.Array    # [..., V_pad] int32
    levels: jax.Array   # per-root levels run
    sentinel: Any = None  # [..., max_levels] int32 in-loop sentinel masks


class ServeBatch(NamedTuple):
    """Checked, untimed solve of one root batch for the serving engine
    (DESIGN.md §14): global-order stripped numpy rows plus the detection
    report.  No TEPS / wall-clock bookkeeping — the server owns the
    clock; ``failures`` maps batch-row index → failed check names for
    the rows still failing after any retry/fallback recovery."""

    parent: np.ndarray          # [B, V] int32
    level: np.ndarray           # [B, V] int32
    counts: dict                # check name -> failing rows at detection
    failures: dict              # row index -> failed check names (final)


@dataclass
class Graph500Result:
    """Uniform runner output, whatever the plan layout.

    ``parent``/``level`` are in global vertex order with any shard
    padding stripped; ``run`` carries the Graph500 timing/validation
    bookkeeping (harmonic-mean TEPS per the spec §Output).
    """

    parent: np.ndarray          # [R, V] int32
    level: np.ndarray           # [R, V] int32 (SSSP: the distance plane)
    run: Graph500Run
    plan: TraversalPlan
    mesh_axes: Optional[dict]   # {axis: size} of the resolved mesh


# ---------------------------------------------------------------------------
# 2. Validation + mesh resolution
# ---------------------------------------------------------------------------

def _flat_names(names) -> tuple:
    out: list = []
    for n in names:
        out.extend(_axis_tuple(n))
    return tuple(out)


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def validate_plan(plan: TraversalPlan) -> None:
    """Field-level checks (no devices touched) — all errors are ValueError.

    Kernel-generic: the engine and exchange vocabularies come from the
    plan's :func:`repro.core.kernels.kernel_spec` row, so e.g. an
    OR-family exchange under the SSSP kernel fails here, not in a
    shard_map trace.
    """
    spec = kernel_spec(plan.kernel)     # rejects unknown kernels
    if plan.engine not in spec.engines:
        raise ValueError(
            f"unknown engine {plan.engine!r} for kernel {plan.kernel!r}; "
            f"expected one of {spec.engines}")
    if plan.layout not in VALID_LAYOUTS:
        raise ValueError(
            f"unknown layout {plan.layout!r}; expected one of {VALID_LAYOUTS}")
    if plan.exchange not in spec.shard_exchanges:
        raise ValueError(
            f"unknown exchange {plan.exchange!r} for kernel "
            f"{plan.kernel!r}; expected one of {spec.shard_exchanges}")
    if plan.partition not in PARTITIONS:
        raise ValueError(
            f"unknown partition {plan.partition!r}; expected one of "
            f"{PARTITIONS}")
    if plan.partition != "block" and "member" not in plan.layout:
        raise ValueError(
            f"partition={plan.partition!r} requires a vertex-sharded "
            f"layout (a 'member' axis); layout {plan.layout} has no "
            f"vertex ownership to partition")
    if plan.layout and plan.engine != "bitmap":
        raise ValueError(
            f"mesh layout {plan.layout} requires engine='bitmap' "
            f"(got {plan.engine!r}); the reference engine is single-device")
    if "root" in plan.layout and not plan.batch_roots:
        raise ValueError(
            "layout with a 'root' axis requires batch_roots=True "
            "(the mesh shards the batched root vector)")
    if plan.batch_roots and plan.engine != "bitmap":
        raise ValueError(
            f"batch_roots=True requires engine='bitmap' (got "
            f"{plan.engine!r}); use batch_roots=False for per-root runs")
    if plan.mesh_shape is not None:
        if not plan.layout:
            raise ValueError("mesh_shape given but layout is () "
                             "(single device has no mesh)")
        if len(plan.mesh_shape) != len(plan.layout):
            raise ValueError(
                f"mesh_shape {plan.mesh_shape} does not match layout "
                f"{plan.layout} (need one size per axis)")
        if any(s < 1 for s in plan.mesh_shape):
            raise ValueError(f"mesh_shape sizes must be >= 1, got "
                             f"{plan.mesh_shape}")
        if "member" in plan.layout:
            m = plan.mesh_shape[plan.layout.index("member")]
            if not _is_pow2(m):
                raise ValueError(
                    f"member axis size {m} is not a power of two; the "
                    f"plan API requires pow2 members so the two-phase "
                    f"monitor collectives halve cleanly (pass a prebuilt "
                    f"mesh= to opt out)")
    if plan.n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {plan.n_chunks}")


def _resolve_mesh(plan: TraversalPlan, mesh, axis_names):
    """Return (mesh, names) for the plan — names[i] is the concrete mesh
    axis (str, or tuple of axes for a factored role) playing layout role
    ``plan.layout[i]``.

    With ``mesh=None`` the mesh is built over the visible devices: the
    ``("root",)`` layout takes them all, vertex layouts take the
    (group, member) split from the interconnect model
    (:func:`repro.comms.topology.plan_device_mesh` — member sized to the
    router group), and the composed 3-axis layout defaults to one root
    lane over the planned vertex mesh.  Infeasible shapes (too few
    devices, planner-derived non-power-of-two member) raise ValueError
    here, before any tracing.
    """
    if not plan.layout:
        if mesh is not None:
            raise ValueError("plan layout is () (single device) but a mesh "
                             "was passed")
        return None, ()
    names = tuple(axis_names) if axis_names is not None else plan.layout
    if len(names) != len(plan.layout):
        raise ValueError(f"axis_names {names} does not match layout "
                         f"{plan.layout}")
    if mesh is None and names != plan.layout:
        raise ValueError(
            f"axis_names {names} requires a prebuilt mesh= — a mesh built "
            f"by compile_plan uses the layout role names {plan.layout}")

    if mesh is not None:
        flat = _flat_names(names)
        if tuple(mesh.axis_names) != flat:
            raise ValueError(
                f"mesh axes {tuple(mesh.axis_names)} do not cover the plan "
                f"layout axes {flat}")
        if plan.mesh_shape is not None:
            sizes = tuple(
                math.prod(mesh.shape[a] for a in _axis_tuple(n))
                for n in names)
            if sizes != plan.mesh_shape:
                raise ValueError(
                    f"mesh sizes {sizes} do not match plan.mesh_shape "
                    f"{plan.mesh_shape}")
        return mesh, names

    n_avail = len(jax.devices())
    shape = plan.mesh_shape
    if shape is None:
        from repro.comms.topology import plan_device_mesh
        n_procs = jax.process_count()
        if plan.layout == ("root",):
            shape = (n_avail,)
        elif n_procs > 1:
            # Process-mesh resolution (DESIGN.md §15): under a
            # multi-process runtime the group axis is aligned to the
            # process boundary — each "node" (process) is one monitor
            # group, its local devices the members — so the inter-group
            # leg of the two-phase collectives is exactly the
            # cross-process (real-wire) leg.  jax.devices() orders
            # devices process-major, so the plain reshape realizes it.
            vshape = (n_procs, n_avail // n_procs)
            shape = (vshape if plan.layout == ("group", "member")
                     else (1,) + vshape)
        elif plan.layout == ("group", "member"):
            shape = plan_device_mesh(n_avail)
        else:  # composed 3-axis: one root lane over the planned vertex mesh
            shape = (1,) + plan_device_mesh(n_avail)
        if "member" in plan.layout:
            m = shape[plan.layout.index("member")]
            if not _is_pow2(m):
                raise ValueError(
                    f"plan_device_mesh({n_avail}) yields a member axis of "
                    f"{m} (not a power of two); pass an explicit "
                    f"mesh_shape for this device count")
    need = math.prod(shape)
    if need > n_avail:
        raise ValueError(
            f"plan layout {plan.layout} with mesh shape {shape} needs "
            f"{need} devices, have {n_avail} — force host devices via "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} or "
            f"shrink mesh_shape")
    return make_mesh(shape, plan.layout), names


def _role_size(mesh, name) -> int:
    return math.prod(int(mesh.shape[a]) for a in _axis_tuple(name))


def mesh_process_count(mesh) -> int:
    """Number of distinct JAX processes owning the mesh's devices (1 for
    any single-process mesh, whatever the fake-device count)."""
    if mesh is None:
        return 1
    return len({getattr(d, "process_index", 0)
                for d in np.asarray(mesh.devices).flat})


def _prepare(built, plan: TraversalPlan, n_dev_vertex: int) -> PreparedGraph:
    if isinstance(built, PreparedGraph):
        pg = dataclasses.replace(built)
    else:
        pg = PreparedGraph(
            ev=getattr(built, "ev", None),
            degree=getattr(built, "degree", None),
            core=getattr(built, "core", None),
            chunks=getattr(built, "chunks", None),
            sharded=getattr(built, "sharded", None),
        )
    needs_w = kernel_spec(plan.kernel).needs_weights
    if needs_w and pg.ev is not None and pg.ev.weight is None:
        raise ValueError(
            f"kernel={plan.kernel!r} needs edge weights — attach them "
            f"with with_edge_weights(ev) before compiling the plan")
    if "member" in plan.layout:
        if pg.sharded is None:
            if pg.ev is None:
                raise ValueError(
                    "vertex-sharded plan needs built.ev (an EdgeView) or a "
                    "pre-built ShardedGraph (built.sharded)")
            pg.sharded = shard_graph(
                np.asarray(pg.ev.src), np.asarray(pg.ev.dst),
                np.asarray(pg.ev.valid), pg.ev.num_vertices,
                n_dev_vertex, plan.n_chunks, partition=plan.partition,
                weight=(np.asarray(pg.ev.weight) if needs_w else None))
        elif pg.sharded.n_devices != n_dev_vertex:
            raise ValueError(
                f"ShardedGraph was partitioned for "
                f"{pg.sharded.n_devices} devices but the plan mesh has "
                f"{n_dev_vertex} (group x member)")
        elif pg.sharded.partition != plan.partition:
            raise ValueError(
                f"ShardedGraph was partitioned with "
                f"partition={pg.sharded.partition!r} but the plan says "
                f"{plan.partition!r} — re-run shard_graph (the owner map "
                f"is baked into the edge split)")
        if needs_w and pg.sharded.weight is None:
            raise ValueError(
                f"kernel={plan.kernel!r} needs a weighted ShardedGraph — "
                f"pass weight= to shard_graph (or let compile_plan shard "
                f"a weighted EdgeView)")
    else:
        if pg.ev is None:
            raise ValueError("plan needs built.ev (an EdgeView)")
        if pg.degree is None:
            raise ValueError("plan needs built.degree")
        if plan.engine == "bitmap" and (
                pg.chunks is None
                or (needs_w and pg.chunks.weight is None)):
            pg.chunks = chunk_edge_view(pg.ev, plan.n_chunks)
    return pg


# ---------------------------------------------------------------------------
# 3. Programs — the ONE copy of each shard_map wiring, cached per
#    (mesh, statics) so repeated compiles reuse the jitted executable.
# ---------------------------------------------------------------------------

_MESH_FN_CACHE: dict = {}


def _root_parallel_fn(mesh, root_axis, alpha, beta, use_core, max_levels,
                      use_pallas_core, fault=None, *, kernel="bfs",
                      delta=1, max_rounds=0):
    """Jitted layer-1 program: roots split over ``root_axis``, graph
    replicated, zero communication.  Kernel-generic — the local body is
    the kernel's single-device engine vmapped over the root slice."""
    key = ("root", mesh, root_axis, alpha, beta, use_core, max_levels,
           use_pallas_core, fault, kernel, delta, max_rounds)
    fn = _MESH_FN_CACHE.get(key)
    if fn is not None:
        return fn

    if kernel == "sssp":
        def local(chunks, degree, n_active, roots, core):
            return jax.vmap(
                lambda r: _run_sssp_impl(
                    chunks, degree, r, delta=delta, max_rounds=max_rounds,
                    fault=fault)
            )(roots)
    else:
        def local(chunks, degree, n_active, roots, core):
            return jax.vmap(
                lambda r: _run_bitmap_impl(
                    chunks, degree, n_active, r, core,
                    alpha=alpha, beta=beta, use_core=use_core,
                    max_levels=max_levels, use_pallas_core=use_pallas_core,
                    fault=fault)
            )(roots)

    fn = jax.jit(jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(root_axis), P()),
        out_specs=P(root_axis),
        check_vma=False,
    ))
    _MESH_FN_CACHE[key] = fn
    return fn


def vertex_sharded_program(
    mesh,
    *,
    w_loc: int,
    n_dev: int,
    group_axis="group",
    member_axis: str = "member",
    root_axis: Optional[str] = None,
    exchange: str = "hier_or",
    partition: str = "block",
    alpha: float = 14.0,
    beta: float = 24.0,
    use_core: bool = False,
    max_levels: int = MAX_LEVELS,
    use_pallas_core: bool = False,
    batched: bool = False,
    fault=None,
    kernel: str = "bfs",
    delta: int = 1,
    max_rounds: int = 0,
):
    """Build the UNJITTED shard_map'd vertex-sharded traversal program.

    The one copy of the layer-2 (and composed layer-1×2) shard_map
    wiring: :func:`compile_plan` jits it for execution and
    ``launch/input_specs.graph500_cell`` lowers it shape-only for the
    256/512-chip dry-run cost cells.  ``group_axis`` may be a *tuple* of
    mesh axes (the dry-run's ``("pod", "data")`` group).  With
    ``root_axis`` set, the roots vector splits over that axis OUTSIDE
    this SPMD program — the composed ``("root", "group", "member")``
    layout — and the body vmaps its local root slice.  ``fault`` is a
    static :class:`repro.core.faults.FaultSpec` baked into the engine's
    injection hooks (DESIGN.md §13); ``None`` compiles the clean program.

    Signature of the returned function::

        f(roots, src, dst_local, valid, src_lo, src_hi, degree_local,
          n_active[, core]) -> (parent, level, levels, sentinel)

    (``core`` is an argument only when ``use_core``; ``sentinel`` is the
    per-level in-loop check-mask trace of ``BFSStats.sentinel``.)

    Under ``kernel="sssp"`` the edge ``weight`` plane joins the sharded
    inputs (after ``src_hi``) and the heavy core never applies::

        f(roots, src, dst_local, valid, src_lo, src_hi, weight,
          degree_local, n_active) -> (parent, dist, rounds, sentinel)
    """
    va = _flat_names((group_axis, member_axis))
    vmapped = batched or root_axis is not None

    if kernel == "sssp":
        if use_core:
            raise ValueError("the SSSP kernel has no heavy-core step "
                             "(boolean-semiring SpMV carries no weights)")
        run_one = functools.partial(
            _run_sssp_sharded,
            delta=delta, max_rounds=max_rounds, w_loc=w_loc, n_dev=n_dev,
            group_axis=group_axis, member_axis=member_axis,
            exchange=exchange, partition=partition, fault=fault,
        )

        def local(roots, src, dst_local, valid, src_lo, src_hi, weight,
                  degree_local, n_active):
            args = (src[0], dst_local[0], valid[0], weight[0],
                    degree_local[0])
            if vmapped:
                res = jax.vmap(lambda r: run_one(*args, r))(roots)
            else:
                res = run_one(*args, roots)
            return (res.parent, res.level, res.stats.levels,
                    res.stats.sentinel)

        n_sharded = 7
    else:
        run_one = functools.partial(
            _run_bitmap_sharded,
            alpha=alpha, beta=beta, use_core=use_core,
            max_levels=max_levels, use_pallas_core=use_pallas_core,
            w_loc=w_loc, n_dev=n_dev, group_axis=group_axis,
            member_axis=member_axis, exchange=exchange,
            partition=partition, fault=fault,
        )

        def local(roots, src, dst_local, valid, src_lo, src_hi,
                  degree_local, n_active, *maybe_core):
            core = maybe_core[0] if use_core else None
            args = (src[0], dst_local[0], valid[0], src_lo[0], src_hi[0],
                    degree_local[0])
            if vmapped:
                res = jax.vmap(
                    lambda r: run_one(*args, n_active, r, core))(roots)
            else:
                res = run_one(*args, n_active, roots, core)
            return (res.parent, res.level, res.stats.levels,
                    res.stats.sentinel)

        n_sharded = 6

    g_spec = P(va)
    core_specs = (P(),) if use_core else ()
    if root_axis is not None:
        in_specs = (P(root_axis),) + (g_spec,) * n_sharded + (P(),) \
            + core_specs
        out_specs = (P(root_axis, va), P(root_axis, va), P(root_axis),
                     P(root_axis))
    elif batched:
        in_specs = (P(),) + (g_spec,) * n_sharded + (P(),) + core_specs
        out_specs = (P(None, va), P(None, va), P(), P())
    else:
        in_specs = (P(),) + (g_spec,) * n_sharded + (P(),) + core_specs
        out_specs = (P(va), P(va), P(), P())
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclass(frozen=True)
class _Program:
    """One jitted traversal program bound to its graph operands: the
    root(s) go in at ``root_pos``, ``statics`` are its static keywords."""

    fn: Callable
    operands: tuple
    root_pos: int
    statics: dict = dataclasses.field(default_factory=dict)

    def _args(self, roots):
        return (self.operands[:self.root_pos] + (roots,)
                + self.operands[self.root_pos:])

    def __call__(self, roots):
        return self.fn(*self._args(roots), **self.statics)

    def lower(self, roots, sharding=None):
        if sharding is None:
            return self.fn.lower(*self._args(roots), **self.statics)
        # Shapes only, placed by ``sharding``, traced afresh: jit's trace
        # cache would hand back a trace made for the backend of the arrays.
        fresh = jax.jit(functools.partial(self.fn.__wrapped__, **self.statics))
        return fresh.lower(*jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            self._args(roots)))


def _vertex_fn(mesh, **kw):
    key = ("vertex", mesh, tuple(sorted(kw.items())))
    fn = _MESH_FN_CACHE.get(key)
    if fn is None:
        fn = jax.jit(vertex_sharded_program(mesh, **kw))
        _MESH_FN_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# 4. compile_plan + the runner
# ---------------------------------------------------------------------------

def compile_plan(plan: TraversalPlan, built, *, mesh=None,
                 axis_names=None, fault=None) -> "CompiledBFS":
    """Validate ``plan``, prepare the graph inputs, and close over one
    jitted (possibly shard_map'd) callable.

    ``built`` is a :class:`PreparedGraph` or anything exposing
    ``ev``/``degree``/``core`` (``pipeline.BuiltGraph``).  ``mesh`` lets
    callers supply a prebuilt device mesh (its axes must cover the plan
    layout; plan-level strictness like the power-of-two member check is
    skipped for caller-supplied meshes).
    ``axis_names`` renames layout roles onto concrete mesh axes (entries
    may be tuples for factored roles).

    ``fault`` (DESIGN.md §13) is a static
    :class:`repro.core.faults.FaultSpec` compiled into the bitmap
    engines' injection hooks — deterministic corruption for exercising
    the checked execution mode and the recovery policy.  ``None`` (the
    default) compiles the clean program; the reference engine has no
    injection sites and rejects a fault.
    """
    validate_plan(plan)
    if fault is not None and plan.engine != "bitmap":
        raise ValueError(
            f"fault injection requires engine='bitmap' (got "
            f"{plan.engine!r}); the reference engine has no hooks")
    mesh, names = _resolve_mesh(plan, mesh, axis_names)
    role = dict(zip(plan.layout, names))
    vertexy = "member" in plan.layout
    n_dev_vertex = 1
    if vertexy:
        n_dev_vertex = (_role_size(mesh, role["group"])
                        * _role_size(mesh, role["member"]))
    pg = _prepare(built, plan, n_dev_vertex)
    # The heavy-core dense corner is a boolean-semiring step — it has no
    # weight plane, so only the BFS kernel consults it.
    use_core = pg.core is not None and plan.kernel == "bfs"
    use_pallas = not kops.interpret_mode()
    root_axis_size = _role_size(mesh, role["root"]) if "root" in role else 1

    # δ-stepping statics (SSSP only): the bucket width is a compile-time
    # constant derived host-side from the max edge weight.
    kernel = plan.kernel
    kernel_kw: dict = {}
    if kernel == "sssp":
        w_arr = (pg.ev.weight
                 if pg.ev is not None and pg.ev.weight is not None
                 else pg.sharded.weight)
        maxw = int(jax.device_get(jnp.max(w_arr)))
        kernel_kw = dict(kernel="sssp", delta=bucket_width(maxw),
                         max_rounds=sssp_max_rounds(plan.max_levels))

    core_arg = pg.core if use_core else None
    perm = None
    if not plan.layout:
        chunks, degree = pg.chunks, pg.degree
        n_active = jnp.sum(degree > 0).astype(jnp.int32)
        bitmap_kw = dict(alpha=plan.alpha, beta=plan.beta, use_core=use_core,
                         max_levels=plan.max_levels, fault=fault)
        if kernel == "sssp":
            sssp_kw = dict(delta=kernel_kw["delta"],
                           max_rounds=kernel_kw["max_rounds"], fault=fault)
            program = _Program(
                _run_sssp_batch if plan.batch_roots else _run_sssp,
                (chunks, degree), 2, sssp_kw)
        elif plan.batch_roots:
            program = _Program(_run_batch, (chunks, degree, n_active, core_arg),
                               3, dict(bitmap_kw, use_pallas_core=use_pallas))
        elif plan.engine == "bitmap":
            program = _Program(_run_bitmap,
                               (chunks, degree, n_active, core_arg), 3,
                               bitmap_kw)
        else:
            program = _Program(
                _run_reference, (pg.ev, degree, n_active), 3,
                dict(alpha=plan.alpha, beta=plan.beta,
                     max_levels=plan.max_levels))
        v_orig = pg.ev.num_vertices
    elif plan.layout == ("root",):
        n_active = jnp.sum(pg.degree > 0).astype(jnp.int32)
        fn = _root_parallel_fn(mesh, role["root"], plan.alpha, plan.beta,
                               use_core, plan.max_levels, use_pallas, fault,
                               **kernel_kw)
        program = _Program(fn, (pg.chunks, pg.degree, n_active, core_arg), 3)
        v_orig = pg.ev.num_vertices
    else:
        sg = pg.sharded
        fn = _vertex_fn(
            mesh,
            w_loc=sg.w_loc, n_dev=sg.n_devices,
            group_axis=role["group"], member_axis=role["member"],
            root_axis=role.get("root"),
            exchange=plan.exchange, partition=plan.partition,
            alpha=plan.alpha, beta=plan.beta,
            use_core=use_core, max_levels=plan.max_levels,
            use_pallas_core=use_pallas, batched=plan.batch_roots,
            fault=fault, **kernel_kw,
        )
        gargs = (sg.src, sg.dst_local, sg.valid, sg.src_lo, sg.src_hi)
        if kernel == "sssp":
            gargs = gargs + (sg.weight,)
        core_args = (pg.core,) if use_core else ()
        program = _Program(
            fn, gargs + (sg.degree_local, sg.n_active) + core_args, 0)
        # Reassembly: shard outputs concatenate shard-major; under the
        # word-cyclic owner map the inverse permutation restores global
        # vertex order (identity for block, where it is skipped).
        if plan.partition != "block":
            perm = jnp.asarray(partition_permutation(
                sg.n_devices, sg.w_loc, plan.partition))
        v_orig = sg.v_orig

    replicate = None
    if mesh_process_count(mesh) > 1:
        # Cross-process mesh (DESIGN.md §15): the raw program's outputs
        # are sharded over devices this process cannot address, so one
        # extra jitted reshard (an XLA all-gather over the real wire)
        # replicates them — every rank then holds the full parent/level
        # arrays addressably and the runner/validation/TEPS machinery
        # below works unchanged on every rank.
        from jax.sharding import NamedSharding
        replicate = jax.jit(lambda t: t,
                            out_shardings=NamedSharding(mesh, P()))

    return CompiledBFS(
        plan=plan, mesh=mesh, graph=pg, num_vertices=v_orig,
        _program=program, _perm=perm, _replicate=replicate,
        _vertexy=vertexy, _root_axis_size=root_axis_size, _axis_names=names,
        _fault=fault,
    )


@dataclass
class CompiledBFS:
    """A validated plan closed over one jitted callable.

    ``bfs`` returns layout-native raw results (a batched
    :class:`BFSResult` for root layouts, a :class:`ShardedRun` with
    padded global vertex order for vertex layouts); ``run`` executes the
    timed Graph500 harness and returns the uniform
    :class:`Graph500Result`.
    """

    plan: TraversalPlan
    mesh: Any
    graph: PreparedGraph
    num_vertices: int           # original V (before shard padding)
    _program: _Program          # the jitted traversal program
    _perm: Any = None           # vertex layouts: shard-major -> global order
    _replicate: Any = None      # cross-process meshes: replicate outputs
    _vertexy: bool = False
    _root_axis_size: int = 1
    _axis_names: tuple = ()
    _fault: Any = None          # the static FaultSpec compiled in (or None)
    _fallback: Any = None       # lazily-built degraded-plan CompiledBFS

    @property
    def mesh_axes(self) -> Optional[dict]:
        if self.mesh is None:
            return None
        return {role: _role_size(self.mesh, name)
                for role, name in zip(self.plan.layout, self._axis_names)}

    def lower(self, roots, sharding=None):
        """The traversal program lowered for ``roots`` (a scalar root for
        per-root plans, a root vector for batched ones), without running
        it: ``.compile().memory_analysis()`` gives its device bytes and
        ``.as_text()`` shows its kernels and collectives.  With
        ``sharding``, every operand is a shape placed by it, so a
        one-device program lowers for a device that holds no arrays."""
        return self._program.lower(jnp.asarray(roots, jnp.int32), sharding)

    def _raw(self, roots):
        """Run the program; outputs in global vertex order, addressable
        by this process."""
        out = self._program(roots)
        if self._perm is not None:
            parent, level, levels, sentinel = out
            out = (jnp.take(parent, self._perm, axis=-1),
                   jnp.take(level, self._perm, axis=-1), levels, sentinel)
        if self._replicate is not None:
            out = self._replicate(out)
        return out

    def bfs(self, roots):
        """Raw traversal(s).  ``batch_roots`` plans take a root vector
        (padded to the root-axis size with ``roots[0]`` and sliced back);
        per-root plans take a scalar root.  A profiler trace shows the
        placement of the roots and the dispatch of the program as the host
        span ``bfs.call``."""
        with jax.profiler.TraceAnnotation("bfs.call"):
            if not self.plan.batch_roots:
                out = self._raw(jnp.asarray(roots, jnp.int32))
                return ShardedRun(*out) if self._vertexy else out
            roots = jnp.asarray(roots, jnp.int32)
            n = roots.shape[0]
            pad = (-n) % self._root_axis_size
            if pad:
                roots = jnp.concatenate(
                    [roots, jnp.broadcast_to(roots[:1], (pad,))])
            out = self._raw(roots)
            if self._vertexy:
                out = ShardedRun(*out)
            if pad:
                out = jax.tree_util.tree_map(lambda x: x[:n], out)
            return out

    def _strip(self, x):    # drop shard padding on the device, not via H2D
        v = self.num_vertices
        return x if x.shape[-1] == v else x[..., :v]

    def _sentinel_of(self, res):
        """The per-level in-loop check-mask trace of one raw result, or
        ``None`` for the reference engine, which has none."""
        if self._vertexy:
            return res.sentinel
        stats = getattr(res, "stats", None)
        return None if stats is None else stats.sentinel

    def _solve_roots(self, roots_np):
        """Untimed re-solve of the given roots: stripped numpy
        parent / level row batches plus the per-root sentinel trace
        (``None`` when the engine has no trace)."""
        roots_np = np.asarray(roots_np, np.int32).reshape(-1)
        if self.plan.batch_roots:
            res = self.bfs(roots_np)
            sent = self._sentinel_of(res)
            return (np.asarray(self._strip(res.parent)),
                    np.asarray(self._strip(res.level)),
                    None if sent is None else np.asarray(sent))
        ps, ls, ss = [], [], []
        for r in roots_np:
            res = self.bfs(int(r))
            ps.append(np.asarray(self._strip(res.parent)))
            ls.append(np.asarray(self._strip(res.level)))
            ss.append(self._sentinel_of(res))
        sent = (np.stack([np.asarray(s) for s in ss])
                if all(s is not None for s in ss) else None)
        return np.stack(ps), np.stack(ls), sent

    def _fallback_compiled(self):
        """The degraded recovery plan (DESIGN.md §13): a single-device
        batched bitmap traversal, compiled lazily from the unsharded
        inputs and cached.  ``None`` when those inputs are missing or
        this plan already IS the degraded shape (no further downgrade
        exists).  The compiled fault rides along — recovery models
        routing around a broken exchange, not un-breaking hardware, so
        only faults whose site exists on the degraded path persist."""
        if self._fallback is not None:
            return self._fallback
        pg = self.graph
        if pg.ev is None or pg.degree is None:
            return None
        if (not self.plan.layout and self.plan.engine == "bitmap"
                and self.plan.batch_roots):
            return None
        fb_plan = TraversalPlan(engine="bitmap", layout=(),
                                batch_roots=True,
                                alpha=self.plan.alpha, beta=self.plan.beta,
                                max_levels=self.plan.max_levels,
                                n_chunks=self.plan.n_chunks,
                                kernel=self.plan.kernel)
        self._fallback = compile_plan(
            fb_plan, PreparedGraph(ev=pg.ev, degree=pg.degree, core=pg.core),
            fault=self._fault)
        return self._fallback

    def run(self, roots, *, warmup: bool = True, do_validate: bool = True,
            check: str | None = None, retries: int = 0,
            fallback: bool = False) -> Graph500Result:
        """Graph500 steps 3 + 4 under this plan, with checked execution.

        Batched plans time ONE fused program and attribute
        wall-clock / n_roots to each search (DESIGN.md §8); per-root
        plans time each search separately.

        ``check`` selects the verification mode (DESIGN.md §13):

          ``"off"``   no checks; ``validated`` stays empty, so
                      ``all_valid`` reports False rather than vacuously
                      True.
          ``"post"``  ONE vmapped :func:`validate_batch` dispatch over
                      the whole root batch (all five spec checks, no
                      per-root host loop), with per-check failure counts
                      in ``run.check_counts`` and per-root attribution
                      in ``run.check_failures``.
          ``"full"``  ``"post"`` plus the cheap in-loop sentinels the
                      bitmap engines carry through the level loop
                      (exchange conservation, frontier∩visited = ∅,
                      level bound) surfaced as the ``"sentinel"`` check.

        ``check=None`` (default) maps ``do_validate`` onto ``"post"`` /
        ``"off"`` for backward compatibility.

        Recovery: roots failing any check are re-run untimed up to
        ``retries`` times, then (``fallback=True``) re-run on the
        degraded single-device plan of :meth:`_fallback_compiled`; roots
        still failing are **quarantined** — TEPS forced to 0.0 so the
        harmonic mean excludes them, root ids recorded in
        ``run.quarantined``.  ``run.retries`` / ``run.fallbacks`` count
        the re-solved roots per stage.
        """
        if check is None:
            check = "post" if do_validate else "off"
        if check not in ("off", "post", "full"):
            raise ValueError(
                f"check must be 'off', 'post' or 'full' (got {check!r})")
        if self.graph.degree is None:
            raise ValueError("CompiledBFS.run needs built.degree for the "
                             "TEPS edge count (pass it via PreparedGraph)")
        roots_np = np.asarray(roots, np.int32).reshape(-1)
        n = len(roots_np)
        v = self.num_vertices
        g500 = Graph500Run(batched=self.plan.batch_roots)
        if n == 0:
            return Graph500Result(
                np.zeros((0, v), np.int32), np.zeros((0, v), np.int32),
                g500, self.plan, self.mesh_axes)
        degree = self.graph.degree

        if self.plan.batch_roots:
            if warmup:
                jax.block_until_ready(self.bfs(roots_np).parent)
            t0 = time.perf_counter()
            res = self.bfs(roots_np)
            res.parent.block_until_ready()
            per_root_s = (time.perf_counter() - t0) / n
            parent_dev = self._strip(res.parent)
            level_dev = self._strip(res.level)
            sent = self._sentinel_of(res)
            times = [per_root_s] * n
        else:
            if warmup:
                jax.block_until_ready(self.bfs(int(roots_np[0])).parent)
            rows, times, sents = [], [], []
            for r in roots_np:
                t0 = time.perf_counter()
                res = self.bfs(int(r))
                res.parent.block_until_ready()
                times.append(time.perf_counter() - t0)
                rows.append((self._strip(res.parent),
                             self._strip(res.level)))
                sents.append(self._sentinel_of(res))
            parent_dev = jnp.stack([p for p, _ in rows])
            level_dev = jnp.stack([l for _, l in rows])
            sent = (jnp.stack(sents)
                    if all(s is not None for s in sents) else None)

        # Host copies up front: writable (recovery patches rows), and the
        # TEPS/validation dispatches below must take process-local inputs
        # — a cross-process replicated output is readable here but cannot
        # be mixed with this rank's local arrays inside one jit.
        parent_np = np.array(parent_dev)
        level_np = np.array(level_dev)
        m_all = jax.vmap(lambda p: traversed_edges(
            degree, BFSResult(parent=p, level=None, stats=None))
        )(parent_np)
        m_np = np.asarray(m_all)
        ev = self.graph.ev
        g500.times_s = [float(dt) for dt in times]
        g500.edges = [int(m) for m in m_np]
        g500.teps = [m / dt if dt > 0 else 0.0
                     for m, dt in zip(g500.edges, times)]

        # --- check phase: one batched validation, no per-root loop ---
        sent_np = (np.asarray(sent)
                   if check == "full" and sent is not None else None)
        counts, failures = _check_batch(ev, parent_np, level_np, roots_np,
                                        check, sent_np,
                                        kernel=self.plan.kernel)
        checked = bool(counts)      # some check actually ran
        g500.check_counts = dict(counts)
        g500.check_failures = {int(roots_np[i]): list(names)
                               for i, names in failures.items()}

        # --- recovery: retry -> degraded fallback -> quarantine ---
        def attempt(idx, solver):
            p2, l2, s2 = solver(roots_np[idx])
            f2 = _recheck_rows(ev, p2, l2, roots_np[idx], check, s2,
                               kernel=self.plan.kernel)
            for j, i in enumerate(idx):
                i = int(i)
                if j in f2:
                    failures[i] = f2[j]
                    continue
                parent_np[i] = p2[j]
                level_np[i] = l2[j]
                m = int(traversed_edges(degree, BFSResult(
                    parent=jnp.asarray(p2[j]), level=None, stats=None)))
                g500.edges[i] = m
                g500.teps[i] = (m / times[i] if times[i] > 0 else 0.0)
                del failures[i]

        if failures:
            for _ in range(max(0, int(retries))):
                if not failures:
                    break
                idx = sorted(failures)
                g500.retries += len(idx)
                attempt(idx, self._solve_roots)
            if failures and fallback:
                fb = self._fallback_compiled()
                if fb is not None:
                    idx = sorted(failures)
                    g500.fallbacks += len(idx)
                    attempt(idx, fb._solve_roots)
        for i in sorted(failures):
            g500.teps[i] = 0.0      # quarantined: excluded from the hmean
            g500.quarantined.append(int(roots_np[i]))
        if checked:
            g500.validated = [i not in failures for i in range(n)]
        return Graph500Result(parent_np, level_np, g500, self.plan,
                              self.mesh_axes)

    def serve_batch(self, roots, *, check: str = "post", retries: int = 0,
                    fallback: bool = False) -> ServeBatch:
        """One checked, untimed root-batch solve — the serving primitive
        (DESIGN.md §14).

        The same detect → retry → degraded-fallback machinery as
        :meth:`run`, minus the Graph500 harness bookkeeping (warmup,
        wall-clock attribution, TEPS, quarantine): the serving engine
        owns the clock and the recovery *policy* — rows still failing
        come back in ``failures`` so the caller re-queues them instead
        of accepting a wrong tree.  Rows are in batch order; padding
        slots the caller added are its own to mask.
        """
        if check not in ("off", "post", "full"):
            raise ValueError(
                f"check must be 'off', 'post' or 'full' (got {check!r})")
        roots_np = np.asarray(roots, np.int32).reshape(-1)
        if roots_np.size == 0:
            v = self.num_vertices
            return ServeBatch(np.zeros((0, v), np.int32),
                              np.zeros((0, v), np.int32), {}, {})
        ev = self.graph.ev
        p, l, sent = self._solve_roots(roots_np)
        parent_np = np.array(p)     # writable: recovery patches rows
        level_np = np.array(l)
        sent_np = sent if check == "full" and sent is not None else None
        counts, failures = _check_batch(ev, parent_np, level_np, roots_np,
                                        check, sent_np,
                                        kernel=self.plan.kernel)

        def attempt(idx, solver):
            p2, l2, s2 = solver(roots_np[idx])
            f2 = _recheck_rows(ev, p2, l2, roots_np[idx], check, s2,
                               kernel=self.plan.kernel)
            for j, i in enumerate(idx):
                i = int(i)
                if j in f2:
                    failures[i] = f2[j]
                    continue
                parent_np[i] = p2[j]
                level_np[i] = l2[j]
                del failures[i]

        if failures:
            for _ in range(max(0, int(retries))):
                if not failures:
                    break
                attempt(sorted(failures), self._solve_roots)
            if failures and fallback:
                fb = self._fallback_compiled()
                if fb is not None:
                    attempt(sorted(failures), fb._solve_roots)
        return ServeBatch(parent_np, level_np, counts, failures)


def _check_batch(ev, parents, levels, roots, check, sent, kernel="bfs"):
    """Detection pass shared by :meth:`CompiledBFS.run`,
    :meth:`CompiledBFS.serve_batch` and the recovery rechecks.

    Returns ``(counts, failures)``: per-check failure counts (zeros
    included whenever the spec checks ran — the stable BENCH shape) and
    a row-index → failed-check-names map.  ``sent`` is the per-row
    in-loop sentinel trace, applied only under ``check="full"``.  The
    spec-check vocabulary is the kernel's (``core.kernels``); for SSSP
    the ``levels`` rows carry the distance plane.
    """
    counts: dict[str, int] = {}
    failures: dict[int, list[str]] = {}
    if check != "off" and ev is not None:
        val = validate_result_batch(
            kernel, ev, jnp.asarray(parents), jnp.asarray(levels),
            np.asarray(roots, np.int32))
        counts, failures = failure_report(val)
    if check == "full" and sent is not None:
        sent = np.asarray(sent)
        bad = np.any((sent != -1) & (sent != SENTINEL_OK), axis=-1)
        counts["sentinel"] = int(np.sum(bad))
        for j in np.nonzero(bad)[0]:
            failures.setdefault(int(j), []).append("sentinel")
    return counts, failures


def _recheck_rows(ev, parents, levels, roots, check, sent, kernel="bfs"):
    """Failure map (row index -> failed check names) for re-solved rows
    during recovery — same checks as the first pass."""
    # the first pass runs the spec checks whenever check != "off", so the
    # recheck must too (sent gating stays inside _check_batch)
    return _check_batch(ev, parents, levels, roots, check,
                        sent if check == "full" else None, kernel=kernel)[1]
