"""Direction-optimizing hybrid BFS (paper §2.1): the reference and bitmap
engines.

Switching policy — paper eq. (1)/(2), Fig. 1::

    top-down  -> bottom-up  when |in| > ThrV1 = (|V| - |vis|) / alpha
    bottom-up -> top-down   when |in| < ThrV2 = |V| / beta

``|V|`` counts *active* (non-isolated) vertices — the isolated ~50%
(paper Fig. 7) are pruned by the degree sort and never traversed.

Engines (the Fig. 18 ladder, DESIGN.md §3):

  * ``reference`` — pure-jnp edge-parallel relaxation both directions
    over boolean frontier/visited arrays (the reference-3.0.0 rung and
    the test oracle).
  * ``bitmap``    — the bitmap-resident Pre-G500 engine (T1 + T2):
    ``frontier`` and ``visited`` live as packed ``uint32 [W]`` across the
    whole ``lax.while_loop`` (bits set once at init, never re-packed
    inside the loop), the level epilogue (mask / merge / popcount) runs
    the fused ``kernels.ops.frontier_update`` Pallas kernel, the bottom-up
    core step consumes the resident bitmap directly, and top-down is
    *chunked*: the degree-sorted edge array is split into fixed chunks
    whose source-vertex ranges are tested against the frontier bitmap so
    small frontiers skip most of the edge scan (frontier-proportional
    work, DESIGN.md §3).
    Bottom-up, the tail edges are a *pull* over the src-sorted rows: each
    unvisited row takes the min frontier neighbour among its own slots,
    a dense segmented min with no scatter (``_pull_relax``).

Everything is a single ``lax.while_loop`` under jit; per-level statistics
(direction, frontier size, scanned edges, scanned chunks) land in
fixed-size arrays (``BFSStats``).  ``_run_batch`` vmaps the bitmap engine
over the 64 Graph500 search keys so the whole benchmark is one jitted
program (a ``batch_roots`` plan, ``core/plan.py``).

The bitmap engines name their phases with ``jax.named_scope``, so a
device trace charges every op to one: ``bfs.init`` (state set-up),
``bfs.td_relax`` (chunk mask and chunked top-down relax), ``bfs.bu_core``
(the dense-core kernel and its winners' scatter-min), ``bfs.bu_relax``
(the pull over the tail edges' rows), ``bfs.epilogue`` (the
direction switch, delta pack, ``frontier_update``, sentinels and the
state update), ``bfs.exchange`` (the sharded engine's delta exchange)
and ``bfs.finish`` (the parent unpack).  Scopes are op metadata only:
the compiled program is the same with or without them.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.core.bfs_steps import (
    ChunkedEdgeView,
    EdgeView,
    chunk_frontier_mask,
    chunk_range_mask,
    frontier_edge_count,
    relax_step,
)
from repro.core.heavy import HeavyCore, padded_bitmap_words, testbit
from repro.kernels import ops as kops
from repro.kernels.bitmap_ops import WORDS_PER_TILE
from repro.kernels.ref import BIG, core_spmv_ref, popcount_u32

MAX_LEVELS = 64
TOP_DOWN, BOTTOM_UP = jnp.int32(0), jnp.int32(1)

ENGINES = ("reference", "bitmap")

#: All in-loop sentinel bits passing (BFSStats.sentinel, DESIGN.md §13).
SENTINEL_OK = 7


def _switch_direction(direction, in_count, vis_count, n_active,
                      alpha: float, beta: float):
    """Paper eq. (1)/(2) hybrid switch — the ONE copy of the formula.

    Shared by the reference, bitmap-resident and vertex-sharded level
    loops so the engines stay bitwise-locked if the heuristic is ever
    tuned.
    """
    thrv1 = ((n_active - vis_count).astype(jnp.float32)
             / alpha).astype(jnp.int32)
    thrv2 = (n_active.astype(jnp.float32) / beta).astype(jnp.int32)
    return jnp.where(
        (direction == TOP_DOWN) & (in_count > thrv1),
        BOTTOM_UP,
        jnp.where(
            (direction == BOTTOM_UP) & (in_count < thrv2),
            TOP_DOWN,
            direction,
        ),
    )


class BFSStats(NamedTuple):
    direction: jax.Array        # [MAX_LEVELS] int32 (-1 unused)
    frontier_size: jax.Array    # [MAX_LEVELS] int32
    scanned_edges: jax.Array    # [MAX_LEVELS] int32 — work estimate per level
    levels: jax.Array           # [] int32
    scanned_chunks: jax.Array   # [MAX_LEVELS] int32 — edge chunks relaxed (-1 n/a)
    total_chunks: jax.Array     # [] int32 — chunk count (0 for unchunked engines)
    # In-loop sentinel trace (DESIGN.md §13): per-level bitmask, -1 for
    # unused levels, else bit0 = exchange conservation (next-frontier
    # popcount == Σ shard delta popcounts), bit1 = frontier ∩ visited = ∅,
    # bit2 = level within bound — a healthy level reads 7.  None for the
    # reference engine, which carries no sentinels.
    sentinel: jax.Array | None = None


class BFSResult(NamedTuple):
    parent: jax.Array  # [V] int32, -1 = unvisited, parent[root] == root
    level: jax.Array   # [V] int32, -1 = unvisited
    stats: BFSStats


# ---------------------------------------------------------------------------
# Reference engine: boolean frontier state, every edge every level.
# ---------------------------------------------------------------------------

class _RefState(NamedTuple):
    parent_ext: jax.Array
    frontier: jax.Array
    visited: jax.Array
    level: jax.Array
    lvl: jax.Array
    direction: jax.Array
    stats_dir: jax.Array
    stats_fs: jax.Array
    stats_se: jax.Array


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "max_levels"))
def _run_reference(
    ev: EdgeView,
    degree: jax.Array,
    n_active: jax.Array,
    root: jax.Array,
    *,
    alpha: float,
    beta: float,
    max_levels: int,
) -> BFSResult:
    """The reference-3.0.0 rung: one edge-parallel relax over every edge
    per level.  The direction switch only labels the level in the stats
    (and picks the work estimate it reports); both directions relax the
    same edges."""
    v = ev.num_vertices
    parent_ext = jnp.full((v + 1,), v, jnp.int32).at[root].set(root)
    frontier = jnp.zeros((v,), bool).at[root].set(True)
    level = jnp.full((v,), -1, jnp.int32).at[root].set(0)

    def cond(s: _RefState):
        return jnp.any(s.frontier) & (s.lvl < max_levels)

    def body(s: _RefState):
        in_count = jnp.sum(s.frontier).astype(jnp.int32)
        vis_count = jnp.sum(s.visited).astype(jnp.int32)
        direction = _switch_direction(
            s.direction, in_count, vis_count, n_active, alpha, beta)
        new_parent, nxt = relax_step(ev, s.parent_ext, s.frontier, s.visited)

        # scanned-edge estimate: TD scans frontier adjacency; BU scans
        # unvisited adjacency (the relax scans all; we report the
        # algorithmic work the direction choice implies — paper Fig. 17).
        m_f = frontier_edge_count(degree, s.frontier)
        m_u = jnp.sum(jnp.where(s.visited, 0, degree))
        scanned = jnp.where(direction == TOP_DOWN, m_f, m_u).astype(jnp.int32)

        return _RefState(
            new_parent, nxt, s.visited | nxt,
            jnp.where(nxt, s.lvl + 1, s.level), s.lvl + 1, direction,
            s.stats_dir.at[s.lvl].set(direction),
            s.stats_fs.at[s.lvl].set(in_count),
            s.stats_se.at[s.lvl].set(scanned),
        )

    init = _RefState(
        parent_ext, frontier, frontier, level,
        jnp.int32(0), TOP_DOWN,
        jnp.full((max_levels,), -1, jnp.int32),
        jnp.zeros((max_levels,), jnp.int32),
        jnp.zeros((max_levels,), jnp.int32),
    )
    s = jax.lax.while_loop(cond, body, init)
    parent = jnp.where(s.parent_ext[:v] == v, -1, s.parent_ext[:v])
    return BFSResult(
        parent=parent,
        level=s.level,
        stats=BFSStats(
            s.stats_dir, s.stats_fs, s.stats_se, s.lvl,
            jnp.full((max_levels,), -1, jnp.int32), jnp.int32(0),
        ),
    )


# ---------------------------------------------------------------------------
# Bitmap-resident engine (DESIGN.md §3).
#
# Loop invariants:
#   I1. frontier_bm / visited_bm are packed uint32 [W] for the *whole*
#       traversal — bits are set once at init and the resident state is
#       never re-packed inside the while body.  Per-slot membership tests
#       are single-bit word gathers; the bottom-up pull's per-row visited
#       test reads visited_bm by a dense shift/reshape, no gather.
#   I2. in_count == popcount(frontier_bm); it comes from the fused
#       frontier_update epilogue of the previous level, never recounted.
#   I3. next-frontier bits are derived from the parent-array *delta*: the
#       newly-found vector is already materialized for level bookkeeping,
#       and the epilogue packs it word-wise (O(V/32) output work) before
#       the fused frontier_update — no per-edge bit bookkeeping, and no
#       round trip of the resident frontier/visited state.
#   I4. parent_ext holds the min frontier neighbour of each newly found
#       vertex: a scatter-min top-down and in the core step, a pull over
#       the src-sorted rows bottom-up (the same min, since the CSR and the
#       tail mask are symmetric); the bitmap engine's parent/level outputs
#       are byte-identical to the reference engine's.
# ---------------------------------------------------------------------------

class _ResidentState(NamedTuple):
    parent_ext: jax.Array    # [V+1] int32
    level: jax.Array         # [V] int32
    frontier_bm: jax.Array   # [W] uint32 — resident, packed
    visited_bm: jax.Array    # [W] uint32 — resident, packed
    in_count: jax.Array      # [] int32 — popcount(frontier_bm)  (I2)
    vis_count: jax.Array     # [] int32 — popcount(visited_bm)
    m_f: jax.Array           # [] int32 — sum of degree over the frontier
    deg_vis: jax.Array       # [] int32 — sum of degree over visited
    lvl: jax.Array
    direction: jax.Array
    stats_dir: jax.Array
    stats_fs: jax.Array
    stats_se: jax.Array
    stats_ch: jax.Array
    stats_ok: jax.Array      # [MAX_LEVELS] int32 — sentinel masks (§13)


def _core_bottom_up_resident(core: HeavyCore, frontier_bm, visited_bm,
                             parent_ext, v, use_pallas_core):
    """Dense-core kernel step consuming the resident frontier bitmap.

    No per-level pack: the kernel reads ``frontier_bm[:K/32]`` directly;
    winners scatter-min their parent row-wise.  ``use_pallas_core=False``
    swaps in the parity-tested jnp oracle — used by the batched harness on
    interpret-mode backends, where a vmapped interpreted kernel grid is
    pure overhead (DESIGN.md §8).
    """
    k = core.k
    spmv = kops.core_spmv if use_pallas_core else core_spmv_ref
    cand = spmv(core.a_core, frontier_bm[: k // 32])  # int32 [K]
    rows = jnp.arange(k, dtype=jnp.int32)
    won = (cand < BIG) & ~testbit(visited_bm, rows)
    tgt = jnp.where(won, rows, v)
    return parent_ext.at[tgt].min(jnp.where(won, cand, v).astype(jnp.int32))


def _relax_edges(sc, dc, vc, frontier_bm, visited_bm, parent, v):
    """One edge-parallel relax pass in bitmap space (the chunked top-down).

    Frontier/visited membership tests are single-bit gathers from the
    resident bitmaps; newly found vertices surface later as the parent
    delta (I3), so the pass itself is a pure scatter-min.
    """
    active = vc & testbit(frontier_bm, sc) & ~testbit(visited_bm, dc)
    cand = jnp.where(active, sc, v).astype(jnp.int32)
    tgt = jnp.where(active, dc, v)
    return parent.at[tgt].min(cand)


def _chunked_relax(chunks: ChunkedEdgeView, live, frontier_bm,
                   visited_bm, parent_ext, v):
    """Top-down relaxation over live edge chunks only.

    ``live[c]`` gates each chunk behind ``lax.cond`` so skipped chunks
    cost nothing — small frontiers touch few chunks (DESIGN.md §3).
    Returns the updated parent scatter-min array and the number of chunks
    relaxed.
    """

    def body(c, carry):
        def relax(carry):
            parent, nsc = carry
            sc = jax.lax.dynamic_index_in_dim(chunks.src, c, 0, keepdims=False)
            dc = jax.lax.dynamic_index_in_dim(chunks.dst, c, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(chunks.valid, c, 0, keepdims=False)
            parent = _relax_edges(
                sc, dc, vc, frontier_bm, visited_bm, parent, v)
            return parent, nsc + 1

        return jax.lax.cond(live[c], relax, lambda x: x, carry)

    return jax.lax.fori_loop(
        0, chunks.n_chunks, body, (parent_ext, jnp.int32(0))
    )


#: Slots per tile of the bottom-up pull's in-tile scan (at most; it is
#: ``gcd(chunk_size, PULL_TILE)``, so tiles always divide a chunk).
PULL_TILE = 1024


def _segmented_min(key: jax.Array, val: jax.Array) -> jax.Array:
    """Inclusive running min of ``val`` along the last axis, restarting
    wherever ``key`` changes.

    ``key`` is sorted along that axis, so two slots ``k`` apart hold the
    same key only if every slot between them does: log2(n) shifted
    compare-and-min steps (Hillis-Steele), all dense, no scatter.
    """
    n = val.shape[-1]
    k = 1
    while k < n:
        same = key[..., k:] == key[..., :-k]
        upd = jnp.where(same, jnp.minimum(val[..., k:], val[..., :-k]),
                        val[..., k:])
        val = jnp.concatenate([val[..., :k], upd], axis=-1)
        k *= 2
    return val


def _row_ends(degree: jax.Array) -> jax.Array:
    """Each row's last slot in the src-sorted edge array, -1 for a row
    with none: ``degree`` counts a row's valid slots, which come first."""
    return jnp.where(degree > 0, jnp.cumsum(degree).astype(jnp.int32) - 1,
                     -1)


def _pull_relax(chunks: ChunkedEdgeView, core_k: int | None, row_end,
                frontier_bm, visited_bm, parent_ext, v):
    """Bottom-up tail relax as a pull over the src-sorted rows.

    The CSR is symmetric and sorted by ``(src, dst)`` with padding at the
    tail, and the tail mask (valid slots not inside the dense core) is
    symmetric too, so the push ``parent[d] = min{s in frontier : (s, d)}``
    over the tail equals, for each unvisited row ``x``, the min of the
    frontier ``u`` over the row's own slots ``(x, u)`` (I4 holds by
    symmetry).  Per slot that is one bit gather (frontier by ``dst``);
    the min over each row's contiguous slots is a dense segmented scan:
    in tiles of ``PULL_TILE`` slots, then over the tiles' last slots,
    carried from chunk to chunk in the loop (hub rows span many tiles
    and chunks).  Each row's min is read at its last slot ``row_end[x]``
    (``_row_ends``), and the visited test is per row.
    """
    c_size = chunks.chunk_size
    tile = math.gcd(c_size, PULL_TILE)

    def body(c, carry):
        rowmin, last_src, last_min = carry
        s2, d2, v2 = (jax.lax.dynamic_index_in_dim(
            a, c, 0, keepdims=False).reshape(-1, tile)
            for a in (chunks.src, chunks.dst, chunks.valid))
        tail = v2 if core_k is None else v2 & ~((s2 < core_k)
                                                & (d2 < core_k))
        m2 = _segmented_min(
            s2, jnp.where(tail & testbit(frontier_bm, d2), d2, v))
        # the running min of the row open at each tile's first slot
        t_src = jnp.concatenate([last_src[None], s2[:, -1]])
        t_min = _segmented_min(t_src,
                               jnp.concatenate([last_min[None], m2[:, -1]]))
        m2 = jnp.where(s2 == t_src[:-1, None],
                       jnp.minimum(m2, t_min[:-1, None]), m2)
        rowmin = jax.lax.dynamic_update_slice_in_dim(
            rowmin, m2, c * (c_size // tile), 0)
        return rowmin, t_src[-1], t_min[-1]

    rowmin, _, _ = jax.lax.fori_loop(
        0, chunks.n_chunks, body,
        (jnp.full((chunks.n_chunks * c_size // tile, tile), v, jnp.int32),
         jnp.int32(-1), jnp.int32(v)))
    shifts = jnp.arange(32, dtype=jnp.uint32)
    visited = ((visited_bm[:, None] >> shifts) & jnp.uint32(1)
               ).reshape(-1)[:v].astype(bool)
    end = jnp.maximum(row_end, 0)
    found = jnp.where((row_end >= 0) & ~visited,
                      rowmin[end // tile, end % tile], v)
    return jnp.minimum(parent_ext, jnp.pad(found, (0, 1), constant_values=v))


def _by_direction(bu, td):
    """``lax.cond(bottom_up, bu, td, *args)``, batched without copying
    the graph for bottom-up.

    vmap of a ``cond`` whose predicate is batched (each root has its own
    direction) runs both branches and selects, with every operand
    broadcast to the batch first: the edge chunks and the dense core,
    once per root.  Here ``td`` is batched just so (its chunk loop slices
    the per-root chunks), while ``bu`` is vmapped over only the arguments
    that are batched, so the pull and the core step read the one graph.
    """
    @jax.custom_batching.custom_vmap
    def step(bottom_up, *args):
        return jax.lax.cond(bottom_up, bu, td, *args)

    @step.def_vmap
    def _batched(axis_size, in_batched, bottom_up, *args):
        ib = tuple(in_batched[1:])
        axes = jax.tree.map(lambda b: 0 if b else None, ib)
        every = jax.tree.map(
            lambda x, b: x if b else jnp.broadcast_to(
                x, (axis_size,) + x.shape), args, ib)
        up = jnp.broadcast_to(bottom_up, (axis_size,))

        def pick(a, b):
            return jnp.where(up.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)

        out = jax.tree.map(
            pick, jax.vmap(bu, in_axes=axes, axis_size=axis_size)(*args),
            jax.vmap(td)(*every))
        return out, jax.tree.map(lambda _: True, out)

    return step


def _pack_delta_words(newly: jax.Array, w: int) -> jax.Array:
    """Pack the per-level newly-found vector into uint32 words (I3).

    This packs the level *delta* (already materialized for level
    bookkeeping), not the resident frontier/visited state — O(V) input,
    O(V/32) output, no gather/scatter.  It feeds the fused
    ``frontier_update`` epilogue as ``next_raw``.

    Deliberately NOT a call to ``heavy.pack_bitmap`` — the acceptance
    contract instruments that symbol to prove the resident state never
    round-trips inside the loop.  The LSB-first convention here must
    match it bit-for-bit; ``tests/test_bitmap.py`` locks the two
    implementations together.
    """
    n = newly.shape[0]
    pad = w * 32 - n
    m = jnp.concatenate([newly, jnp.zeros((pad,), bool)]) if pad else newly
    bits = m.reshape(w, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(bits << shifts, axis=1, dtype=jnp.uint32)


def _run_bitmap_impl(
    chunks: ChunkedEdgeView,
    degree: jax.Array,
    n_active: jax.Array,
    root: jax.Array,
    core: HeavyCore | None,
    *,
    alpha: float,
    beta: float,
    use_core: bool,
    max_levels: int,
    use_pallas_core: bool = True,
    fault=None,
) -> BFSResult:
    v = chunks.num_vertices
    w = padded_bitmap_words(v)
    with jax.named_scope("bfs.init"):
        nnz_total = jnp.sum(degree).astype(jnp.int32)

        parent_ext = jnp.full((v + 1,), v, jnp.int32).at[root].set(root)
        level = jnp.full((v,), -1, jnp.int32).at[root].set(0)
        # pack once at init: the root is the only set bit.
        root_bit = jnp.uint32(1) << (root % 32).astype(jnp.uint32)
        frontier_bm = jnp.zeros((w,), jnp.uint32).at[root // 32].set(root_bit)
        visited_bm = frontier_bm
        deg_root = degree[root].astype(jnp.int32)

        row_end = _row_ends(degree)   # for the bottom-up pull

    def cond(s: _ResidentState):
        return (s.in_count > 0) & (s.lvl < max_levels)

    def body(s: _ResidentState):
        with jax.named_scope("bfs.epilogue"):
            # Under vmap (bfs_batch) the while loop runs until *all* roots
            # are done; `alive` masks the state update for roots already
            # finished.
            alive = s.in_count > 0
            direction = _switch_direction(
                s.direction, s.in_count, s.vis_count, n_active, alpha, beta)
            bottom_up = direction == BOTTOM_UP

        def bu(ch, a_core, row_end, frontier_bm, visited_bm, parent_ext):
            # Dense-core kernel step (consuming the resident bitmap), then
            # the pull over every tail slot's row: BU frontiers are large,
            # so there is nothing for chunk skipping to skip.
            if use_core:
                with jax.named_scope("bfs.bu_core"):
                    # a_core is the step's argument, so that batched it
                    # stays one copy (_by_direction); k is static.
                    p1 = _core_bottom_up_resident(
                        dataclasses.replace(core, a_core=a_core),
                        frontier_bm, visited_bm, parent_ext,
                        v, use_pallas_core)
            else:
                p1 = parent_ext
            with jax.named_scope("bfs.bu_relax"):
                p2 = _pull_relax(
                    ch, core.k if use_core else None, row_end,
                    frontier_bm, visited_bm, p1, v)
            return p2, jnp.int32(ch.n_chunks)  # full scan

        def td(ch, a_core, row_end, frontier_bm, visited_bm, parent_ext):
            with jax.named_scope("bfs.td_relax"):
                live = chunk_frontier_mask(ch, frontier_bm)
                return _chunked_relax(ch, live, frontier_bm,
                                      visited_bm, parent_ext, v)

        new_parent, nsc = _by_direction(bu, td)(
            bottom_up, chunks, core.a_core if use_core else None, row_end,
            s.frontier_bm, s.visited_bm, s.parent_ext)

        with jax.named_scope("bfs.epilogue"):
            # Epilogue: the newly-found delta (needed for level bookkeeping
            # anyway) packs word-wise into next_raw (I3), then the fused
            # kernel does mask / merge / popcount in one pass (T1).
            newly = (new_parent[:v] != v) & (s.parent_ext[:v] == v)
            if fault is not None and fault.site == "parent":
                pv = faults.corrupt_parent(
                    fault, new_parent[:v], newly,
                    jnp.arange(v, dtype=jnp.int32), jnp.int32(v),
                    level=s.lvl, root=root)
                new_parent = jnp.concatenate([pv, new_parent[v:]])
            found = _pack_delta_words(newly, w)
            next_bm, new_visited_bm, count = kops.frontier_update(
                found, s.visited_bm)

            # In-loop sentinels (§13): delta conservation (no found bit was
            # already visited), frontier ∩ visited = ∅, level bound.
            s1 = count.astype(jnp.int32) == jnp.sum(
                popcount_u32(found)).astype(jnp.int32)
            s2 = jnp.sum(popcount_u32(next_bm & s.visited_bm)) == 0
            s3 = s.lvl + 1 <= jnp.int32(max_levels)
            ok_mask = (s1.astype(jnp.int32) + 2 * s2.astype(jnp.int32)
                       + 4 * s3.astype(jnp.int32))

            new_level = jnp.where(newly, s.lvl + 1, s.level)
            m_next = jnp.sum(jnp.where(newly, degree, 0)).astype(jnp.int32)

            # scanned-edge estimate, kept incrementally (paper Fig. 17):
            # TD scans frontier adjacency (m_f), BU unvisited adjacency.
            m_u = nnz_total - s.deg_vis
            scanned = jnp.where(direction == TOP_DOWN, s.m_f,
                                m_u).astype(jnp.int32)

            nxt = _ResidentState(
                new_parent, new_level, next_bm, new_visited_bm,
                count.astype(jnp.int32),
                s.vis_count + count.astype(jnp.int32),
                m_next, s.deg_vis + m_next,
                s.lvl + 1, direction,
                s.stats_dir.at[s.lvl].set(direction),
                s.stats_fs.at[s.lvl].set(s.in_count),
                s.stats_se.at[s.lvl].set(scanned),
                s.stats_ch.at[s.lvl].set(nsc),
                s.stats_ok.at[s.lvl].set(ok_mask),
            )
            return jax.tree_util.tree_map(
                lambda new, old: jnp.where(alive, new, old), nxt, s)

    init = _ResidentState(
        parent_ext, level, frontier_bm, visited_bm,
        jnp.int32(1), jnp.int32(1), deg_root, deg_root,
        jnp.int32(0), TOP_DOWN,
        jnp.full((max_levels,), -1, jnp.int32),
        jnp.zeros((max_levels,), jnp.int32),
        jnp.zeros((max_levels,), jnp.int32),
        jnp.full((max_levels,), -1, jnp.int32),
        jnp.full((max_levels,), -1, jnp.int32),
    )
    s = jax.lax.while_loop(cond, body, init)
    # unpack once at exit: outputs are the parent/level arrays (the resident
    # bitmaps never leave packed form).
    with jax.named_scope("bfs.finish"):
        parent = jnp.where(s.parent_ext[:v] == v, -1, s.parent_ext[:v])
    return BFSResult(
        parent=parent,
        level=s.level,
        stats=BFSStats(
            s.stats_dir, s.stats_fs, s.stats_se, s.lvl,
            s.stats_ch, jnp.int32(chunks.n_chunks),
            s.stats_ok,
        ),
    )


_BITMAP_STATICS = ("alpha", "beta", "use_core", "max_levels",
                   "use_pallas_core", "fault")

_run_bitmap = functools.partial(
    jax.jit, static_argnames=_BITMAP_STATICS,
)(_run_bitmap_impl)


@functools.partial(jax.jit, static_argnames=_BITMAP_STATICS)
def _run_batch(chunks, degree, n_active, roots, core, *,
               alpha, beta, use_core, max_levels, use_pallas_core,
               fault=None):
    """All search keys under ONE jitted program (vmap over roots)."""
    return jax.vmap(
        lambda r: _run_bitmap_impl(
            chunks, degree, n_active, r, core,
            alpha=alpha, beta=beta, use_core=use_core, max_levels=max_levels,
            use_pallas_core=use_pallas_core, fault=fault)
    )(roots)


# ---------------------------------------------------------------------------
# Layer 1 — root-parallel mesh sharding (DESIGN.md §9) has no engine code
# of its own: core/plan.py (`_root_parallel_fn`) shard_maps the batched
# bitmap engine over a ("root",) mesh.
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Layer 2 — vertex-sharded resident bitmaps (DESIGN.md §9, paper T3).
#
# One giant traversal spans a (group, member) mesh.  Ownership is
# word-granular under one of two maps (the plan's `partition` axis):
# contiguous BLOCKS — device d (flat index, group-major) owns words
# [d*W_loc, (d+1)*W_loc) — or WORD-CYCLIC (paper eq. (3) at uint32-word
# granularity) — device d owns words {w : w % P == d}, interleaving the
# degree-sorted heavy prefix evenly across shards.  Each shard holds:
#   * parent/level/visited for its owned vertices only (resident, packed);
#   * the edge chunks whose DESTINATION it owns (bottom-up orientation,
#     paper §4.2 — each device relaxes the edges pointing at its own
#     vertices), src-sorted and chunked for frontier-proportional TD;
#   * a replicated view of the current frontier bitmap (the only state
#     that travels).
# Per level the shard packs its newly-found delta words and the global
# next frontier is the bitwise-OR combination of all shards' deltas —
# routed through the T3 two-phase monitor collective
# (comms.hierarchical.hierarchical_por: OR-reduce-scatter over member,
# OR-exchange over group, all-gather over member).  Comms volume is
# V/8 bytes per level per device, like the paper's bitmap exchange.
# ---------------------------------------------------------------------------

SHARD_EXCHANGES = ("hier_or", "hier_gather", "flat")


def _axis_names_tuple(name) -> tuple:
    """Normalize a mesh-axis role to a tuple of concrete axis names.

    The dry-run lowers the engine on production meshes where the group
    role spans several mesh axes (e.g. ``("pod", "data")``); the runtime
    meshes use plain strings.
    """
    return tuple(name) if isinstance(name, (tuple, list)) else (name,)


def _shard_index(group_axis, member_axis):
    """Flat device index (group-major) of this shard inside shard_map."""
    idx = jnp.int32(0)
    for n in _axis_names_tuple(group_axis) + _axis_names_tuple(member_axis):
        idx = idx * jax.lax.axis_size(n) + jax.lax.axis_index(n)
    return idx


def _exchange_delta(delta_loc, dev, w_loc, n_dev, *, exchange,
                    group_axis, member_axis, partition="block",
                    fault=None, level=None, root=None):
    """Combine per-shard delta words into the full next-frontier bitmap.

    Delta bits live only in the owner's words (dst-owned edges find owned
    vertices), so OR-combining the shards' words reassembles the global
    frontier exactly.  The exchange must follow the owner map
    (``partition``): under ``block`` ownership shard ``d``'s local word
    ``j`` is global word ``d*W_loc + j`` — exactly the device-major block
    order the gather collectives emit; under ``word_cyclic`` it is global
    word ``d + j*P``, so the OR-scatter is strided and the gathered
    device-major blocks transpose into word order.  Three wirings, all
    bit-identical:

      * ``hier_or``     — scatter the owned words into a zero full-width
        vector and run the T3 two-phase bitwise-OR reduction
        (:func:`~repro.comms.hierarchical.hierarchical_por`).  This is the
        general form: it stays correct if a future edge partition lets
        shards produce overlapping deltas.
      * ``hier_gather`` — two-phase hierarchical all-gather of the blocks
        (1/M inter-group bytes; exploits disjointness).
      * ``flat``        — single-phase all-gather (the ablation baseline).
    """
    from repro.comms.hierarchical import (
        hierarchical_all_gather,
        hierarchical_por,
    )

    # Fault site "exchange" (§13): the outgoing per-level delta words —
    # shared by every wiring, upstream of scatter/gather.
    delta_loc = faults.corrupt_delta(fault, delta_loc, level=level,
                                     device=dev, root=root)

    axes = _axis_names_tuple(group_axis) + _axis_names_tuple(member_axis)
    if exchange == "hier_or":
        if partition == "word_cyclic":
            # global word j*P + d <-> matrix slot [j, d]: placing the
            # owned words in column `dev` of a [W_loc, P] zero matrix is
            # the strided owner scatter, row-major flatten restores word
            # order.
            full = jnp.where(
                jnp.arange(n_dev, dtype=jnp.int32)[None, :] == dev,
                delta_loc[:, None], jnp.uint32(0)).reshape(-1)
        else:
            full = jnp.zeros((n_dev * w_loc,), jnp.uint32)
            full = jax.lax.dynamic_update_slice(full, delta_loc,
                                                (dev * w_loc,))
        return hierarchical_por(full, group_axis, member_axis,
                                fault=fault, level=level, device=dev,
                                root=root)
    if exchange == "hier_gather":
        out = hierarchical_all_gather(delta_loc, group_axis, member_axis)
    elif exchange == "flat":
        out = jax.lax.all_gather(delta_loc, axes, axis=0, tiled=True)
    else:
        raise ValueError(
            f"unknown exchange {exchange!r}; expected one of "
            f"{SHARD_EXCHANGES}")
    if partition == "word_cyclic":
        # gathered blocks are device-major [d, j]; word order is [j, d].
        out = out.reshape(n_dev, w_loc).T.reshape(-1)
    return out


class _ShardState(NamedTuple):
    parent_loc: jax.Array    # [V_loc+1] int32, global parent ids, sentinel V
    level_loc: jax.Array     # [V_loc] int32
    frontier_bm: jax.Array   # [W] uint32 — full width, replicated value
    visited_loc: jax.Array   # [W_loc] uint32 — resident, owned words only
    in_count: jax.Array      # [] int32 — global popcount(frontier)
    vis_count: jax.Array     # [] int32 — global
    m_f: jax.Array           # [] int32 — global frontier degree sum
    deg_vis: jax.Array       # [] int32 — global visited degree sum
    lvl: jax.Array
    direction: jax.Array
    stats_dir: jax.Array
    stats_fs: jax.Array
    stats_se: jax.Array
    stats_ch: jax.Array
    stats_ok: jax.Array      # [MAX_LEVELS] int32 — sentinel masks (§13)


def _relax_owned_edges(sc, dst_loc, vc, frontier_bm, visited_loc,
                       parent_loc, v_loc, sentinel):
    """Edge-parallel relax of dst-owned edges against the full frontier.

    ``sc`` holds global source ids (frontier membership is a bit gather
    from the replicated frontier bitmap), ``dst_loc`` local owned slots
    (visited test against the resident owned words; scatter-min into the
    owned parent block).  The sharded sibling of :func:`_relax_edges`.
    """
    active = (vc & testbit(frontier_bm, jnp.clip(sc, 0, sentinel - 1))
              & ~testbit(visited_loc, jnp.clip(dst_loc, 0, v_loc - 1)))
    cand = jnp.where(active, sc, sentinel).astype(jnp.int32)
    tgt = jnp.where(active, dst_loc, v_loc)
    return parent_loc.at[tgt].min(cand)


def _run_bitmap_sharded(
    src: jax.Array,        # [n_chunks, chunk_size] int32 — global src ids
    dst_loc: jax.Array,    # [n_chunks, chunk_size] int32 — owned local slots
    valid: jax.Array,      # [n_chunks, chunk_size] bool
    src_lo: jax.Array,     # [n_chunks] int32
    src_hi: jax.Array,     # [n_chunks] int32
    degree_loc: jax.Array, # [V_loc] int32 — degree of owned vertices
    n_active: jax.Array,   # [] int32 — global
    root: jax.Array,       # [] int32 — global id
    core: HeavyCore | None,
    *,
    alpha: float,
    beta: float,
    use_core: bool,
    max_levels: int,
    use_pallas_core: bool,
    w_loc: int,
    n_dev: int,
    group_axis: str = "group",
    member_axis: str = "member",
    exchange: str = "hier_or",
    partition: str = "block",
    fault=None,
) -> BFSResult:
    """Vertex-sharded bitmap-resident BFS — runs INSIDE ``shard_map``.

    The sharded sibling of :func:`_run_bitmap_impl`: same invariants
    (I1–I4, DESIGN.md §3) with residency per owned word set and one
    hierarchical delta exchange per level (DESIGN.md §9).  ``partition``
    selects the word-granular owner map — contiguous ``block`` or the
    paper's eq.-(3) ``word_cyclic`` (device ``d`` owns words
    ``{w : w % P == d}``); all global↔local id arithmetic below goes
    through it.  Returns the shard's slice of the result (parent/level
    for owned vertices, shard-major — the plan runner restores global
    vertex order) plus replicated stats; parents are bitwise-identical
    to the single-device engine.
    """
    # Deferred import: distributed_bfs imports this module at load time,
    # but the owner-map arithmetic must stay ONE copy (shared with the
    # host partitioner and the reassembly permutation).
    from repro.core.distributed_bfs import owner_local_of

    axes = _axis_names_tuple(group_axis) + _axis_names_tuple(member_axis)
    v_loc = w_loc * 32
    v_pad = n_dev * v_loc          # sentinel (padded global vertex count)
    w_pad = n_dev * w_loc
    n_chunks = src.shape[0]
    dev = _shard_index(group_axis, member_axis)
    start = dev * v_loc
    cyclic = partition == "word_cyclic"

    def to_local(ids):
        """(is_mine, local slot) of global vertex ids on this shard."""
        owner, local = owner_local_of(ids, n_dev, w_loc, partition)
        return owner == dev, local

    def to_global(slots_loc):
        """Global vertex id of local slots on this shard (inverse of
        ``to_local`` for owned ids — it is parameterized by ``dev``, so
        it lives here rather than in ``owner_local_of``)."""
        if cyclic:
            return (dev + (slots_loc // 32) * n_dev) * 32 + slots_loc % 32
        return slots_loc + start

    with jax.named_scope("bfs.init"):
        # The root bit is set once; its owner holds parent/level/visited.
        is_mine, root_slot = to_local(root)
        slots = jnp.arange(v_loc, dtype=jnp.int32)
        parent_loc = jnp.where((slots == root_slot) & is_mine, root,
                               jnp.int32(v_pad))
        parent_loc = jnp.concatenate(
            [parent_loc, jnp.full((1,), v_pad, jnp.int32)])
        level_loc = jnp.where((slots == root_slot) & is_mine, 0, -1)
        level_loc = level_loc.astype(jnp.int32)
        root_bit = jnp.uint32(1) << (root % 32).astype(jnp.uint32)
        frontier_bm = jnp.zeros((w_pad,), jnp.uint32).at[root // 32].set(
            root_bit)
        word_slot = jnp.clip(root_slot // 32, 0, w_loc - 1)
        visited_loc = jnp.where(
            jnp.arange(w_loc) == word_slot,
            jnp.where(is_mine, root_bit, jnp.uint32(0)),
            jnp.uint32(0),
        )
        deg_root = jax.lax.psum(
            jnp.where(is_mine,
                      degree_loc[jnp.clip(root_slot, 0, v_loc - 1)],
                      0).astype(jnp.int32), axes)
        nnz_total = jax.lax.psum(jnp.sum(degree_loc).astype(jnp.int32),
                                 axes)

        # Bottom-up scans the owned chunks front-to-back; the dense core
        # covers (src < K) & (dst < K), so shards owning core rows drop
        # those edges from their tail.  Shard padding is a contiguous
        # per-chunk tail (shard_graph), so the all-invalid chunks (sentinel
        # src_hi = -1) form a suffix: BU relaxes only the live prefix — a
        # light shard of a skewed partition never scans its pure-padding
        # chunks (the chunk_range_mask kills the same chunks in TD).
        if use_core:
            dst_global = to_global(dst_loc)
            tail = valid & ~((src < core.k) & (dst_global < core.k))
        else:
            tail = valid
        n_live_chunks = jnp.sum(src_hi >= 0).astype(jnp.int32)

    def core_step(frontier, visited, parent):
        """Dense-core bottom-up: full-core SpMV (replicated work), winners
        applied to owned rows only (round-robin across shards under the
        word-cyclic partition — the heavy rows split P ways)."""
        k = core.k
        spmv = kops.core_spmv if use_pallas_core else core_spmv_ref
        cand = spmv(core.a_core, frontier[: k // 32])
        rows = jnp.arange(k, dtype=jnp.int32)
        owned, rloc = to_local(rows)
        rloc_c = jnp.clip(rloc, 0, v_loc - 1)
        won = (cand < BIG) & owned & ~testbit(visited, rloc_c)
        tgt = jnp.where(won, rloc_c, v_loc)
        return parent.at[tgt].min(
            jnp.where(won, cand, v_pad).astype(jnp.int32))

    def chunked_td(frontier, visited, parent):
        live = chunk_range_mask(src_lo, src_hi, frontier)

        def body(c, carry):
            def relax(carry):
                p, nsc = carry
                sc = jax.lax.dynamic_index_in_dim(src, c, 0, keepdims=False)
                dc = jax.lax.dynamic_index_in_dim(dst_loc, c, 0,
                                                  keepdims=False)
                vc = jax.lax.dynamic_index_in_dim(valid, c, 0, keepdims=False)
                p = _relax_owned_edges(sc, dc, vc, frontier, visited, p,
                                       v_loc, v_pad)
                return p, nsc + 1

            return jax.lax.cond(live[c], relax, lambda x: x, carry)

        return jax.lax.fori_loop(0, n_chunks, body, (parent, jnp.int32(0)))

    def cond(s: _ShardState):
        return (s.in_count > 0) & (s.lvl < max_levels)

    def body(s: _ShardState):
        with jax.named_scope("bfs.epilogue"):
            alive = s.in_count > 0   # batched-roots guard (vmap over roots)
            direction = _switch_direction(
                s.direction, s.in_count, s.vis_count, n_active, alpha, beta)
            bottom_up = direction == BOTTOM_UP

        def bu(_):
            if use_core:
                with jax.named_scope("bfs.bu_core"):
                    p1 = core_step(s.frontier_bm, s.visited_loc, s.parent_loc)
            else:
                p1 = s.parent_loc

            def body(c, p):
                sc = jax.lax.dynamic_index_in_dim(src, c, 0, keepdims=False)
                dc = jax.lax.dynamic_index_in_dim(dst_loc, c, 0,
                                                  keepdims=False)
                vc = jax.lax.dynamic_index_in_dim(tail, c, 0, keepdims=False)
                return _relax_owned_edges(
                    sc, dc, vc, s.frontier_bm, s.visited_loc, p, v_loc, v_pad)

            # Only the live-chunk prefix: BU frontiers are large so there
            # is nothing for *frontier*-range skipping to win, but a light
            # shard's padding suffix is dead for every frontier.
            with jax.named_scope("bfs.bu_relax"):
                p2 = jax.lax.fori_loop(0, n_live_chunks, body, p1)
            return p2, n_live_chunks

        def td(_):
            with jax.named_scope("bfs.td_relax"):
                return chunked_td(s.frontier_bm, s.visited_loc, s.parent_loc)

        new_parent, nsc = jax.lax.cond(bottom_up, bu, td, None)

        # Epilogue: pack the owned delta words (I3), OR-combine across the
        # mesh (T3 two-phase), fuse the owned-slice mask/merge/popcount.
        with jax.named_scope("bfs.epilogue"):
            newly = ((new_parent[:v_loc] != v_pad)
                     & (s.parent_loc[:v_loc] == v_pad))
            if fault is not None and fault.site == "parent":
                pv = faults.corrupt_parent(
                    fault, new_parent[:v_loc], newly, to_global(slots),
                    jnp.int32(v_pad), level=s.lvl, device=dev, root=root)
                new_parent = jnp.concatenate([pv, new_parent[v_loc:]])
            delta_loc = _pack_delta_words(newly, w_loc)
        with jax.named_scope("bfs.exchange"):
            next_bm = _exchange_delta(
                delta_loc, dev, w_loc, n_dev, exchange=exchange,
                group_axis=group_axis, member_axis=member_axis,
                partition=partition, fault=fault, level=s.lvl, root=root)
        with jax.named_scope("bfs.epilogue"):
            in_count = jnp.sum(popcount_u32(next_bm)).astype(jnp.int32)

            # In-loop sentinels (§13): exchange conservation (the combined
            # next frontier must carry exactly the bits the shards packed —
            # owner words are disjoint, so popcounts add), frontier ∩ visited
            # = ∅ over the owned slice, level bound.  A corrupted exchange
            # (dropped leg, flipped word) breaks one of the first two the
            # moment it fires.
            delta_sum = jax.lax.psum(
                jnp.sum(popcount_u32(delta_loc)).astype(jnp.int32), axes)
            if cyclic:
                own_next = jnp.take(next_bm.reshape(w_loc, n_dev), dev, axis=1)
            else:
                own_next = jax.lax.dynamic_slice(next_bm, (dev * w_loc,),
                                                 (w_loc,))
            overlap = jax.lax.psum(jnp.sum(
                popcount_u32(own_next & s.visited_loc)).astype(jnp.int32),
                axes)
            s1 = in_count == delta_sum
            s2 = overlap == 0
            s3 = s.lvl + 1 <= jnp.int32(max_levels)
            ok_mask = (s1.astype(jnp.int32) + 2 * s2.astype(jnp.int32)
                       + 4 * s3.astype(jnp.int32))
            if w_loc % WORDS_PER_TILE == 0:
                _, new_visited_loc, _ = kops.frontier_update(
                    delta_loc, s.visited_loc)
            else:
                # owned word blocks below the kernel tile: plain fused OR
                # (delta bits are never already-visited — owner exactness).
                new_visited_loc = s.visited_loc | delta_loc

            new_level = jnp.where(newly, s.lvl + 1, s.level_loc)
            m_next = jax.lax.psum(jnp.sum(
                jnp.where(newly, degree_loc, 0)).astype(jnp.int32), axes)
            nsc_all = jax.lax.psum(nsc, axes)

            m_u = nnz_total - s.deg_vis
            scanned = jnp.where(direction == TOP_DOWN, s.m_f,
                                m_u).astype(jnp.int32)

            nxt = _ShardState(
                new_parent, new_level, next_bm, new_visited_loc,
                in_count, s.vis_count + in_count,
                m_next, s.deg_vis + m_next,
                s.lvl + 1, direction,
                s.stats_dir.at[s.lvl].set(direction),
                s.stats_fs.at[s.lvl].set(s.in_count),
                s.stats_se.at[s.lvl].set(scanned),
                s.stats_ch.at[s.lvl].set(nsc_all),
                s.stats_ok.at[s.lvl].set(ok_mask),
            )
            return jax.tree_util.tree_map(
                lambda new, old: jnp.where(alive, new, old), nxt, s)

    init = _ShardState(
        parent_loc, level_loc, frontier_bm, visited_loc,
        jnp.int32(1), jnp.int32(1), deg_root, deg_root,
        jnp.int32(0), TOP_DOWN,
        jnp.full((max_levels,), -1, jnp.int32),
        jnp.zeros((max_levels,), jnp.int32),
        jnp.zeros((max_levels,), jnp.int32),
        jnp.full((max_levels,), -1, jnp.int32),
        jnp.full((max_levels,), -1, jnp.int32),
    )
    s = jax.lax.while_loop(cond, body, init)
    with jax.named_scope("bfs.finish"):
        parent = jnp.where(s.parent_loc[:v_loc] == v_pad, -1,
                           s.parent_loc[:v_loc])
    return BFSResult(
        parent=parent,
        level=s.level_loc,
        stats=BFSStats(
            s.stats_dir, s.stats_fs, s.stats_se, s.lvl,
            s.stats_ch, jnp.int32(n_chunks),
            s.stats_ok,
        ),
    )
