"""Delta-stepping SSSP engines (DESIGN.md §16) — the second Graph500 kernel.

Graph500's SSSP benchmark (kernel 3) runs single-source shortest paths
over the same Kronecker graph with edge weights uniform in [0, 1).  Here a
weight is an integer count of ``graph_build.WEIGHT_UNIT`` = 2**-20 in
``[1, 2**20 - 1]`` (``graph_build.edge_weights``), so every distance is
an exact uint32 sum and every tree edge adds at least one unit; the
engines return distances in that unit.  The traversal lifecycle is the
BFS one with three substitutions (the kernel interface of §16):

  * **state carrier** — the packed ``changed`` bitmap replaces the BFS
    frontier/visited pair, and a ``uint32`` distance plane rides along
    (``INF_U32`` = unreached); the per-round frontier is *derived*: the
    changed vertices in the minimum δ-bucket.
  * **relax rule** — each round runs one relax pass, pass A, which
    min-relaxes distances (``dist[v] <- min(dist[v], dist[u] + w)`` over
    frontier out-edges).  On one device it is a pull over the src-sorted
    rows: the graph and its weights are symmetric, so each row takes the
    min of ``dist[u] + w`` over its own slots to frontier ``u`` — one
    gather per slot and a dense segmented min, no scatter
    (:func:`_pull_dist`).  The vertex-sharded engine's slots are
    dst-owned, so there pass A stays a scatter-min push.  Parents are not
    tracked through the rounds:
    pass B runs once per search, after the round loop, and takes each
    vertex's parent as the *minimum source among edges achieving its
    final distance* — ``parent[v] = min{u : dist[u] + w(u,v) ==
    dist[v]}`` (:func:`_min_source_parents`).  Every weight is at least
    one unit, so those edges form a tree rooted at the root, and the
    parent is a pure function of the final distances, bitwise-checkable
    against the host Dijkstra oracle below.
  * **exchange combine** — distances combine across shards with the
    min-reduction family (``comms.hierarchical.hierarchical_pmin``, the
    T3 two-phase monitor shape), while the changed-set *delta* bitmap
    rides the existing OR family (the ``hier_or`` wiring, or ``flat``
    under the ``flat`` plan).

Bucket loop (label-correcting δ-stepping): each round pops the entire
minimum bucket ``b = min(dist // δ)`` over the changed set as the
frontier, relaxes all its out-edges (light and heavy together — no
settled/unsettled split), and re-enters every distance-improved vertex.
Improvements satisfy ``new_dist >= b*δ + 1``, so the bucket index is
monotone non-decreasing (sentinel s1) and termination follows from
integer distances decreasing monotonically per vertex.

Parents stay global vertex ids with the BFS sentinel conventions and the
distance plane is surfaced through the ``BFSResult.level`` slot as int32
counts of ``WEIGHT_UNIT`` (-1 unreached), so validation, serving, fault
recovery, and the multiprocess launcher run the SSSP kernel through the
exact machinery built for BFS.  ``BFSStats.levels`` counts the rounds and
``scanned_edges`` each round's frontier degree.

The engines name each phase of a round with ``jax.named_scope``, so a
device trace charges every op to one: ``sssp.init``, ``sssp.select``
(the changed set's minimum bucket gives the frontier; the next changed
set and the round's counters), ``sssp.relax`` (pass A: the pull, or the
sharded engine's push),
``sssp.exchange`` (vertex-sharded engine only) in the round loop, and
after it ``sssp.parent`` (pass B, once per search) and ``sssp.finish``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

# Module binding, not names: comms.hierarchical imports repro.core for its
# fault hooks, so pulling names out of it at import time would read a
# partially initialized module whenever `repro.comms` is imported first.
from repro.comms import hierarchical as _hier
from repro.core import faults
from repro.core.bfs_steps import ChunkedEdgeView
from repro.core.heavy import padded_bitmap_words, testbit
from repro.core.hybrid_bfs import (
    BFSResult,
    BFSStats,
    _axis_names_tuple,
    _exchange_delta,
    _pack_delta_words,
    _pull_rows,
    _row_ends,
    _shard_index,
)
from repro.kernels.ref import popcount_u32

#: Exchange wirings of the SSSP kernel: ``hier_min`` is the T3 two-phase
#: min-reduction for distances + hierarchical OR for the changed
#: delta; ``flat`` is the single-phase ablation baseline for both legs.
SSSP_EXCHANGES = ("hier_min", "flat")

#: Round bound: δ-stepping takes more rounds than BFS takes levels (one
#: bucket can re-iterate over light-edge chains), so the engine sizes its
#: stats/bound at least this high regardless of ``plan.max_levels``.
DEFAULT_MAX_ROUNDS = 512


def bucket_width(max_weight: int) -> int:
    """The δ of δ-stepping, chosen host-side from the max edge weight
    (in units of ``WEIGHT_UNIT``: about 2**19, so δ is about one half).

    ``δ = max(1, maxw // 2)`` keeps the bucket count proportional to the
    weighted diameter in units of the heaviest edge — small enough that
    bucket scans stay cheap, large enough that light-edge re-iteration
    within a bucket stays shallow.  Static under jit (a compile-time
    constant of the plan).
    """
    return max(1, int(max_weight) // 2)


def sssp_max_rounds(max_levels: int) -> int:
    """Engine round bound for a plan's ``max_levels`` (never below the
    δ-stepping default — BFS levels underestimate SSSP rounds)."""
    return max(int(max_levels), DEFAULT_MAX_ROUNDS)


def _min_source_parents(src, dst, valid, wgt, dist_src_ext, dist_dst_ext,
                        sentinel):
    """Pass B: each slot's parent from the final distances, once per search.

    ``parent[d] = min{src : dist[src] + w == dist[d]}`` over the valid
    slots whose source is reached (``dist[src] != INF``: masked before the
    add, which would wrap in uint32).  ``dist_src_ext`` is indexed by
    ``src`` (global ids), ``dist_dst_ext`` by ``dst`` (the slots this
    engine owns); each ends in one ``INF`` pad slot for the padding
    index.  Returns int32 parents over ``dist_dst_ext``'s slots, the
    root's and every unreached slot's left at ``sentinel``: no source
    wins the root, since its distance is 0 and every weight is at least 1.
    """
    n = dist_dst_ext.shape[0] - 1
    ds = dist_src_ext[src]
    won = valid & (ds != jnp.uint32(_hier.INF_U32)) & (
        ds + wgt == dist_dst_ext[dst])
    parent = jnp.full((n + 1,), sentinel, jnp.int32).at[
        jnp.where(won, dst, n)].min(jnp.where(won, src, sentinel))
    return parent[:n]


def _pull_dist(chunks: ChunkedEdgeView, row_end, front, dist):
    """Pass A as a pull over the src-sorted rows: each row ``x`` takes
    ``min(dist[x], min{dist[u] + w(x, u) : u in front, slot (x, u)})``.

    The graph is symmetric and so are its weights (the build symmetrizes
    and dedupes the edges, ``graph_build.edge_weights`` hashes the
    unordered pair), so this equals the push of ``dist[u] + w`` over the
    frontier's out-edges scatter-min'd into ``dist[v]``, bit for bit.
    The frontier masks the distance plane once (``INF`` off it, and at
    the padding slots' ``dst`` = V); then each slot does one gather from
    it, and the min over each row is :func:`hybrid_bfs._pull_rows`'
    dense segmented scan, with no scatter.
    """
    inf = jnp.uint32(_hier.INF_U32)
    dist_front = jnp.concatenate(
        [jnp.where(front, dist, inf), jnp.full((1,), inf)])

    def slot_val(s2, d2, v2, w2):
        g = dist_front[d2]
        # masked before the add, which would wrap in uint32
        return jnp.where(v2 & (g != inf), g + w2, inf)

    return jnp.minimum(dist, _pull_rows(
        chunks, (chunks.dst, chunks.valid, chunks.weight), slot_val, inf,
        row_end))


# ---------------------------------------------------------------------------
# Single-device engine.
# ---------------------------------------------------------------------------

class _SsspState(NamedTuple):
    dist: jax.Array         # [V] uint32 — tentative distances, INF_U32 unreached
    changed_bm: jax.Array   # [W] uint32 — packed changed set (re-entries live)
    n_changed: jax.Array    # [] int32 — popcount(changed_bm)
    prev_b: jax.Array       # [] uint32 — last round's bucket (monotonicity s1)
    rnd: jax.Array          # [] int32 — round counter
    stats_b: jax.Array      # [max_rounds] int32 — bucket index per round
    stats_fs: jax.Array     # [max_rounds] int32 — frontier popcount
    stats_se: jax.Array     # [max_rounds] int32 — frontier degree sum
    stats_ok: jax.Array     # [max_rounds] int32 — sentinel masks (§13)


def _run_sssp_impl(
    chunks: ChunkedEdgeView,
    degree: jax.Array,
    root: jax.Array,
    *,
    delta: int,
    max_rounds: int,
    fault=None,
) -> BFSResult:
    """One δ-stepping SSSP from ``root`` (single device, pull relax).

    SSSP frontiers are thin slices of one δ-bucket, but *which* chunk a
    bucket touches is weight-dependent, not degree-ordered — so pass A
    reads every slot every round, as a pull over the src-sorted rows of
    the chunked view (:func:`_pull_dist`, the BFS bottom-up's scan):
    one gather of the frontier-masked distance plane per slot and a
    dense segmented min per row, no scatter.  Pass B, once per search,
    reads the flat view.  The heavy core is not consulted: the
    dense-corner SpMV is a boolean-semiring step with no weight plane.
    """
    assert chunks.weight is not None, "SSSP needs a weighted ChunkedEdgeView"
    v = chunks.num_vertices
    w = padded_bitmap_words(v)
    d32 = jnp.uint32(delta)
    inf = jnp.uint32(_hier.INF_U32)
    with jax.named_scope("sssp.init"):
        src = chunks.src.reshape(-1)
        dst = chunks.dst.reshape(-1)
        valid = chunks.valid.reshape(-1)
        wgt = chunks.weight.reshape(-1)
        ids = jnp.arange(v, dtype=jnp.int32)
        row_end = _row_ends(degree)   # for the pull

        dist = jnp.full((v,), _hier.INF_U32, jnp.uint32).at[root].set(
            jnp.uint32(0))
        root_bit = jnp.uint32(1) << (root % 32).astype(jnp.uint32)
        changed_bm = jnp.zeros((w,), jnp.uint32).at[root // 32].set(root_bit)

    def cond(s: _SsspState):
        return (s.n_changed > 0) & (s.rnd < max_rounds)

    def body(s: _SsspState):
        alive = s.n_changed > 0   # batched-roots guard (vmap over roots)

        # Derive the frontier: changed vertices in the minimum bucket.
        with jax.named_scope("sssp.select"):
            changed = testbit(s.changed_bm, ids)
            bkt = jnp.where(changed, s.dist // d32, inf)
            b = jnp.min(bkt)
            front = changed & (bkt == b)
            frontier_bm = _pack_delta_words(front, w)
            popped_bm = s.changed_bm & ~frontier_bm

        # Pass A: each row pulls its distance over its frontier neighbours.
        with jax.named_scope("sssp.relax"):
            new_dist = _pull_dist(chunks, row_end, front, s.dist)
            improved = new_dist < s.dist

        # The next round's changed set: the popped rest plus every
        # improved vertex; then the round's counters.
        with jax.named_scope("sssp.select"):
            new_changed = popped_bm | _pack_delta_words(improved, w)
            n_changed = jnp.sum(popcount_u32(new_changed)).astype(jnp.int32)

            # In-loop sentinels (§13): bucket monotone, frontier
            # nonempty, round within bound — a healthy round reads
            # SENTINEL_OK == 7.
            fs = jnp.sum(popcount_u32(frontier_bm)).astype(jnp.int32)
            s1 = b >= s.prev_b
            s2 = fs > 0
            s3 = s.rnd + 1 <= jnp.int32(max_rounds)
            ok_mask = (s1.astype(jnp.int32) + 2 * s2.astype(jnp.int32)
                       + 4 * s3.astype(jnp.int32))
            scanned = jnp.sum(jnp.where(front, degree, 0)).astype(jnp.int32)
            b_i32 = jnp.minimum(b, jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)

            nxt = _SsspState(
                new_dist, new_changed, n_changed, b,
                s.rnd + 1,
                s.stats_b.at[s.rnd].set(b_i32),
                s.stats_fs.at[s.rnd].set(fs),
                s.stats_se.at[s.rnd].set(scanned),
                s.stats_ok.at[s.rnd].set(ok_mask),
            )
            return jax.tree_util.tree_map(
                lambda new, old: jnp.where(alive, new, old), nxt, s)

    with jax.named_scope("sssp.init"):
        init = _SsspState(
            dist, changed_bm,
            jnp.int32(1), jnp.uint32(0), jnp.int32(0),
            jnp.full((max_rounds,), -1, jnp.int32),
            jnp.zeros((max_rounds,), jnp.int32),
            jnp.zeros((max_rounds,), jnp.int32),
            jnp.full((max_rounds,), -1, jnp.int32),
        )
    s = jax.lax.while_loop(cond, body, init)

    # Pass B, once per search: parent = min source achieving the final
    # distance.
    with jax.named_scope("sssp.parent"):
        dist_ext = jnp.concatenate([s.dist, jnp.full((1,), inf)])
        parent = _min_source_parents(
            src, dst, valid, wgt, dist_ext, dist_ext, v).at[root].set(root)
        if fault is not None and fault.site == "parent":
            parent = faults.corrupt_parent(
                fault, parent, (s.dist != inf) & (ids != root), ids,
                jnp.int32(v), level=s.rnd - 1, root=root)
    with jax.named_scope("sssp.finish"):
        parent = jnp.where(parent == v, -1, parent)
        dist_i = jnp.where(s.dist == inf, -1, s.dist.astype(jnp.int32))
        return BFSResult(
            parent=parent,
            level=dist_i,   # the distance plane rides the level slot (int32)
            stats=BFSStats(
                s.stats_b, s.stats_fs, s.stats_se, s.rnd,
                jnp.full((max_rounds,), -1, jnp.int32), jnp.int32(0),
                s.stats_ok,
            ),
        )


_SSSP_STATICS = ("delta", "max_rounds", "fault")

_run_sssp = functools.partial(
    jax.jit, static_argnames=_SSSP_STATICS,
)(_run_sssp_impl)


@functools.partial(jax.jit, static_argnames=_SSSP_STATICS)
def _run_sssp_batch(chunks, degree, roots, *, delta, max_rounds, fault=None):
    """All search keys under ONE jitted program (vmap over roots)."""
    return jax.vmap(
        lambda r: _run_sssp_impl(
            chunks, degree, r, delta=delta, max_rounds=max_rounds,
            fault=fault)
    )(roots)


# ---------------------------------------------------------------------------
# Vertex-sharded engine — runs INSIDE shard_map (the sibling of
# hybrid_bfs._run_bitmap_sharded, with the kernel interface substitutions).
#
# Replication discipline: the distance plane is held FULL-WIDTH and
# replicated (like the BFS frontier bitmap) — bucket selection is then a
# pure local computation every round, no extra collective.  Each shard
# relaxes the edges whose destination it owns, so distance improvements
# land only in owned slots; one min-reduction reassembles the replicated
# plane and one OR exchange reassembles the changed-set delta.
# ---------------------------------------------------------------------------

class _SsspShardState(NamedTuple):
    dist_full: jax.Array    # [V_pad] uint32 — replicated distance plane
    changed_bm: jax.Array   # [W_pad] uint32 — replicated changed set
    n_changed: jax.Array    # [] int32
    prev_b: jax.Array       # [] uint32
    rnd: jax.Array
    stats_b: jax.Array
    stats_fs: jax.Array
    stats_se: jax.Array
    stats_ok: jax.Array


def _run_sssp_sharded(
    src: jax.Array,        # [n_chunks, chunk_size] int32 — global src ids
    dst_loc: jax.Array,    # [n_chunks, chunk_size] int32 — owned local slots
    valid: jax.Array,      # [n_chunks, chunk_size] bool
    weight: jax.Array,     # [n_chunks, chunk_size] uint32
    degree_loc: jax.Array, # [V_loc] int32 — degree of owned vertices
    root: jax.Array,       # [] int32 — global id
    *,
    delta: int,
    max_rounds: int,
    w_loc: int,
    n_dev: int,
    group_axis: str = "group",
    member_axis: str = "member",
    exchange: str = "hier_min",
    partition: str = "block",
    fault=None,
) -> BFSResult:
    """Vertex-sharded δ-stepping SSSP — runs INSIDE ``shard_map``.

    Returns the shard's slice of the result (parent/distance for owned
    vertices, shard-major — the plan runner restores global vertex
    order) plus replicated stats; parents and distances are bitwise-
    identical to the single-device engine for every exchange wiring.
    """
    from repro.core.distributed_bfs import owner_local_of

    if exchange not in SSSP_EXCHANGES:
        raise ValueError(f"unknown SSSP exchange {exchange!r}; expected "
                         f"one of {SSSP_EXCHANGES}")
    axes = _axis_names_tuple(group_axis) + _axis_names_tuple(member_axis)
    v_loc = w_loc * 32
    v_pad = n_dev * v_loc
    w_pad = n_dev * w_loc
    d32 = jnp.uint32(delta)
    inf = jnp.uint32(_hier.INF_U32)
    dev = _shard_index(group_axis, member_axis)
    start = dev * v_loc
    cyclic = partition == "word_cyclic"

    def to_local(gids):
        owner, local = owner_local_of(gids, n_dev, w_loc, partition)
        return owner == dev, local

    def to_global(slots_loc):
        if cyclic:
            return (dev + (slots_loc // 32) * n_dev) * 32 + slots_loc % 32
        return slots_loc + start

    src_flat = src.reshape(-1)
    dst_flat = dst_loc.reshape(-1)
    valid_flat = valid.reshape(-1)
    wgt_flat = weight.reshape(-1)
    slots = jnp.arange(v_loc, dtype=jnp.int32)
    gslots = to_global(slots)
    ids_full = jnp.arange(v_pad, dtype=jnp.int32)

    # The changed-set delta rides the OR exchange family over the same
    # hop structure as the distances: two-phase under ``hier_min``,
    # single-phase under ``flat``.  Owner deltas are disjoint, so the OR
    # is exact whichever wiring carries it.
    delta_wire = "flat" if exchange == "flat" else "hier_or"

    # --- init: root bit set once.
    with jax.named_scope("sssp.init"):
        dist_full = jnp.full((v_pad,), _hier.INF_U32, jnp.uint32).at[
            root].set(jnp.uint32(0))
        root_bit = jnp.uint32(1) << (root % 32).astype(jnp.uint32)
        changed_bm = jnp.zeros((w_pad,), jnp.uint32).at[root // 32].set(
            root_bit)

    def cond(s: _SsspShardState):
        return (s.n_changed > 0) & (s.rnd < max_rounds)

    def body(s: _SsspShardState):
        alive = s.n_changed > 0

        # Bucket selection is replicated work on replicated state — every
        # shard computes the same frontier with zero communication.
        with jax.named_scope("sssp.select"):
            changed = testbit(s.changed_bm, ids_full)
            bkt = jnp.where(changed, s.dist_full // d32, inf)
            b = jnp.min(bkt)
            front_full = changed & (bkt == b)
            frontier_bm = _pack_delta_words(front_full, w_pad)
            popped_bm = s.changed_bm & ~frontier_bm

        # Pass A over dst-owned edges: frontier membership from the
        # replicated bitmap, distance scatter-min into owned slots.
        with jax.named_scope("sssp.relax"):
            dist_loc = s.dist_full[gslots]
            dist_ext = jnp.concatenate(
                [s.dist_full, jnp.full((1,), _hier.INF_U32, jnp.uint32)])
            active = valid_flat & testbit(
                frontier_bm, jnp.clip(src_flat, 0, v_pad - 1))
            cand = jnp.where(
                active, dist_ext[jnp.clip(src_flat, 0, v_pad)] + wgt_flat,
                inf)
            tgt = jnp.where(active, dst_flat, v_loc)
            dist_loc_ext = jnp.concatenate(
                [dist_loc, jnp.full((1,), _hier.INF_U32, jnp.uint32)])
            new_dist_loc_ext = dist_loc_ext.at[tgt].min(cand)
            new_dist_loc = new_dist_loc_ext[:v_loc]
            improved_loc = new_dist_loc < dist_loc

        with jax.named_scope("sssp.exchange"):
            # Exchange 1 — distance plane: owner slots carry the new
            # values, everyone else contributes INF; the min-reduction
            # reassembles the replicated plane (T3 two-phase under
            # hier_min).
            contrib = jnp.full((v_pad,), _hier.INF_U32, jnp.uint32).at[
                gslots].set(new_dist_loc)
            if exchange == "flat":
                new_dist_full = _hier._min_all_reduce(
                    contrib, axes, fault=fault, level=s.rnd, device=dev,
                    root=root)
            else:
                new_dist_full = _hier.hierarchical_pmin(
                    contrib, group_axis, member_axis, fault=fault,
                    level=s.rnd, device=dev, root=root)

            # Exchange 2 — changed-set delta bitmap (OR family).
            delta_bm_loc = _pack_delta_words(improved_loc, w_loc)
            changed_delta_full = _exchange_delta(
                delta_bm_loc, dev, w_loc, n_dev, exchange=delta_wire,
                group_axis=group_axis, member_axis=member_axis,
                partition=partition, fault=fault, level=s.rnd, root=root)

        with jax.named_scope("sssp.select"):
            new_changed = popped_bm | changed_delta_full
            n_changed = jnp.sum(popcount_u32(new_changed)).astype(jnp.int32)

            # In-loop sentinels (§13): exchange conservation (owner
            # deltas are disjoint, popcounts add), replicated-vs-owned
            # distance agreement (a dropped min leg desynchronizes the
            # plane), bucket monotone within the round bound.
            delta_sum = jax.lax.psum(
                jnp.sum(popcount_u32(delta_bm_loc)).astype(jnp.int32), axes)
            got_sum = jnp.sum(
                popcount_u32(changed_delta_full)).astype(jnp.int32)
            mism = jax.lax.psum(
                jnp.sum((new_dist_full[gslots] != new_dist_loc)
                        .astype(jnp.int32)), axes)
            s1 = got_sum == delta_sum
            s2 = mism == 0
            s3 = (b >= s.prev_b) & (s.rnd + 1 <= jnp.int32(max_rounds))
            ok_mask = (s1.astype(jnp.int32) + 2 * s2.astype(jnp.int32)
                       + 4 * s3.astype(jnp.int32))

            fs = jnp.sum(popcount_u32(frontier_bm)).astype(jnp.int32)
            front_owned = testbit(frontier_bm, gslots)
            scanned = jax.lax.psum(
                jnp.sum(jnp.where(front_owned, degree_loc, 0))
                .astype(jnp.int32), axes)
            b_i32 = jnp.minimum(b, jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)

            nxt = _SsspShardState(
                new_dist_full, new_changed, n_changed, b,
                s.rnd + 1,
                s.stats_b.at[s.rnd].set(b_i32),
                s.stats_fs.at[s.rnd].set(fs),
                s.stats_se.at[s.rnd].set(scanned),
                s.stats_ok.at[s.rnd].set(ok_mask),
            )
            return jax.tree_util.tree_map(
                lambda new, old: jnp.where(alive, new, old), nxt, s)

    with jax.named_scope("sssp.init"):
        init = _SsspShardState(
            dist_full, changed_bm,
            jnp.int32(1), jnp.uint32(0), jnp.int32(0),
            jnp.full((max_rounds,), -1, jnp.int32),
            jnp.zeros((max_rounds,), jnp.int32),
            jnp.zeros((max_rounds,), jnp.int32),
            jnp.full((max_rounds,), -1, jnp.int32),
        )
    s = jax.lax.while_loop(cond, body, init)

    # Pass B, once per search, over the dst-owned slots: dist[src] from
    # the replicated plane, dist[dst] from the owned slice.
    with jax.named_scope("sssp.parent"):
        dist_own = s.dist_full[gslots]
        is_mine, root_slot = to_local(root)
        parent = _min_source_parents(
            jnp.clip(src_flat, 0, v_pad), dst_flat, valid_flat, wgt_flat,
            jnp.concatenate([s.dist_full, jnp.full((1,), inf)]),
            jnp.concatenate([dist_own, jnp.full((1,), inf)]), v_pad)
        parent = jnp.where((slots == root_slot) & is_mine, root, parent)
        if fault is not None and fault.site == "parent":
            parent = faults.corrupt_parent(
                fault, parent, (dist_own != inf) & (gslots != root), gslots,
                jnp.int32(v_pad), level=s.rnd - 1, device=dev, root=root)
    with jax.named_scope("sssp.finish"):
        parent = jnp.where(parent == v_pad, -1, parent)
        dist_i = jnp.where(dist_own == inf, -1,
                           dist_own.astype(jnp.int32))
        return BFSResult(
            parent=parent,
            level=dist_i,
            stats=BFSStats(
                s.stats_b, s.stats_fs, s.stats_se, s.rnd,
                jnp.full((max_rounds,), -1, jnp.int32), jnp.int32(0),
                s.stats_ok,
            ),
        )


# ---------------------------------------------------------------------------
# Host reference oracle — the bitwise ground truth of tests/test_sssp.py.
# ---------------------------------------------------------------------------

def sssp_oracle(src, dst, valid, weight, num_vertices: int, root: int):
    """Host Dijkstra + deterministic min-source parents.

    Returns ``(parent, dist)`` int32 numpy arrays matching the engine's
    output contract exactly: ``dist`` in the weights' integer unit, -1 for
    unreached, ``parent`` -1 for
    unreached / root's parent is itself; for every reached non-root
    vertex ``parent[v] = min{u : dist[u] + w(u,v) == dist[v]}`` — the
    engines' fixpoint parent rule, so equality is bitwise.
    """
    import heapq

    import numpy as np

    s = np.asarray(src)
    d = np.asarray(dst)
    va = np.asarray(valid)
    w = np.asarray(weight)
    s = s[va].astype(np.int64)
    d = d[va].astype(np.int64)
    w = w[va].astype(np.int64)

    order = np.argsort(s, kind="stable")
    s2, d2, w2 = s[order], d[order], w[order]
    starts = np.searchsorted(s2, np.arange(num_vertices + 1))

    inf = np.iinfo(np.int64).max
    dist = np.full(num_vertices, inf, np.int64)
    dist[root] = 0
    settled = np.zeros(num_vertices, bool)
    heap = [(0, int(root))]
    while heap:
        du, u = heapq.heappop(heap)
        if settled[u]:
            continue
        settled[u] = True
        for i in range(int(starts[u]), int(starts[u + 1])):
            vtx = int(d2[i])
            nd = du + int(w2[i])
            if nd < dist[vtx]:
                dist[vtx] = nd
                heapq.heappush(heap, (nd, vtx))

    reached_src = dist[s] != inf
    cand = np.where(reached_src, dist[s] + w, inf)
    wins = (cand == dist[d]) & (dist[d] != inf)
    parent = np.full(num_vertices, inf, np.int64)
    np.minimum.at(parent, d[wins], s[wins])
    parent = np.where(dist == inf, -1,
                      np.where(parent == inf, -1, parent))
    parent[root] = root
    dist_out = np.where(dist == inf, -1, dist).astype(np.int32)
    return parent.astype(np.int32), dist_out
