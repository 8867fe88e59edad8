"""Distributed hybrid BFS over a (group, member) device mesh (T2 + T3).

This module is a thin host-side wrapper around the vertex-sharded
bitmap-resident engine (``core.hybrid_bfs._run_bitmap_sharded``,
DESIGN.md §9).  The original engine here carried its own level loop with
a pack-per-level frontier exchange (``pack_bitmap`` of a bool vector
inside the loop body, cyclic vertex ownership, owner-major id
translation on every edge every level); that loop is retired — the
resident engine keeps all state packed across the whole traversal and
the per-level exchange is the bitwise-OR two-phase monitor collective.

Partitioning (paper §4.2): TWO word-granular vertex ownership maps,
selected by the plan's ``partition`` axis (DESIGN.md §9):

  * ``"block"``       — device ``d`` (flat group-major mesh index) owns
    the contiguous words ``[d*W_loc, (d+1)*W_loc)``, i.e. vertices
    ``[d*W_loc*32, (d+1)*W_loc*32)``.  The reduce-scatter shard of the
    two-phase collective IS the owner's resident block and global
    reassembly is a concatenation — but after the T2a degree sort the
    heavy prefix lands entirely on shard 0.
  * ``"word_cyclic"`` — the paper's eq. (3) cyclic ``owner(v) = v % P``
    lifted to uint32-word granularity: device ``d`` owns words
    ``{w : w % P == d}`` (local word ``j`` is global word ``d + j*P``).
    Heavy words interleave round-robin across shards, so the
    degree-sorted prefix (and the dense-core rows inside it) load-
    balances while packed-word arithmetic and the I3 delta pack stay
    untouched.  Global reassembly applies the inverse word permutation
    (:func:`partition_permutation`, one gather at traversal exit).

Edges are partitioned by **destination owner** (bottom-up orientation:
each device relaxes the edges pointing at its own vertices) and kept
src-sorted + chunked per shard so small frontiers skip most of the scan.

Exercised three ways:
  * tests/test_distributed.py + tests/test_sharded.py run it on host
    device meshes (subprocess);
  * benchmarks/bfs_sharded.py ladders it over mesh shapes;
  * launch/dryrun.py's graph500 rows lower the same engine shape-only on
    the 256/512-chip production meshes (core/plan.py's
    ``vertex_sharded_program`` is the shared shard_map wiring).

The engine runs through the plan API (``TraversalPlan(layout=("group",
"member"))`` — DESIGN.md §10); this module keeps the host-side
partitioner (``shard_graph``) and its owner-map helpers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bfs_steps import DEFAULT_CHUNKS
from repro.core.heavy import padded_bitmap_words
from repro.util import pytree_dataclass

PARTITIONS = ("block", "word_cyclic")


@pytree_dataclass(meta=("num_vertices", "v_orig", "n_devices", "n_chunks",
                        "chunk_size", "w_loc", "partition"))
class ShardedGraph:
    """Dst-owned, per-shard-chunked edge partition.

    ``partition`` names the word-granular vertex ownership map (block vs
    word-cyclic, see the module docstring).  ``num_vertices`` is the
    padded global count ``P * W_loc * 32``; ids in
    ``[v_orig, num_vertices)`` never appear in edges and stay unvisited.
    """

    src: jax.Array           # [P, n_chunks, chunk_size] int32 global src ids
    dst_local: jax.Array     # [P, n_chunks, chunk_size] int32 owned local slot
    valid: jax.Array         # [P, n_chunks, chunk_size] bool
    src_lo: jax.Array        # [P, n_chunks] int32 — min valid src per chunk
    src_hi: jax.Array        # [P, n_chunks] int32 — max valid src (-1 empty)
    degree_local: jax.Array  # [P, V_loc] int32 degree of owned vertices
    n_active: jax.Array      # [] int32 — global non-isolated vertex count
    num_vertices: int        # padded global V (= P * W_loc * 32)
    v_orig: int              # true vertex count before padding
    n_devices: int
    n_chunks: int
    chunk_size: int
    w_loc: int               # bitmap words owned per device
    partition: str = "block"
    # [P, n_chunks, chunk_size] uint32 per-edge weights (SSSP kernel);
    # None on unweighted BFS shards — an empty pytree subtree, so every
    # existing BFS shard_map program keeps its exact signature.
    weight: jax.Array | None = None


def owner_local_of(v, n_devices: int, w_loc: int, partition: str):
    """(owner, local slot) of global vertex ids ``v`` under ``partition``.

    Pure integer arithmetic shared by the host partitioner, the inverse
    reassembly permutation, and the tests — works on numpy or jnp arrays.
    Block: ``owner = v // V_loc``; word-cyclic (paper eq. (3) at uint32-word
    granularity): ``owner = (v // 32) % P``, local word ``(v // 32) // P``.
    """
    if partition not in PARTITIONS:
        raise ValueError(
            f"unknown partition {partition!r}; expected one of {PARTITIONS}")
    v_loc = w_loc * 32
    if partition == "block":
        owner = v // v_loc
        return owner, v - owner * v_loc
    word = v // 32
    return word % n_devices, (word // n_devices) * 32 + v % 32


def partition_permutation(n_devices: int, w_loc: int,
                          partition: str) -> "np.ndarray":
    """Gather indices restoring global vertex order from the shard-major
    concatenation of per-shard outputs.

    ``concat[owner(g) * V_loc + local(g)]`` holds vertex ``g``, so
    ``concat[perm]`` is in global order with ``perm[g] = owner(g) * V_loc
    + local(g)``.  Identity for the block partition (reassembly is a
    concatenation there); a strided word permutation for word-cyclic.
    """
    import numpy as np

    g = np.arange(n_devices * w_loc * 32, dtype=np.int32)
    owner, local = owner_local_of(g, n_devices, w_loc, partition)
    return (owner * (w_loc * 32) + local).astype(np.int32)


def shard_edge_skew(sg: ShardedGraph) -> dict:
    """Per-shard edge-count balance metric recorded in BENCH rung
    metadata: ``max_over_mean`` is 1.0 for a perfectly balanced partition
    and grows with the heavy-prefix skew the block layout suffers after
    the degree sort (the padded edge width is ``counts.max()``, so this
    ratio IS the padding overhead of the light shards)."""
    import numpy as np

    counts = np.asarray(sg.valid).sum(axis=(1, 2))
    mean = float(counts.mean()) if counts.size else 0.0
    return {
        "per_shard_edges": [int(c) for c in counts],
        "max": int(counts.max()) if counts.size else 0,
        "mean": mean,
        "max_over_mean": float(counts.max() / mean) if mean else 0.0,
    }


def shard_graph(src, dst, valid, num_vertices: int, n_devices: int,
                n_chunks: int = DEFAULT_CHUNKS,
                partition: str = "block", weight=None) -> ShardedGraph:
    """Host-side partitioner: word-granular vertex ownership (``block`` or
    ``word_cyclic``), dst-owner edge split, per-shard src-sorted chunks
    with source ranges.  ``weight`` (optional [E_pad] uint32) rides the
    same per-shard boolean select as the edges themselves."""
    import numpy as np

    if partition not in PARTITIONS:
        raise ValueError(
            f"unknown partition {partition!r}; expected one of {PARTITIONS}")
    p = n_devices
    w_base = padded_bitmap_words(num_vertices)
    w_loc = -(-w_base // p)
    v_loc = w_loc * 32
    v_pad = p * v_loc
    src = np.asarray(src)
    dst = np.asarray(dst)
    valid = np.asarray(valid)
    weight = None if weight is None else np.asarray(weight, np.uint32)
    dst_owner, dst_slot = owner_local_of(dst, p, w_loc, partition)
    owner = np.where(valid, dst_owner, p)
    counts = np.bincount(owner[valid], minlength=p)[:p]
    e_loc = int(counts.max()) if counts.size else 1
    chunk_size = max(128, -(-e_loc // n_chunks))
    e_pad = n_chunks * chunk_size

    s = np.full((p, e_pad), v_pad, np.int32)
    dl = np.zeros((p, e_pad), np.int32)
    va = np.zeros((p, e_pad), bool)
    wt = None if weight is None else np.zeros((p, e_pad), np.uint32)
    for pe in range(p):
        sel = valid & (owner == pe)
        k = int(sel.sum())
        # csr_to_edge_arrays emits (src, dst)-sorted edges; the boolean
        # select preserves that order, so each shard's slice stays
        # src-sorted and contiguous chunks cover contiguous src bands.
        # Padding is a contiguous per-shard TAIL: all-invalid chunks
        # carry the sentinels src_lo = v_pad, src_hi = -1, so live
        # chunks form a prefix (the engine's BU scan stops there).
        s[pe, :k] = src[sel]
        dl[pe, :k] = dst_slot[sel]
        va[pe, :k] = True
        if wt is not None:
            wt[pe, :k] = weight[sel]
    s = s.reshape(p, n_chunks, chunk_size)
    dl = dl.reshape(p, n_chunks, chunk_size)
    va = va.reshape(p, n_chunks, chunk_size)
    if wt is not None:
        wt = wt.reshape(p, n_chunks, chunk_size)
    src_lo = np.where(va, s, v_pad).min(axis=2).astype(np.int32)
    src_hi = np.where(va, s, -1).max(axis=2).astype(np.int32)

    deg = np.zeros((p, v_loc), np.int32)
    np.add.at(deg, (owner[valid], dst_slot[valid]), 1)
    # Non-isolated count over BOTH endpoints: a vertex with only outgoing
    # edges has no dst entry but still participates in the traversal (the
    # single-device engines count it via degree > 0, and the eq. (1)/(2)
    # direction switch diverges if the shards disagree on |V_active|).
    ends = np.concatenate([src[valid], dst[valid]])
    n_active = int((np.bincount(ends, minlength=num_vertices) > 0).sum())
    return ShardedGraph(
        src=jnp.asarray(s), dst_local=jnp.asarray(dl), valid=jnp.asarray(va),
        src_lo=jnp.asarray(src_lo), src_hi=jnp.asarray(src_hi),
        degree_local=jnp.asarray(deg), n_active=jnp.int32(n_active),
        num_vertices=v_pad, v_orig=num_vertices, n_devices=p,
        n_chunks=n_chunks, chunk_size=chunk_size, w_loc=w_loc,
        partition=partition,
        weight=None if wt is None else jnp.asarray(wt),
    )
