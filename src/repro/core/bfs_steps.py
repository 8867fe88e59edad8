"""Single BFS level steps (pure-JAX reference engines).

A BFS level over an undirected graph is a Boolean-semiring SpMV
(DESIGN.md §2). With JAX's static-shape constraint the natural TPU-native
formulation is *edge-parallel relaxation*: every directed CSR entry
``(u -> v)`` tests ``frontier[u] & ~visited[v]`` and scatter-mins its source
into ``parent[v]`` — the push, which these reference steps and the bitmap
engine's top-down use.  The bitmap engine's bottom-up reads the same
slots as rows instead: the CSR is symmetric and src-sorted, so each
unvisited ``x`` takes the min frontier ``u`` over its own contiguous
slots ``(x, u)`` — a pull with one bit gather per slot and a dense
segmented min, no scatter (``hybrid_bfs._pull_relax``; SSSP's pass A
pulls distances through the same scan, ``sssp_steps._pull_dist``).
Direction also shows in

  * the kernelized bottom-up core step (``kernels/frontier_spmv``), which
    scans the dense heavy-vertex corner bitmap-wide with early-exit-free
    VPU ops (the paper's SVE scan, §4.1), and
  * the distributed engine, where direction decides what is communicated
    (frontier queues vs visited bitmaps, §2.1 table 1 of the paper).

Scatter-min convention: ``parent[v] == V`` (sentinel) means unvisited; the
root points at itself. The winning parent is the minimum frontier
neighbor id — deterministic, and after degree sorting that is also the
*heaviest* neighbor, which shortens validation chains.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.graph_build import CSRGraph, csr_to_edge_arrays
from repro.util import pytree_dataclass


@pytree_dataclass(meta=("num_vertices",))
class EdgeView:
    """Edge-parallel view of a CSR graph (static shapes).

    ``weight`` is the optional per-entry uint32 weight plane the SSSP
    kernel consumes (``graph_build.edge_weights``, in units of 2**-20;
    symmetric, 0 on invalid slots); ``None`` for unweighted BFS graphs —
    the pytree registration treats a ``None`` field as an empty subtree,
    so every existing BFS program is byte-identical.
    """

    src: jax.Array    # [E_pad] int32 (sentinel V on padding)
    dst: jax.Array    # [E_pad] int32
    valid: jax.Array  # [E_pad] bool
    num_vertices: int
    weight: jax.Array | None = None   # [E_pad] uint32 (0 on padding)


def edge_view(g: CSRGraph) -> EdgeView:
    s, d, valid = csr_to_edge_arrays(g)
    s = jnp.where(valid, s, g.num_vertices)
    d = jnp.where(valid, d, g.num_vertices)
    return EdgeView(s, d, valid, g.num_vertices)


def with_edge_weights(ev: EdgeView, *, seed: int,
                      old_from_new: jax.Array | None = None) -> EdgeView:
    """The same view with Graph500 kernel 3's weight plane attached
    (``graph_build.edge_weights``, in units of ``WEIGHT_UNIT``).

    The weights are drawn over the edges' original generator ids:
    ``old_from_new`` maps the view's ids back to them when the graph was
    relabelled (``Reordering.old_from_new``); without it the view's ids
    are the original ones."""
    from repro.core.graph_build import edge_weights

    src, dst = ev.src, ev.dst
    if old_from_new is not None:
        # Padding slots carry the sentinel V; their weight is 0 anyway.
        orig = jnp.concatenate([jnp.asarray(old_from_new, jnp.int32),
                                jnp.full((1,), ev.num_vertices, jnp.int32)])
        src, dst = orig[src], orig[dst]
    w = edge_weights(src, dst, ev.valid, seed=seed)
    return EdgeView(ev.src, ev.dst, ev.valid, ev.num_vertices, w)


def relax_step(
    ev: EdgeView,
    parent: jax.Array,     # [V+1] int32 (slot V is scratch)
    frontier: jax.Array,   # [V] bool
    visited: jax.Array,    # [V] bool
) -> tuple[jax.Array, jax.Array]:
    """One level: relax all edges whose source is in the frontier.

    Returns ``(new_parent, next_frontier)``.
    """
    v = ev.num_vertices
    f_ext = jnp.concatenate([frontier, jnp.zeros((1,), bool)])
    vis_ext = jnp.concatenate([visited, jnp.ones((1,), bool)])
    active = ev.valid & f_ext[ev.src] & ~vis_ext[ev.dst]
    cand = jnp.where(active, ev.src, v).astype(jnp.int32)
    tgt = jnp.where(active, ev.dst, v)
    new_parent = parent.at[tgt].min(cand)
    next_frontier = (new_parent[:v] != v) & ~visited
    return new_parent, next_frontier


def frontier_edge_count(degree: jax.Array, frontier: jax.Array) -> jax.Array:
    """Edges incident to the frontier — the m_f quantity in the direction switch."""
    return jnp.sum(jnp.where(frontier, degree, 0))


def unvisited_edge_count(degree: jax.Array, visited: jax.Array) -> jax.Array:
    return jnp.sum(jnp.where(visited, 0, degree))


# ---------------------------------------------------------------------------
# Chunked edge view: frontier-proportional top-down (DESIGN.md §3).
#
# The CSR edge arrays are sorted by (src, dst) with sentinel padding at the
# tail, and the graph is degree-sorted, so a *contiguous* slice of the edge
# array covers a contiguous band of source vertices.  Splitting ``E_pad``
# into fixed chunks and precomputing each chunk's source-vertex range lets
# the level loop skip chunks whose range holds no frontier bit — after the
# degree sort a small frontier touches few chunks, so the all-edges O(E)
# scan becomes roughly frontier-proportional.
# ---------------------------------------------------------------------------

DEFAULT_CHUNKS = 64


@pytree_dataclass(meta=("num_vertices", "n_chunks", "chunk_size"))
class ChunkedEdgeView:
    """``EdgeView`` re-laid-out as [n_chunks, chunk_size] with src ranges."""

    src: jax.Array      # [n_chunks, chunk_size] int32 (sentinel V on padding)
    dst: jax.Array      # [n_chunks, chunk_size] int32
    valid: jax.Array    # [n_chunks, chunk_size] bool
    src_lo: jax.Array   # [n_chunks] int32 — min valid src (V when chunk empty)
    src_hi: jax.Array   # [n_chunks] int32 — max valid src (-1 when chunk empty)
    num_vertices: int
    n_chunks: int
    chunk_size: int
    weight: jax.Array | None = None   # [n_chunks, chunk_size] uint32


def chunk_edge_view(ev: EdgeView, n_chunks: int = DEFAULT_CHUNKS) -> ChunkedEdgeView:
    """Split the (src-sorted) edge arrays into ``n_chunks`` fixed chunks."""
    v = ev.num_vertices
    e_pad = ev.src.shape[0]
    chunk_size = -(-e_pad // n_chunks)  # ceil
    pad = n_chunks * chunk_size - e_pad
    src = jnp.pad(ev.src, (0, pad), constant_values=v).reshape(n_chunks, chunk_size)
    dst = jnp.pad(ev.dst, (0, pad), constant_values=v).reshape(n_chunks, chunk_size)
    valid = jnp.pad(ev.valid, (0, pad)).reshape(n_chunks, chunk_size)
    src_lo = jnp.min(jnp.where(valid, src, v), axis=1).astype(jnp.int32)
    src_hi = jnp.max(jnp.where(valid, src, -1), axis=1).astype(jnp.int32)
    weight = (None if ev.weight is None
              else jnp.pad(ev.weight, (0, pad)).reshape(n_chunks, chunk_size))
    return ChunkedEdgeView(src, dst, valid, src_lo, src_hi, v, n_chunks,
                           chunk_size, weight)


def chunk_range_mask(src_lo: jax.Array, src_hi: jax.Array,
                     frontier_bm: jax.Array) -> jax.Array:
    """bool per chunk: source range ``[src_lo, src_hi]`` intersects the
    frontier bitmap.

    Word-granularity (conservative superset) test: a chunk is live when any
    bitmap word overlapping its range is nonzero.  O(W + n_chunks) per
    level — negligible next to the edge scan it saves.  Shared by the
    single-device chunked top-down and the vertex-sharded engine (whose
    per-shard chunks carry their own range arrays).
    """
    w = frontier_bm.shape[0]
    word_nz = (frontier_bm != 0).astype(jnp.int32)
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(word_nz)])
    lo_w = jnp.clip(src_lo // 32, 0, w - 1)
    hi_w = jnp.clip(src_hi // 32, 0, w - 1)
    nonempty = src_hi >= src_lo
    return nonempty & ((cum[hi_w + 1] - cum[lo_w]) > 0)


def chunk_frontier_mask(chunks: ChunkedEdgeView, frontier_bm: jax.Array) -> jax.Array:
    """bool [n_chunks]: chunk source range intersects the frontier bitmap."""
    return chunk_range_mask(chunks.src_lo, chunks.src_hi, frontier_bm)
