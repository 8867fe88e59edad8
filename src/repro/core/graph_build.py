"""Graph construction: edge list -> static-shape CSR (Graph500 step 2).

JAX has no CSR/CSC sparse type (BCOO only), so the compressed structure is
built from first principles with sort + ``segment_sum`` + ``cumsum`` — per
the assignment this is part of the system, not a gap.

Layout decisions (DESIGN.md §6):
  * the graph is symmetrized (undirected), so one structure serves both the
    top-down (CSR) and bottom-up (CSC) traversal directions;
  * self loops are dropped and duplicate edges removed — required for the
    bit-scatter core builder in ``heavy.py`` (add == or only without dups);
  * all arrays keep a static length ``2 * M``; invalid slots carry the
    sentinel ``src == num_vertices`` and sort to the tail.

Kernel 3's edge weights (``edge_weights``) are drawn here too: uniform
over the multiples of ``WEIGHT_UNIT`` = 2**-20 in (0, 1), a hash of the
seed and the edge's original endpoint pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.kronecker import EdgeList
from repro.util import pytree_dataclass


@pytree_dataclass(meta=("num_vertices",))
class CSRGraph:
    """Symmetric static-shape CSR.

    ``row_offsets`` is ``[V+1]`` int32; ``col_indices`` is ``[E_pad]`` int32
    where slots ``>= nnz`` hold the sentinel ``V``. ``degree[v]`` is the
    (deduped) undirected degree.
    """

    row_offsets: jax.Array   # [V+1] int32
    col_indices: jax.Array   # [E_pad] int32 (sentinel V in padding)
    edge_valid: jax.Array    # [E_pad] bool
    degree: jax.Array        # [V] int32
    nnz: jax.Array           # [] int32 — directed entries (2x undirected)
    num_vertices: int        # static

    @property
    def padded_edges(self) -> int:
        return int(self.col_indices.shape[0])

    def edge_sources(self) -> jax.Array:
        """Recover per-entry source ids from row_offsets (O(E) searchsorted)."""
        e = jnp.arange(self.padded_edges, dtype=jnp.int32)
        return jnp.searchsorted(
            self.row_offsets, e, side="right"
        ).astype(jnp.int32) - 1


@functools.partial(jax.jit, static_argnames=("num_vertices",))
def _build(src: jax.Array, dst: jax.Array, *, num_vertices: int) -> CSRGraph:
    v = num_vertices
    # --- symmetrize -------------------------------------------------------
    s = jnp.concatenate([src, dst])
    d = jnp.concatenate([dst, src])
    # --- drop self loops (mark invalid with sentinel) ---------------------
    self_loop = s == d
    s = jnp.where(self_loop, v, s)
    d = jnp.where(self_loop, v, d)
    # --- lexsort by (src, dst): invalid rows sort last --------------------
    order = jnp.lexsort((d, s))
    s, d = s[order], d[order]
    # --- dedupe: identical consecutive (s, d) pairs -----------------------
    dup = (s[1:] == s[:-1]) & (d[1:] == d[:-1])
    dup = jnp.concatenate([jnp.zeros((1,), bool), dup])
    valid = (s < v) & ~dup
    s = jnp.where(valid, s, v)
    d = jnp.where(valid, d, v)
    # re-sort so invalidated duplicates move to the tail, keeping CSR dense.
    order2 = jnp.lexsort((d, s))
    s, d, valid = s[order2], d[order2], valid[order2]
    # --- CSR assembly ------------------------------------------------------
    degree = jax.ops.segment_sum(
        valid.astype(jnp.int32), s, num_segments=v + 1
    )[:v]
    row_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(degree).astype(jnp.int32)]
    )
    nnz = row_offsets[-1]
    return CSRGraph(
        row_offsets=row_offsets,
        col_indices=d.astype(jnp.int32),
        edge_valid=valid,
        degree=degree.astype(jnp.int32),
        nnz=nnz,
        num_vertices=v,
    )


def build_csr(edges: EdgeList) -> CSRGraph:
    """Graph500 step 2: construct the symmetric CSR from the edge list."""
    return _build(edges.src, edges.dst, num_vertices=edges.num_vertices)


def csr_to_edge_arrays(g: CSRGraph) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(src, dst, valid) per directed CSR entry — the edge-parallel view."""
    return g.edge_sources(), g.col_indices, g.edge_valid


# ---------------------------------------------------------------------------
# Edge weights of Graph500 kernel 3 (SSSP, DESIGN.md §16).
#
# The spec draws each edge's weight uniformly from [0, 1).  Here the draw
# is a 32-bit finalizer hash of the seed and the unordered pair of the
# edge's *original* generator ids, so w(u, v) == w(v, u) without an
# undirected edge list, and the weights do not depend on the degree
# relabelling or on any later layout.  A weight is ``k * WEIGHT_UNIT``
# with ``k`` in ``[1, 2**WEIGHT_BITS - 1]``: on that dyadic grid every
# path length is an exact sum (in integer units, or in float32 below 16),
# and every tree edge adds at least one unit, so the min-source parent
# rule always gives a tree.  A continuous draw could let a tiny weight
# round away, and two vertices at equal distance adopt each other.  The
# engines therefore keep weights and distances as uint32 counts of
# WEIGHT_UNIT, and every distance they return is in that unit.
# ---------------------------------------------------------------------------

WEIGHT_BITS = 20
#: The unit of every weight and distance the SSSP kernel holds: 2**-20.
WEIGHT_UNIT = 2.0 ** -WEIGHT_BITS


def _mix32(h):
    """32-bit finalizer (lowbias32-style avalanche); numpy/jnp uint32."""
    u32 = jnp.uint32
    h = h ^ (h >> u32(16))
    h = h * u32(0x7FEB352D)
    h = h ^ (h >> u32(15))
    h = h * u32(0x846CA68B)
    return h ^ (h >> u32(16))


def edge_weights(src, dst, valid, *, seed: int):
    """uint32 weight per directed edge entry, in units of WEIGHT_UNIT:
    ``k`` in ``[1, 2**WEIGHT_BITS - 1]``, 0 on invalid slots.

    ``src`` and ``dst`` are original generator ids.  The weight is a
    hash of ``seed`` (all of its bits up to 64) and the unordered pair,
    so it is symmetric.  ``k`` is the hash modulo ``2**20 - 1``, plus 1:
    uniform over the grid to within one part in 4096.  Works on numpy or
    jnp arrays (integer-exact either way)."""
    u32 = jnp.uint32
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    s = jnp.asarray(src).astype(u32)
    d = jnp.asarray(dst).astype(u32)
    a = jnp.minimum(s, d)
    b = jnp.maximum(s, d)
    key = _mix32(u32(seed & 0xFFFFFFFF) ^ _mix32(u32(seed >> 32)))
    h = _mix32(a * u32(0x9E3779B9) + key)
    h = _mix32(h ^ (b * u32(0x85EBCA6B)))
    w = u32(1) + h % u32((1 << WEIGHT_BITS) - 1)
    return jnp.where(jnp.asarray(valid), w, u32(0))
