"""Graph500 step 4: BFS tree validation (spec §Validation, 5 checks).

Checks (all vectorized, no host loops):
  V1. parent[root] == root, level[root] == 0.
  V2. every visited non-root vertex has a visited parent and
      level[v] == level[parent[v]] + 1  (no cycles, correct depths).
  V3. every tree edge (v, parent[v]) exists in the input graph.
  V4. every graph edge spans levels differing by at most 1.
  V5. both endpoints of every edge are visited iff either is
      (component-consistency: the traversal covered the root's component).

SSSP checks (kernel ``"sssp"``, DESIGN.md §16 — same shape, different
invariants over ``(parent, dist)`` where ``dist`` rides in the result's
``level`` plane):
  S1. parent[root] == root, dist[root] == 0.
  S2. every non-root vertex reached by either plane (a parent or a
      distance) has both, and
      dist[v] == dist[parent[v]] + w(parent[v], v)  (tree distances: a
      distance no tree edge achieves fails here).
  S3. every tree edge (v, parent[v]) exists in the input graph.
  S4. no edge gives a shorter path than claimed:
      dist[d] <= dist[s] + w(s, d) for every edge with both ends reached
      (triangle inequality at the fixpoint — distances are optimal).
  S5. component-consistency, as V5.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.bfs_steps import EdgeView
from repro.core.hybrid_bfs import BFSResult

#: Short names of the five spec checks, in Validation field order —
#: the vocabulary used for failure attribution (``check_counts`` /
#: ``check_failures`` on :class:`repro.core.teps.Graph500Run`).
CHECK_NAMES = ("root", "depth", "tree_edge", "edge_level", "component")


class Validation(NamedTuple):
    ok: jax.Array          # [] bool
    root_ok: jax.Array
    depth_ok: jax.Array
    tree_edge_ok: jax.Array
    edge_level_ok: jax.Array
    component_ok: jax.Array


@functools.partial(jax.jit, static_argnames=())
def validate(ev: EdgeView, result: BFSResult, root: jax.Array) -> Validation:
    v = ev.num_vertices
    parent, level = result.parent, result.level
    visited = parent >= 0

    root_ok = (parent[root] == root) & (level[root] == 0)

    p_safe = jnp.where(visited, parent, 0)
    is_root = jnp.arange(v) == root
    depth_ok = jnp.all(
        jnp.where(
            visited & ~is_root,
            (parent >= 0)
            & (parent < v)
            & (level == level[p_safe] + 1)
            & (parent != jnp.arange(v)),
            True,
        )
    )

    # V3: tree edges must exist — scatter formulation (no 64-bit keys):
    # an edge (s, d) "witnesses" vertex s's tree edge when d == parent[s].
    p_ext = jnp.concatenate([p_safe, jnp.full((1,), -7, jnp.int32)])
    witness = ev.valid & (p_ext[ev.src] == ev.dst)
    has_tree_edge = jax.ops.segment_max(
        witness.astype(jnp.int32), ev.src, num_segments=v + 1
    )[:v].astype(bool)
    tree_edge_ok = jnp.all(jnp.where(visited & ~is_root, has_tree_edge, True))

    lvl_ext = jnp.concatenate([level, jnp.full((1,), -1, jnp.int32)])
    ls, ld = lvl_ext[ev.src], lvl_ext[ev.dst]
    edge_level_ok = jnp.all(
        jnp.where(ev.valid & (ls >= 0) & (ld >= 0), jnp.abs(ls - ld) <= 1, True)
    )

    vis_ext = jnp.concatenate([visited, jnp.zeros((1,), bool)])
    component_ok = jnp.all(
        jnp.where(ev.valid, vis_ext[ev.src] == vis_ext[ev.dst], True)
    )

    ok = root_ok & depth_ok & tree_edge_ok & edge_level_ok & component_ok
    return Validation(ok, root_ok, depth_ok, tree_edge_ok, edge_level_ok, component_ok)


@jax.jit
def validate_batch(ev: EdgeView, parents: jax.Array, levels: jax.Array,
                   roots: jax.Array) -> Validation:
    """All five spec checks for a ``[R, V]`` parent/level batch in ONE
    program — every Validation leaf comes back ``[R]`` bool.

    One dispatch for the whole batch, with per-check booleans per root
    for failure attribution.  Roots are checked one after another
    (``lax.map``): each check holds per-edge temporaries, so a vmap over
    R roots needs R times the memory of one (DESIGN.md §13).
    """
    return jax.lax.map(
        lambda a: validate(ev, BFSResult(parent=a[0], level=a[1],
                                         stats=None), a[2]),
        (parents, levels, jnp.asarray(roots, jnp.int32)))


#: Short names of the five SSSP invariants, in SsspValidation field order.
SSSP_CHECK_NAMES = ("root", "tree_dist", "tree_edge", "no_shorter_edge",
                    "component")


class SsspValidation(NamedTuple):
    ok: jax.Array          # [] bool
    root_ok: jax.Array
    tree_dist_ok: jax.Array
    tree_edge_ok: jax.Array
    no_shorter_edge_ok: jax.Array
    component_ok: jax.Array


@jax.jit
def validate_sssp(ev: EdgeView, result: BFSResult, root: jax.Array
                  ) -> SsspValidation:
    """The five SSSP invariants over one ``(parent, dist)`` pair.

    ``result.level`` carries the int32 distance plane (-1 = unreached);
    ``ev.weight`` must be attached (``with_edge_weights``).  Like the BFS
    checks, everything is a vectorized whole-graph pass — the tree-edge
    weight is recovered by the same witness-scatter as V3 (the CSR is
    deduped, so at most one edge witnesses each (v, parent[v]) pair).
    """
    v = ev.num_vertices
    parent, dist = result.parent, result.level
    reached = parent >= 0
    wgt = ev.weight.astype(jnp.int32)

    root_ok = (parent[root] == root) & (dist[root] == 0)

    p_safe = jnp.where(reached, parent, 0)
    is_root = jnp.arange(v) == root

    # S3 witness scatter, reused for S2: the witnessing edge's weight is
    # the tree-edge weight w(parent[v], v).
    p_ext = jnp.concatenate([p_safe, jnp.full((1,), -7, jnp.int32)])
    witness = ev.valid & (p_ext[ev.src] == ev.dst)
    has_tree_edge = jax.ops.segment_max(
        witness.astype(jnp.int32), ev.src, num_segments=v + 1
    )[:v].astype(bool)
    w_tree = jax.ops.segment_max(
        jnp.where(witness, wgt, 0), ev.src, num_segments=v + 1
    )[:v]
    tree_edge_ok = jnp.all(jnp.where(reached & ~is_root, has_tree_edge, True))

    tree_dist_ok = jnp.all(
        jnp.where(
            (reached | (dist >= 0)) & ~is_root,
            (parent >= 0)
            & (parent < v)
            & (parent != jnp.arange(v))
            & (dist[p_safe] >= 0)
            & (dist == dist[p_safe] + w_tree),
            True,
        )
    )

    # S4: at the fixpoint no edge relaxes further — distances are optimal
    # (with S2's consistency this is exactly Dijkstra's certificate).
    dist_ext = jnp.concatenate([dist, jnp.full((1,), -1, jnp.int32)])
    ds, dd = dist_ext[ev.src], dist_ext[ev.dst]
    no_shorter_edge_ok = jnp.all(
        jnp.where(ev.valid & (ds >= 0) & (dd >= 0), dd <= ds + wgt, True)
    )

    vis_ext = jnp.concatenate([reached, jnp.zeros((1,), bool)])
    component_ok = jnp.all(
        jnp.where(ev.valid, vis_ext[ev.src] == vis_ext[ev.dst], True)
    )

    ok = (root_ok & tree_dist_ok & tree_edge_ok & no_shorter_edge_ok
          & component_ok)
    return SsspValidation(ok, root_ok, tree_dist_ok, tree_edge_ok,
                          no_shorter_edge_ok, component_ok)


@jax.jit
def validate_sssp_batch(ev: EdgeView, parents: jax.Array, levels: jax.Array,
                        roots: jax.Array) -> SsspValidation:
    """Batched SSSP validation — SsspValidation leaves come back [R] bool.
    Roots are checked one after another, as in :func:`validate_batch`."""
    return jax.lax.map(
        lambda a: validate_sssp(ev, BFSResult(parent=a[0], level=a[1],
                                              stats=None), a[2]),
        (parents, levels, jnp.asarray(roots, jnp.int32)))


def failure_report(val):
    """Host-side attribution of a batched Validation/SsspValidation.

    Returns ``(counts, failures)``: ``counts`` maps every check name to
    the number of roots failing it (zeros included, so the dict shape is
    stable for BENCH metadata), ``failures`` maps each failing root
    *index* to the list of check names it failed.  Check names are read
    off the result type's ``*_ok`` fields, so BFS and SSSP batches both
    work.
    """
    import numpy as np

    names = tuple(f[:-3] for f in val._fields if f.endswith("_ok"))
    per_check = {name: np.asarray(getattr(val, f"{name}_ok"))
                 for name in names}
    counts = {name: int(np.sum(~okv)) for name, okv in per_check.items()}
    failures: dict[int, list[str]] = {}
    for i in np.nonzero(~np.asarray(val.ok))[0]:
        failures[int(i)] = [name for name in names
                            if not per_check[name][i]]
    return counts, failures
