"""Group-based monitor communication as hierarchical mesh collectives (T3).

Paper §4.3 shortens arbitrary point-to-point traffic by routing through one
elected monitor per router group: collect (intra-group, 1 hop) -> forward
(monitor mirror group) -> deliver (intra-group). On a TPU mesh the same
structure is a *two-phase factored collective* over a pair of mesh axes:

    global all-to-all over P = G x M devices
      == all-to-all over ``member`` (intra-group phase)
       ∘ all-to-all over ``group``  (mirror-group phase)

with the generalization that all M members act as parallel monitors, each
forwarding 1/M of the inter-group traffic (the paper's Fig. 9 shows one
mirror group per color — this is all M colors at once; strictly more link
parallelism, same hop structure).

Why it wins on hardware with hierarchical bandwidth (ICI within a pod,
DCN/optical between pods): the inter-group phase moves only 1/M of the
bytes per link that a flat all-to-all would push across the top-level
bisection, and the intra-group phase rides the cheap links. These
functions are reused by: distributed BFS frontier exchange, MoE token
dispatch, recsys embedding-id exchange, and cross-pod gradient reduction.

All functions are designed to run **inside** ``jax.shard_map``; the
``*_spmd`` wrappers build the shard_map for standalone use.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import faults


# ---------------------------------------------------------------------------
# In-shard_map primitives. Axis names refer to mesh axes bound by shard_map.
# ---------------------------------------------------------------------------

def hierarchical_all_to_all(
    x: jax.Array,
    group_axis: str,
    member_axis: str,
    *,
    split_axis: int = 0,
    concat_axis: int = 0,
    tiled: bool = True,
) -> jax.Array:
    """Two-phase all-to-all. ``x``'s ``split_axis`` must factor as G*M blocks
    ordered destination-major: block index d = g_dest * M + m_dest.

    Phase 1 (intra-group): member m collects every local block whose
    destination *member index* is m — the monitor collection step.
    Phase 2 (mirror group): monitors exchange across groups.
    """
    g = lax.axis_size(group_axis)
    m = lax.axis_size(member_axis)
    shape = x.shape
    blocks = shape[split_axis]
    assert blocks % (g * m) == 0, (blocks, g, m)
    # view: [G_dest, M_dest, rest...] along split_axis
    lead = shape[:split_axis]
    tail = shape[split_axis + 1:]
    per = blocks // (g * m)
    xv = x.reshape(*lead, g, m, per, *tail)
    # Phase 1: a2a over member on the M_dest dim (dim split_axis+1).
    xv = lax.all_to_all(xv, member_axis, split_axis=split_axis + 1,
                        concat_axis=split_axis + 1, tiled=True)
    # now [G_dest, M_src, per, ...] at member m: all blocks destined to
    # member m of every group, gathered from the whole local group.
    # Phase 2: a2a over group on the G_dest dim.
    xv = lax.all_to_all(xv, group_axis, split_axis=split_axis,
                        concat_axis=split_axis, tiled=True)
    # now [G_src, M_src, per, ...]: fully delivered.
    out = xv.reshape(*lead, blocks, *tail)
    if not tiled:
        raise NotImplementedError("destination-major tiled layout only")
    return out


def flat_all_to_all(x, axes: Sequence[str], *, split_axis: int = 0):
    """Single-phase all-to-all over the flattened axes (the baseline)."""
    return lax.all_to_all(x, tuple(axes), split_axis=split_axis,
                          concat_axis=split_axis, tiled=True)


def hierarchical_psum(x, group_axis: str, member_axis: str):
    """reduce-scatter(member) -> psum(group) -> all-gather(member).

    Equal to ``psum(x, (group, member))`` but each inter-group link carries
    1/M of the gradient bytes (the monitor forwards its shard only).
    """
    m = lax.axis_size(member_axis)
    lead = x.shape[0]
    if lead % m != 0:
        # fall back: reduce within group first, then across (still 2-phase)
        x = lax.psum(x, member_axis)
        return lax.psum(x, group_axis)
    shard = lax.psum_scatter(x, member_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, group_axis)
    return lax.all_gather(shard, member_axis, axis=0, tiled=True)


def compressed_hierarchical_psum(x, group_axis: str, member_axis: str,
                                 compress_dtype=jnp.bfloat16):
    """Hierarchical psum with lossy compression on the *inter-group* leg only
    (gradient compression across the expensive links; intra-group stays
    full precision).

    Integer and boolean payloads (bitmap words, counters, ids) never take
    the float compress cast: rounding a ``uint32`` bitmap word through
    bfloat16 silently clears bits.  They go through the exact
    :func:`hierarchical_psum` instead — same two-phase hop structure,
    lossless.
    """
    if jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_:
        return hierarchical_psum(x, group_axis, member_axis)
    m = lax.axis_size(member_axis)
    lead = x.shape[0]
    orig = x.dtype
    if lead % m != 0:
        x = lax.psum(x, member_axis)
        return lax.psum(x.astype(compress_dtype), group_axis).astype(orig)
    shard = lax.psum_scatter(x, member_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard.astype(compress_dtype), group_axis).astype(orig)
    return lax.all_gather(shard, member_axis, axis=0, tiled=True)


def _or_reduce_scatter(x, axis_name: str):
    """Bitwise-OR reduce-scatter over one mesh axis (tiled, dim 0).

    There is no OR flavor of ``lax.psum_scatter``, so the same traffic
    pattern is built from its primitive decomposition: all-to-all the
    destination-major blocks, then fold OR locally.  Bytes on the wire are
    identical to ``psum_scatter`` (each device sends lead/n to each peer).
    """
    n = lax.axis_size(axis_name)
    lead = x.shape[0]
    assert lead % n == 0, (lead, n)
    blocks = x.reshape(n, lead // n, *x.shape[1:])
    blocks = lax.all_to_all(blocks, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    out = blocks[0]
    for i in range(1, n):
        out = out | blocks[i]
    return out


def _or_all_reduce(x, axis_name: str, *, fault=None, level=None,
                   device=None, root=None):
    """Bitwise-OR all-reduce over one mesh axis (gather + local fold).

    ``fault`` (DESIGN.md §13, site ``inter_group``) is only threaded in
    by the inter-group call sites: when it fires, every receiver keeps
    only the axis-index-0 contribution (``g[0]`` is replicated along the
    reduced axis, so the SPMD loop stays uniform) — the dropped-forward
    failure mode of the monitor exchange.
    """
    n = lax.axis_size(axis_name)
    g = lax.all_gather(x, axis_name, axis=0, tiled=False)
    out = g[0]
    for i in range(1, n):
        out = out | g[i]
    return faults.drop_peers(fault, out, g[0], level=level, device=device,
                             root=root) if fault is not None else out


def hierarchical_por(x, group_axis: str, member_axis: str, *,
                     fault=None, level=None, device=None, root=None):
    """Lossless bitwise-OR hierarchical all-reduce for bitmap payloads.

    The integer/bitmap analogue of :func:`hierarchical_psum` — the T3
    monitor aggregation of the per-level BFS delta bitmaps (Lv et al.'s
    compression-and-sieve inter-group leg, arXiv:1208.5542, with OR as the
    sieve): OR-reduce-scatter over ``member`` (intra-group collection),
    OR all-reduce over ``group`` (mirror-group exchange of the 1/M shard),
    all-gather over ``member`` (delivery).  Exact for uint32 words —
    nothing round-trips through a float dtype.
    """
    if not (jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_):
        raise TypeError(f"hierarchical_por is for integer/bool payloads, "
                        f"got {x.dtype}")
    m = lax.axis_size(member_axis)
    if x.shape[0] % m != 0:
        # fall back: OR within group first, then across (still two-phase)
        x = _or_all_reduce(x, member_axis)
        return _or_all_reduce(x, group_axis, fault=fault, level=level,
                              device=device, root=root)
    shard = _or_reduce_scatter(x, member_axis)
    shard = _or_all_reduce(shard, group_axis, fault=fault, level=level,
                           device=device, root=root)
    return lax.all_gather(shard, member_axis, axis=0, tiled=True)


# ---------------------------------------------------------------------------
# hier_min: the minimum-combine twin of the OR family (DESIGN.md §16).
#
# SSSP swaps the frontier exchange's idempotent combine from bitwise OR
# (bitmap union) to element-wise MIN over uint32 distance words, with
# 0xFFFFFFFF (= +inf distance) as the identity the way 0 is OR's.  The
# hop structure is identical to ``hierarchical_por`` — min-reduce-scatter
# over ``member``, min all-reduce over ``group``, delivery all-gather —
# so the same mesh axes, the same non-dividing fallback, and the same
# ``inter_group`` fault site apply unchanged.
# ---------------------------------------------------------------------------

#: uint32 +infinity — the identity of the min combine (unreached distance).
INF_U32 = 0xFFFFFFFF


def _min_reduce_scatter(x, axis_name: str):
    """Element-wise-min reduce-scatter over one mesh axis (tiled, dim 0).

    Same primitive decomposition as :func:`_or_reduce_scatter` (there is
    no MIN flavor of ``psum_scatter`` either): all-to-all the
    destination-major blocks, fold min locally.
    """
    n = lax.axis_size(axis_name)
    lead = x.shape[0]
    assert lead % n == 0, (lead, n)
    blocks = x.reshape(n, lead // n, *x.shape[1:])
    blocks = lax.all_to_all(blocks, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    out = blocks[0]
    for i in range(1, n):
        out = jnp.minimum(out, blocks[i])
    return out


def _min_all_reduce(x, axis_name, *, fault=None, level=None,
                    device=None, root=None):
    """Element-wise-min all-reduce over one mesh axis (or an axis tuple —
    the flat-exchange wiring reduces both axes in one phase).

    ``fault`` (site ``inter_group``) mirrors :func:`_or_all_reduce`: when
    it fires, every receiver keeps only the axis-index-0 contribution —
    dropped monitor forwards leave the other groups' distances at INF.
    """
    n = lax.axis_size(axis_name)
    g = lax.all_gather(x, axis_name, axis=0, tiled=False)
    if isinstance(axis_name, (tuple, list)):
        g = g.reshape(n, *x.shape)
    out = g[0]
    for i in range(1, n):
        out = jnp.minimum(out, g[i])
    return faults.drop_peers(fault, out, g[0], level=level, device=device,
                             root=root) if fault is not None else out


def hierarchical_pmin(x, group_axis: str, member_axis: str, *,
                      fault=None, level=None, device=None, root=None):
    """Lossless element-wise-min hierarchical all-reduce for integer
    distance planes — ``hier_min``, the SSSP leg of the monitor exchange.

    Each device contributes a full-width plane that is INF everywhere but
    its owned slots; the two-phase min delivers the global scatter-min
    exactly (min is associative, commutative, idempotent — the same
    algebra the OR family relies on).  Integer payloads only: a float
    round-trip could perturb the ``dist + w`` tie-breaks the parent
    convention depends on.
    """
    if not jnp.issubdtype(x.dtype, jnp.integer):
        raise TypeError(f"hierarchical_pmin is for integer payloads, "
                        f"got {x.dtype}")
    m = lax.axis_size(member_axis)
    if x.shape[0] % m != 0:
        # fall back: min within group first, then across (still two-phase)
        x = _min_all_reduce(x, member_axis)
        return _min_all_reduce(x, group_axis, fault=fault, level=level,
                               device=device, root=root)
    shard = _min_reduce_scatter(x, member_axis)
    shard = _min_all_reduce(shard, group_axis, fault=fault, level=level,
                            device=device, root=root)
    return lax.all_gather(shard, member_axis, axis=0, tiled=True)


def hierarchical_all_gather(x, group_axis: str, member_axis: str, *, axis: int = 0):
    """all-gather(member) then all-gather(group): intra-group collection
    followed by the mirror-group exchange — the frontier-bitmap exchange of
    the distributed BFS. Output block order is (group, member)-major,
    identical to the flat ``all_gather`` over ``(group, member)``."""
    x = lax.all_gather(x, member_axis, axis=axis, tiled=True)
    return lax.all_gather(x, group_axis, axis=axis, tiled=True)


# ---------------------------------------------------------------------------
# Standalone SPMD wrappers (build their own shard_map over a mesh).
# ---------------------------------------------------------------------------

def _two_axes(mesh: Mesh, group_axis: str, member_axis: str):
    assert group_axis in mesh.axis_names and member_axis in mesh.axis_names, (
        mesh.axis_names, group_axis, member_axis)
    return (group_axis, member_axis)


def all_to_all_spmd(mesh: Mesh, group_axis: str = "group",
                    member_axis: str = "member", hierarchical: bool = True):
    """Returns f(x_global) performing the (hierarchical) a2a; x_global's dim 0
    is sharded over both axes and must factor as P*P*chunk."""
    axes = _two_axes(mesh, group_axis, member_axis)
    spec = P(axes)

    def local(x):
        if hierarchical:
            return hierarchical_all_to_all(x, group_axis, member_axis)
        return flat_all_to_all(x, axes)

    return jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)
    )


def psum_spmd(mesh: Mesh, group_axis: str = "group", member_axis: str = "member",
              hierarchical: bool = True, compress: bool = False):
    """Returns f(x) for x of shape [P, n] (dim 0 sharded over both axes):
    out[i] = sum_j x[j] — the data-parallel gradient synchronization."""

    def local(x):
        v = x[0]
        if not hierarchical:
            r = lax.psum(v, _two_axes(mesh, group_axis, member_axis))
        elif compress:
            r = compressed_hierarchical_psum(v, group_axis, member_axis)
        else:
            r = hierarchical_psum(v, group_axis, member_axis)
        return r[None]

    return jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=P((group_axis, member_axis)),
                  out_specs=P((group_axis, member_axis)))
    )
