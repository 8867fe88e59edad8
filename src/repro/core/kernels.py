"""Kernel registry: the traversal-lifecycle contract (DESIGN.md §16).

A Graph500 *kernel* is one point on four orthogonal interface axes the
plan compiler assembles a traversal from:

  * **state carrier** — what lives packed across the level/round loop
    (BFS: frontier + visited bitmaps; SSSP: changed bitmap + uint32
    distance plane);
  * **relax rule** — how an edge updates the carrier (BFS: parent
    scatter-min over frontier edges; SSSP: distance min-relax + the
    fixpoint min-source parent rebuild);
  * **exchange combine** — the collective family reassembling per-shard
    updates (BFS: bitwise OR; SSSP: element-wise min for distances, OR
    for the changed delta);
  * **result/validation contract** — what the output arrays mean and
    which spec checks apply (``core.validate``: the five BFS checks vs
    the five SSSP invariants).

``plan.validate_plan`` and ``plan.compile_plan`` consult this table; the
engines themselves live in ``hybrid_bfs`` / ``sssp_steps``.  Adding a
kernel means adding a row here plus its engine + validator — the plan /
runner / serving / fault-recovery layers are kernel-generic.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

from repro.core.hybrid_bfs import ENGINES, SHARD_EXCHANGES
from repro.core.sssp_steps import SSSP_EXCHANGES
from repro.core.validate import CHECK_NAMES, SSSP_CHECK_NAMES


@dataclass(frozen=True)
class KernelSpec:
    """One kernel row: the static facts the plan layer dispatches on."""

    name: str
    combine: str             # shard-exchange reduction: "or" | "min"
    needs_weights: bool      # requires an EdgeView weight plane
    engines: tuple           # plan.engine values this kernel supports
    shard_exchanges: tuple   # valid plan.exchange values for this kernel
    default_exchange: str    # what the generic default normalizes to
    check_names: tuple       # validation vocabulary (failure attribution)


KERNELS = {
    "bfs": KernelSpec(
        name="bfs", combine="or", needs_weights=False,
        engines=ENGINES, shard_exchanges=SHARD_EXCHANGES,
        default_exchange="hier_or", check_names=CHECK_NAMES),
    "sssp": KernelSpec(
        name="sssp", combine="min", needs_weights=True,
        engines=("bitmap",), shard_exchanges=SSSP_EXCHANGES,
        default_exchange="hier_min", check_names=SSSP_CHECK_NAMES),
}


def kernel_spec(name: str) -> KernelSpec:
    spec = KERNELS.get(name)
    if spec is None:
        raise ValueError(f"unknown kernel {name!r}; expected one of "
                         f"{tuple(KERNELS)}")
    return spec


def rekernel_plan(plan, kernel: str):
    """Retarget ``plan`` at ``kernel`` (the §16 migration rule).

    The kernel axis rides on top of a tuned/explicit plan: layout,
    mesh_shape, partition and α/β carry over unchanged, but an exchange
    outside the target kernel's family falls back to that kernel's
    default wiring (a BFS-tuned ``hier_gather`` has no min-combine
    analogue).
    """
    if kernel == plan.kernel:
        return plan
    spec = kernel_spec(kernel)
    kw: dict = {"kernel": kernel}
    if plan.exchange not in spec.shard_exchanges:
        kw["exchange"] = spec.default_exchange
    return dataclasses.replace(plan, **kw)


def validate_result_batch(kernel: str, ev, parents, levels, roots):
    """Kernel-dispatched batched spec validation (one vmapped program)."""
    if kernel == "sssp":
        from repro.core.validate import validate_sssp_batch
        return validate_sssp_batch(ev, parents, levels, roots)
    from repro.core.validate import validate_batch
    return validate_batch(ev, parents, levels, roots)
