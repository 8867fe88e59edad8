"""Deterministic fault injection for the Graph500 engines (DESIGN.md §13).

At 512 nodes the paper's two-phase monitor exchange is exactly where
silent corruption — a dropped inter-group forward, a flipped delta
word — would go undetected: the traversal
finishes, the TEPS number looks plausible, and only spec validation
(step 4) can tell the tree is wrong.  This module makes those failure
modes *injectable on purpose*, deterministically, inside the real jitted
code paths, so the checked execution mode (``CompiledBFS.run(...,
check=...)``) and the retry → fallback → quarantine recovery policy can
be exercised and regression-tested without flaky hardware.

A :class:`FaultSpec` is a frozen (hashable) dataclass threaded through
``compile_plan(plan, built, fault=...)`` as a *static* argument — the
corruption is baked into the compiled program, which keeps the clean
path byte-identical (``fault=None`` compiles exactly the pre-fault
program).  Each spec names one injection **site** (where in the real
code path the corruption applies), one **kind** (how the payload is
corrupted), and predicates (level / device / root) evaluated on traced
values inside the loop:

  site ``exchange``   — the per-level delta words at the entry of
                        ``hybrid_bfs._exchange_delta`` (every wiring).
                        Kinds: ``zero`` (drop the outgoing delta),
                        ``flip`` (XOR one bit into it).
  site ``parent``     — the parent scatter-min epilogue of the bitmap
                        engines (single-device AND sharded): per level
                        in BFS, once per search in SSSP (pass B after
                        the round loop).  Kinds: ``self`` (newly-found
                        vertices become their own parent), ``offset``
                        (parent ids bumped +1 mod V).
  site ``inter_group`` — the inter-group leg of ``hierarchical_por``
                        (and of the SSSP min exchanges): every receiver
                        keeps only group 0's contribution (the other
                        groups' monitor forwards are dropped on the
                        floor — replicated, so the SPMD loop stays
                        uniform).  Kind: ``drop``.

All helpers below are no-ops returning their input unchanged when the
fault is ``None`` or targets a different site — the hooks cost nothing
when inactive and the corruption itself is a single ``jnp.where`` on the
traced activation predicate.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

FAULT_SITES = ("exchange", "parent", "inter_group")

FAULT_KINDS = {
    "exchange": ("zero", "flip"),
    "parent": ("self", "offset"),
    "inter_group": ("drop",),
}

#: The fault classes of the detection matrix (DESIGN.md §13): one
#: (site, kind) pair per distinct silent-corruption mode.
FAULT_CLASSES = tuple(
    (site, kind) for site in FAULT_SITES for kind in FAULT_KINDS[site])


@dataclass(frozen=True)
class FaultSpec:
    """One deterministic injected fault (static under jit — hashable).

    ``level``/``device``/``root`` are firing predicates on traced loop
    values (``-1`` matches everything); ``persistent=True`` widens the
    level predicate from ``lvl == level`` to ``lvl >= level`` (a fault
    that keeps firing — the quarantine-path demonstrator).  ``word`` /
    ``bit`` parameterize the ``exchange``/``flip`` corruption.
    """

    site: str
    kind: str
    level: int = -1        # BFS level to fire at (-1 = every level)
    persistent: bool = False  # fire at every level >= `level`
    device: int = -1       # flat shard index (-1 = every device)
    root: int = -1         # global root id (-1 = every root)
    word: int = 0          # target word of the delta
    bit: int = 0           # target bit within the word

    def __post_init__(self):
        if self.site not in FAULT_SITES:
            raise ValueError(f"unknown fault site {self.site!r}; expected "
                             f"one of {FAULT_SITES}")
        if self.kind not in FAULT_KINDS[self.site]:
            raise ValueError(
                f"unknown kind {self.kind!r} for site {self.site!r}; "
                f"expected one of {FAULT_KINDS[self.site]}")

    def describe(self) -> str:
        when = ("always" if self.level < 0 else
                f"level>={self.level}" if self.persistent else
                f"level=={self.level}")
        where = "all devices" if self.device < 0 else f"device {self.device}"
        which = "all roots" if self.root < 0 else f"root {self.root}"
        return (f"{self.site}/{self.kind} @ {when}, {where}, {which}")


def fires(fault, site: str, *, level=None, device=None, root=None):
    """Traced activation predicate, or ``None`` when statically inactive
    (wrong site / no fault) so callers can skip the hook entirely."""
    if fault is None or fault.site != site:
        return None
    act = jnp.bool_(True)
    if level is not None and fault.level >= 0:
        lvl = jnp.asarray(level, jnp.int32)
        act = act & (lvl >= fault.level if fault.persistent
                     else lvl == fault.level)
    if device is not None and fault.device >= 0:
        act = act & (jnp.asarray(device, jnp.int32) == fault.device)
    if root is not None and fault.root >= 0:
        act = act & (jnp.asarray(root, jnp.int32) == fault.root)
    return act


def corrupt_delta(fault, words, *, level, device=None, root=None):
    """Site ``exchange``: corrupt the outgoing uint32 delta words."""
    act = fires(fault, "exchange", level=level, device=device, root=root)
    if act is None:
        return words
    if fault.kind == "zero":
        bad = jnp.zeros_like(words)
    else:  # flip
        w = fault.word % words.shape[0]
        b = jnp.uint32(1) << jnp.uint32(fault.bit % 32)
        bad = words.at[w].set(words[w] ^ b)
    return jnp.where(act, bad, words)


def corrupt_parent(fault, parent, newly, self_ids, sentinel, *, level,
                   device=None, root=None):
    """Site ``parent``: corrupt the scatter-min parent epilogue.

    ``parent`` holds the post-relax parent values for this level's local
    vertex range (global ids, unvisited marked ``sentinel``), ``newly``
    the vertices found this level, ``self_ids`` each slot's own global
    vertex id.

    The SSSP engines build parents once per search, after the round
    loop, and call this once there: ``level`` is then the search's last
    round index (rounds minus one) and ``newly`` every reached vertex
    but the root.  A fault with ``level=k`` fires on a search whose last
    round is ``k`` (``persistent``: at least ``k``), and corrupts the
    whole tree.
    """
    act = fires(fault, "parent", level=level, device=device, root=root)
    if act is None:
        return parent
    if fault.kind == "self":
        wrong = self_ids.astype(parent.dtype)
    else:  # offset: a wrong-but-plausible (in-range) parent id
        wrong = jnp.where(parent + 1 >= sentinel, 0, parent + 1)
    return jnp.where(act & newly, wrong, parent)


def drop_peers(fault, combined, first_leg, *, level, device=None, root=None):
    """Site ``inter_group``: the OR-combined inter-group result loses
    every contribution but group 0's (``first_leg`` — identical on every
    receiver, so the SPMD loop stays uniform)."""
    act = fires(fault, "inter_group", level=level, device=device, root=root)
    if act is None:
        return combined
    return jnp.where(act, first_leg, combined)
