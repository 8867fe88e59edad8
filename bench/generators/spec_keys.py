"""Graph500 kernel-2 traffic: the spec's search keys, run back to back.

Parameters (``bench/traffic/<t>.json``): ``n_keys``, the number of
search keys (the spec's 64), which the window cycles through in order,
and ``check_sample``, the most searches the comparison looks at.
"""
from __future__ import annotations

from bench import gen


def make(traffic: dict, seed: int, src, dst, n_vertices: int):
    """The search keys of ``seed`` over the edge list ``(src, dst)`` of
    ``n_vertices`` vertices, in original vertex ids."""
    return gen.search_keys(seed, src, dst, n_vertices, int(traffic["n_keys"]))
