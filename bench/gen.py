"""The benchmark's own input generators.

The Graph500 Kronecker generator is a copy, not an import, of the
program's (``repro.core.kronecker``); the benchmark makes its search keys
and its reference graph from it, so a change to the program's generator
cannot change what is measured or what the answers are compared with.
``bench/tests/test_gen.py`` holds the copy equal to the program's for a
seed.  The search keys follow the Graph500 3.0.0 specification (§4),
not the program's ``sample_roots``, which draws edge endpoints and so
favours high-degree vertices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Graph500 3.0.0 §3 R-MAT quadrant probabilities.
A, B, C, D = 0.57, 0.19, 0.19, 0.05
_AB = A + B
_C_NORM = C / (1.0 - _AB)
_A_NORM = A / _AB


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor"))
def _kronecker(key, *, scale: int, edge_factor: int):
    n_edges = edge_factor << scale
    key_bits, _, _ = jax.random.split(key, 3)
    u = jax.random.uniform(key_bits, (scale, 2, n_edges), dtype=jnp.float32)

    def one_bit(carry, u_bit):
        src, dst, shift = carry
        ii_bit = (u_bit[0] > _AB).astype(jnp.int32)
        thresh = _C_NORM * ii_bit + _A_NORM * (1 - ii_bit)
        jj_bit = (u_bit[1] > thresh).astype(jnp.int32)
        return (src + (ii_bit << shift), dst + (jj_bit << shift),
                shift + 1), None

    zero = jnp.zeros((n_edges,), jnp.int32)
    (src, dst, _), _ = jax.lax.scan(one_bit, (zero, zero, jnp.int32(0)), u)
    return src, dst


def kronecker_edges(seed: int, scale: int, edge_factor: int = 16):
    """The Graph500 Kronecker edge list of ``seed`` as host arrays
    ``(src, dst)``, int32 ``[edge_factor * 2**scale]``, unpermuted.

    It is computed on the host's CPU backend, so the benchmark's inputs
    take no memory on the chip, whose peak is a measured metric, and do
    not share a device fault with the program's own generator."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        key = jax.device_put(jax.random.PRNGKey(seed), cpu)
        src, dst = _kronecker(key, scale=scale, edge_factor=edge_factor)
    return np.asarray(src), np.asarray(dst)


def search_keys(seed: int, src, dst, n_vertices: int,
                n_keys: int) -> np.ndarray:
    """Graph500 3.0.0 search keys: ``n_keys`` unique vertices drawn
    uniformly from those with at least one edge, self loops not counted.
    Original (generator) vertex ids, in the order drawn."""
    src, dst = np.asarray(src), np.asarray(dst)
    edge = src != dst
    has_edge = np.zeros(int(n_vertices), bool)
    has_edge[src[edge]] = True
    has_edge[dst[edge]] = True
    rng = np.random.default_rng(seed)
    keys = rng.choice(np.flatnonzero(has_edge), n_keys, replace=False)
    return keys.astype(np.int32)
