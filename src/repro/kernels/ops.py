"""Public jit'd wrappers for the Pallas kernels.

Kernels compile to Mosaic on a TPU backend and run with ``interpret=True``
(traced to XLA ops) exactly when the backend is the CPU.  Benchmark runs
record the backend and the resolved mode in ``BENCH_bfs.json`` metadata
(``backend``, ``interpret_mode``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import bitmap_ops, cin, frontier_spmv, spmv_mxu
from repro.kernels.ref import BIG  # re-export sentinel


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: only on the CPU."""
    return jax.default_backend() == "cpu"


def frontier_update(next_raw: jax.Array, visited: jax.Array):
    """Fused: next &= ~visited; visited |= next; count = popcount(next).

    The hot per-level epilogue of the bitmap-resident BFS loop
    (``core/hybrid_bfs.py``, DESIGN.md §3 I2).
    """
    return bitmap_ops.frontier_update(next_raw, visited, interpret=interpret_mode())


def core_spmv(a_core: jax.Array, frontier_bm: jax.Array, *, rows_per_tile: int = 8):
    """Bottom-up step over the dense core: min frontier neighbor per row."""
    return frontier_spmv.core_spmv(
        a_core, frontier_bm, rows_per_tile=rows_per_tile,
        interpret=interpret_mode(),
    )


def multi_source_spmv(a_core8: jax.Array, frontier8: jax.Array):
    """Batched-root Boolean SpMV on the MXU (int8 x int8 -> int32)."""
    return spmv_mxu.spmv_mxu(a_core8, frontier8, interpret=interpret_mode())


def cin_layer(x0: jax.Array, xl: jax.Array, w: jax.Array, *, batch_tile: int = 128):
    """Fused xDeepFM CIN layer; pads the embedding lane dim to 128."""
    b, f0, d = x0.shape
    d_pad = max(128, ((d + 127) // 128) * 128)
    if d != d_pad:
        pad = ((0, 0), (0, 0), (0, d_pad - d))
        x0p, xlp = jnp.pad(x0, pad), jnp.pad(xl, pad)
    else:
        x0p, xlp = x0, xl
    bt = min(batch_tile, b)
    while b % bt:
        bt //= 2
    out = cin.cin_layer(x0p, xlp, w, batch_tile=bt, interpret=interpret_mode())
    return out[..., :d]
