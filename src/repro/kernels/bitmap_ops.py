"""Pallas kernel: fused frontier bitmap update (paper T1, SVE -> VPU).

The hot per-level epilogue of the bitmap BFS engine::

    next    = next_raw & ~visited     # mask already-visited bits
    visited = visited | next
    count   = popcount(next)          # |in| for the direction switch

On Matrix-2000+ this is the SVE loop of paper §4.1 (16-32 lanes); on TPU a
(8, 128) uint32 VPU tile touches 32,768 vertex bits per op. The three ops
are fused into one VMEM pass — the unfused jnp version reads the bitmaps
three times from HBM; at the 2**30-vertex scales the paper targets the
bitmaps are 128 MiB each, so fusion cuts HBM traffic 3x on the level
epilogue.

Layout: bitmaps are uint32 [W] with W % 1024 == 0 (see
``heavy.padded_bitmap_words``); the kernel views them as [W // 128, 128]
and tiles (ROWS_PER_TILE, 128).

This kernel IS the per-level epilogue of the bitmap-resident BFS engine
(DESIGN.md §3 I2): the engine's ``lax.while_loop`` carries packed
frontier/visited words and calls this once per level — the returned
popcount is the ``|in|`` of the direction switch, never recounted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS_PER_TILE = 8
LANES = 128
WORDS_PER_TILE = ROWS_PER_TILE * LANES  # 1024 words = 32768 bits


def _popcount_tile(w):
    w = w - ((w >> 1) & jnp.uint32(0x55555555))
    w = (w & jnp.uint32(0x33333333)) + ((w >> 2) & jnp.uint32(0x33333333))
    w = (w + (w >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((w * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _frontier_update_kernel(next_ref, vis_ref, out_next_ref, out_vis_ref, count_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        count_ref[0, 0] = jnp.int32(0)

    nxt = next_ref[...] & ~vis_ref[...]
    out_next_ref[...] = nxt
    out_vis_ref[...] = vis_ref[...] | nxt
    count_ref[0, 0] += jnp.sum(_popcount_tile(nxt))


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_update(next_raw: jax.Array, visited: jax.Array, *, interpret: bool = True):
    """Fused (mask, merge, popcount). uint32 [W] x2 -> (uint32 [W], uint32 [W], int32).

    The popcount accumulates in one SMEM scalar across the tile grid, so
    the grid axis is sequential (``"arbitrary"``); a per-tile ``(1, 1)``
    VMEM count block would break Mosaic's (8, 128) block rule.
    """
    w = next_raw.shape[0]
    assert w % WORDS_PER_TILE == 0, f"bitmap length {w} not a multiple of {WORDS_PER_TILE}"
    rows = w // LANES
    grid = rows // ROWS_PER_TILE
    n2 = next_raw.reshape(rows, LANES)
    v2 = visited.reshape(rows, LANES)
    tile = lambda i: (i, 0)
    out_next, out_vis, count = pl.pallas_call(
        _frontier_update_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((ROWS_PER_TILE, LANES), tile),
            pl.BlockSpec((ROWS_PER_TILE, LANES), tile),
        ],
        out_specs=[
            pl.BlockSpec((ROWS_PER_TILE, LANES), tile),
            pl.BlockSpec((ROWS_PER_TILE, LANES), tile),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(n2, v2)
    return out_next.reshape(w), out_vis.reshape(w), count[0, 0]
