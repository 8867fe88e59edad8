"""Bytes each Pallas kernel must move per call, from its shapes, and the
roofline share that follows from the kernel's time in the trace.

Both kernels are bound by HBM bandwidth: they do a few integer ops per
word they read.  The bytes are the least any implementation moves, each
operand read once and each result written once, so a share above 100%
means the trace's time leaves out part of the work.
"""
from __future__ import annotations

WORD = 4  # uint32 / int32 bytes


def core_spmv_bytes(k: int, w: int) -> int:
    """``kernels/frontier_spmv.core_spmv``: the ``[K, W]`` core bitmap and
    the ``[W]`` frontier words in, ``[K]`` int32 row minima out."""
    return WORD * (k * w + w + k)


def frontier_update_bytes(w: int) -> int:
    """``kernels/bitmap_ops.frontier_update``: the next and visited
    ``[W]`` bitmaps in, the masked next and merged visited out, plus the
    popcount scalar."""
    return WORD * (4 * w + 1)


def roofline_share(bytes_per_call: int, calls: int, kernel_s: float,
                   hbm_bytes_per_s: float) -> float | None:
    """Least time at peak bandwidth over the measured kernel time, in %;
    None when the trace holds no call of the kernel."""
    if calls <= 0 or kernel_s <= 0:
        return None
    return 100.0 * bytes_per_call * calls / hbm_bytes_per_s / kernel_s


# A kernel's calls are the device trace's custom calls named after it
# (``%core_spmv.1 = s32[61440,1] custom-call(...)`` on a v5e).
BYTES = {
    "core_spmv": lambda s: core_spmv_bytes(s["k"], s["w"]),
    "frontier_update": lambda s: frontier_update_bytes(s["w"]),
}


def read_roofline(ctx, kernel: str) -> float | None:
    """A kernel's roofline share in a traced run, or None when the run
    was not traced, the program has no such kernel, or the trace names
    none of its calls."""
    if ctx.trace is None or kernel not in ctx.shapes:
        return None
    secs, calls = ctx.trace.op_seconds(kernel)
    return roofline_share(BYTES[kernel](ctx.shapes[kernel]), calls, secs,
                          ctx.peaks["hbm_bytes_per_s"])
