"""HBM roofline share of the frontier epilogue kernel
(kernels/bitmap_ops.frontier_update)."""
from bench.kernels import read_roofline

LAYER = "kernels"
UNIT = "%"
MOVES = "bfs_teps"


def read(ctx):
    return read_roofline(ctx, "frontier_update")
