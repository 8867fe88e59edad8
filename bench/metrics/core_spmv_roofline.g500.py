"""HBM roofline share of the dense-core kernel
(kernels/frontier_spmv.core_spmv)."""
from bench.kernels import read_roofline

LAYER = "kernels"
UNIT = "%"
MOVES = "bfs_teps"


def read(ctx):
    return read_roofline(ctx, "core_spmv")
