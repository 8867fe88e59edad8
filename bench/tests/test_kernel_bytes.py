"""The kernels' byte counts match their operands' and results' shapes."""
import jax
import jax.numpy as jnp
import pytest

from bench import kernels


def nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("k", [1024, 4096])
def test_core_spmv_bytes(k):
    from repro.kernels.frontier_spmv import core_spmv

    a = jax.ShapeDtypeStruct((k, k // 32), jnp.uint32)
    f = jax.ShapeDtypeStruct((k // 32,), jnp.uint32)
    out = jax.eval_shape(lambda a, f: core_spmv(a, f, lanes=k // 32), a, f)
    assert kernels.core_spmv_bytes(k, k // 32) == nbytes((a, f, out))


@pytest.mark.parametrize("w", [1024, 32768])
def test_frontier_update_bytes(w):
    from repro.kernels.bitmap_ops import frontier_update

    x = jax.ShapeDtypeStruct((w,), jnp.uint32)
    out = jax.eval_shape(frontier_update, x, x)
    assert kernels.frontier_update_bytes(w) == nbytes((x, x, out))


def test_roofline_share_arithmetic():
    # 819 MB at 819 GB/s is 1 ms: a 2 ms kernel is at half its roofline.
    assert kernels.roofline_share(819_000_000, 1, 2e-3, 819e9) == \
        pytest.approx(50.0)
    assert kernels.roofline_share(10, 3, 1.0, 1.0) == pytest.approx(3000.0)
    assert kernels.roofline_share(10, 0, 1.0, 1.0) is None
    assert kernels.roofline_share(10, 2, 0.0, 1.0) is None
