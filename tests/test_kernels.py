"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bitmap_ops import frontier_update
from repro.kernels.frontier_spmv import core_spmv
from repro.kernels.spmv_mxu import spmv_mxu
from repro.kernels.cin import cin_layer
from repro.kernels import ops


def rand_u32(rng, shape, density=0.5):
    bits = rng.random(shape + (32,)) < density
    return np.packbits(bits.astype(np.uint8), axis=-1, bitorder="little") \
        .view(np.uint32).reshape(shape)


@pytest.mark.parametrize("n_words", [1024, 4096, 8192])
@pytest.mark.parametrize("density", [0.01, 0.5])
@pytest.mark.parametrize("batch", [None, 8], ids=["one", "vmap8"])
def test_frontier_update_matches_ref(n_words, density, batch):
    # vmap8: the popcount accumulates across the tile grid, per root
    rng = np.random.default_rng(n_words)
    lead = () if batch is None else (batch,)
    nxt = jnp.asarray(rand_u32(rng, lead + (n_words,), density))
    vis = jnp.asarray(rand_u32(rng, lead + (n_words,), density))
    fu = lambda n, v: frontier_update(n, v, interpret=True)
    ref_fu = ref.frontier_update_ref
    if batch is not None:
        fu, ref_fu = jax.vmap(fu), jax.vmap(ref_fu)
    out_n, out_v, count = fu(nxt, vis)
    ref_n, ref_v, ref_c = ref_fu(nxt, vis)
    np.testing.assert_array_equal(np.asarray(out_n), np.asarray(ref_n))
    np.testing.assert_array_equal(np.asarray(out_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(count), np.asarray(ref_c))


def test_frontier_update_popcount_exact():
    # all-ones / all-zeros corners
    w = 1024
    ones = jnp.full((w,), 0xFFFFFFFF, jnp.uint32)
    zeros = jnp.zeros((w,), jnp.uint32)
    _, _, c = frontier_update(ones, zeros, interpret=True)
    assert int(c) == w * 32
    _, _, c = frontier_update(ones, ones, interpret=True)
    assert int(c) == 0


@pytest.mark.parametrize("k", [4096, 8192])
@pytest.mark.parametrize("rows_per_tile", [8, 16])
@pytest.mark.parametrize("density", [0.001, 0.05])
def test_core_spmv_matches_ref(k, rows_per_tile, density):
    rng = np.random.default_rng(k + rows_per_tile)
    a = rand_u32(rng, (k, k // 32), density)
    f = rand_u32(rng, (k // 32,), 0.1)
    out = core_spmv(jnp.asarray(a), jnp.asarray(f),
                    rows_per_tile=rows_per_tile, interpret=True)
    expected = ref.core_spmv_ref(jnp.asarray(a), jnp.asarray(f))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expected))


def test_core_spmv_finds_min_neighbor():
    # hand-built case: row 0 connects to {5, 70, 4000}, frontier = {70, 4000}
    k = 4096
    a = np.zeros((k, k // 32), np.uint32)
    for j in (5, 70, 4000):
        a[0, j // 32] |= np.uint32(1) << (j % 32)
    f = np.zeros((k // 32,), np.uint32)
    for j in (70, 4000):
        f[j // 32] |= np.uint32(1) << (j % 32)
    out = core_spmv(jnp.asarray(a), jnp.asarray(f), interpret=True)
    assert int(out[0]) == 70
    assert int(out[1]) == ref.BIG


@pytest.mark.parametrize("k,r", [(256, 128), (512, 256)])
def test_spmv_mxu_matches_ref(k, r):
    rng = np.random.default_rng(k * r)
    a = (rng.random((k, k)) < 0.05).astype(np.int8)
    f = (rng.random((k, r)) < 0.1).astype(np.int8)
    out = spmv_mxu(jnp.asarray(a), jnp.asarray(f), interpret=True)
    expected = ref.spmv_mxu_ref(jnp.asarray(a), jnp.asarray(f))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expected))


@pytest.mark.parametrize("b,f0,fl,h,d", [
    (128, 8, 8, 16, 4), (256, 12, 20, 8, 10), (128, 39, 16, 8, 10)])
def test_cin_kernel_matches_ref(b, f0, fl, h, d):
    rng = np.random.default_rng(b + f0)
    x0 = jnp.asarray(rng.normal(size=(b, f0, d)).astype(np.float32))
    xl = jnp.asarray(rng.normal(size=(b, fl, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(h, f0, fl)).astype(np.float32))
    out = ops.cin_layer(x0, xl, w)
    expected = ref.cin_layer_ref(x0, xl, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_popcount_ctz_reference_against_python():
    rng = np.random.default_rng(0)
    w = rng.integers(0, 2**32, size=1000, dtype=np.uint32)
    pc = np.asarray(ref.popcount_u32(jnp.asarray(w)))
    cz = np.asarray(ref.ctz_u32(jnp.asarray(w)))
    for i in range(len(w)):
        assert pc[i] == bin(int(w[i])).count("1")
        expected_cz = 32 if w[i] == 0 else (int(w[i]) & -int(w[i])).bit_length() - 1
        assert cz[i] == expected_cz


def test_kernels_under_jit_and_grad_safe():
    # kernels are forward-only; ensure they compose under jit
    rng = np.random.default_rng(1)
    a = jnp.asarray(rand_u32(rng, (4096, 128), 0.01))
    f = jnp.asarray(rand_u32(rng, (128,), 0.2))

    @jax.jit
    def level(a, f):
        cand = core_spmv(a, f, interpret=True)
        return jnp.sum(jnp.where(cand < ref.BIG, 1, 0))

    assert int(level(a, f)) >= 0
