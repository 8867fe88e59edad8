"""The benchmark's copy of the Kronecker generator equals the program's
for a seed, and its search keys are the Graph500 specification's."""
import numpy as np
import pytest

from bench import gen

SEED = 2**31 + 99  # the driver's seeds run past 32 signed bits


@pytest.mark.parametrize("scale,edge_factor", [(8, 16), (10, 4)])
def test_kronecker_copy_equals_program(scale, edge_factor):
    from repro.core import kronecker

    src, dst = gen.kronecker_edges(SEED, scale, edge_factor)
    want = kronecker.generate_edges(SEED, scale, edge_factor)
    np.testing.assert_array_equal(np.asarray(src), np.asarray(want.src))
    np.testing.assert_array_equal(np.asarray(dst), np.asarray(want.dst))


def test_search_keys_are_the_vertices_with_an_edge():
    # 0 has only a self loop, 5 no edge at all, 1-4 an edge each.
    src = np.array([0, 1, 2, 3], np.int32)
    dst = np.array([0, 2, 1, 4], np.int32)
    keys = gen.search_keys(SEED, src, dst, 6, 4)
    assert sorted(keys.tolist()) == [1, 2, 3, 4]
    with pytest.raises(ValueError):
        gen.search_keys(SEED, src, dst, 6, 5)


@pytest.mark.parametrize("seed", [SEED, 2**32 + 7, 0])
def test_search_keys_are_unique_and_fixed_by_the_seed(seed):
    src, dst = gen.kronecker_edges(SEED, 9)
    keys = gen.search_keys(seed, src, dst, 1 << 9, 64)
    assert keys.dtype == np.int32 and len(np.unique(keys)) == 64
    edge = src != dst
    assert np.all(np.isin(keys, np.concatenate([src[edge], dst[edge]])))
    np.testing.assert_array_equal(
        keys, gen.search_keys(seed, src, dst, 1 << 9, 64))
    assert not np.array_equal(
        keys, gen.search_keys(seed + 1, src, dst, 1 << 9, 64))


def test_search_keys_do_not_favour_hubs():
    # A star: drawing edge endpoints would pick the hub half of the
    # time; drawing vertices uniformly, one time in a hundred per key.
    leaves = np.arange(1, 100, dtype=np.int32)
    src, dst = np.zeros_like(leaves), leaves
    hub = [0 in gen.search_keys(SEED + i, src, dst, 100, 1).tolist()
           for i in range(400)]
    assert np.mean(hub) < 0.05
