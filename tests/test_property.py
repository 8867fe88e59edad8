"""Property-based tests (hypothesis) on the system's invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.heavy import pack_bitmap, unpack_bitmap
from repro.core.heavy import testbit as bit_at  # alias: pytest must not collect
from repro.core.reorder import degree_reorder
from repro.comms.topology import TreeTopology, elect_monitors
from repro.kernels import ref
from repro.models.moe import MoEDims, _route

SMALL = settings(max_examples=25, deadline=None)


@SMALL
@given(st.lists(st.booleans(), min_size=1, max_size=300))
def test_bitmap_roundtrip(bits):
    mask = jnp.asarray(np.array(bits))
    w = (len(bits) + 31) // 32
    bm = pack_bitmap(mask, w)
    back = unpack_bitmap(bm, len(bits))
    assert np.array_equal(np.asarray(back), np.array(bits))


@SMALL
@given(st.lists(st.booleans(), min_size=1, max_size=300),
       st.integers(0, 10_000))
def test_bitmap_testbit_agrees_with_mask(bits, seed):
    mask = np.array(bits)
    bm = pack_bitmap(jnp.asarray(mask), (len(bits) + 31) // 32)
    idx = np.random.default_rng(seed).integers(0, len(bits), size=32)
    got = np.asarray(bit_at(bm, jnp.asarray(idx, jnp.int32)))
    assert np.array_equal(got, mask[idx])


@SMALL
@given(st.lists(st.integers(0, 1000), min_size=1, max_size=200))
def test_degree_reorder_always_permutation(degrees):
    d = jnp.asarray(np.array(degrees, np.int32))
    r = degree_reorder(d)
    ofn = np.asarray(r.old_from_new)
    assert sorted(ofn.tolist()) == list(range(len(degrees)))
    ds = np.asarray(r.degree_sorted)
    assert np.all(np.diff(ds) <= 0)
    assert int(r.n_active) == int((np.array(degrees) > 0).sum())


@SMALL
@given(st.integers(0, 2**32 - 1))
def test_popcount_ctz_single(w):
    arr = jnp.asarray(np.array([w], np.uint32))
    assert int(ref.popcount_u32(arr)[0]) == bin(w).count("1")
    expected = 32 if w == 0 else (w & -w).bit_length() - 1
    assert int(ref.ctz_u32(arr)[0]) == expected


@SMALL
@given(st.integers(2, 6), st.integers(2, 6))
def test_topology_hops_symmetric_triangle(f0, f1):
    topo = TreeTopology((f0, f1))
    n = topo.n_nodes
    rng = np.random.default_rng(f0 * 7 + f1)
    a = rng.integers(0, n, 50)
    b = rng.integers(0, n, 50)
    c = rng.integers(0, n, 50)
    hab = topo.hops(a, b)
    hba = topo.hops(b, a)
    np.testing.assert_array_equal(hab, hba)          # symmetry
    assert np.all(topo.hops(a, a) == 0)              # identity
    # tree-metric triangle inequality
    assert np.all(topo.hops(a, c) <= topo.hops(a, b) + topo.hops(b, c))


@SMALL
@given(st.integers(0, 10_000))
def test_monitor_election_deterministic_given_seed(seed):
    topo = TreeTopology((4, 4))
    rng = np.random.default_rng(seed)
    w = rng.random(topo.n_nodes)
    p1 = elect_monitors(topo, w, "orchestra", seed=0)
    p2 = elect_monitors(topo, w, "orchestra", seed=0)
    np.testing.assert_array_equal(p1.monitors, p2.monitors)


@SMALL
@given(st.integers(1, 8), st.integers(2, 16), st.integers(1, 4))
def test_moe_route_slots_within_capacity(seed, t, k):
    e = 4
    k = min(k, e)
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(t, e)).astype(np.float32))
    cap = max(1, (t * k) // e)
    dims = MoEDims(d_model=4, d_ff=8, n_experts=e, top_k=k)
    slot, gate, aux = _route(logits, dims, cap)
    s = np.asarray(slot)
    # every slot is either the drop bucket or within [0, e*cap)
    assert np.all((s == e * cap) | ((s >= 0) & (s < e * cap)))
    # no slot collision among kept pairs
    kept = s[s < e * cap]
    assert len(np.unique(kept)) == len(kept)
    # gates normalized per token
    g = np.asarray(gate).reshape(t, k)
    np.testing.assert_allclose(g.sum(1), 1.0, rtol=1e-4)


@SMALL
@given(st.integers(0, 1000))
def test_kronecker_edges_in_range(seed):
    from repro.core import generate_edges
    e = generate_edges(seed, 6, 4)
    s = np.asarray(e.src)
    d = np.asarray(e.dst)
    assert s.min() >= 0 and s.max() < 64
    assert d.min() >= 0 and d.max() < 64
