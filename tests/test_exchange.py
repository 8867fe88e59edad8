"""The exchange layer by itself (DESIGN.md §9, §16).

Each per-level exchange is called as the engines call it, under
``shard_map`` on a (2, 4) ("group", "member") mesh of 8 host devices,
on random inputs, and compared with NumPy:

  * the BFS delta exchanges (``hier_or``, ``hier_gather``, ``flat``),
    through ``hybrid_bfs._exchange_delta``: every shard sets bits only
    in the words it owns, and every shard must get back the OR of all
    shards' words at their global positions;
  * the SSSP distance exchanges: ``hier_min`` through
    ``hierarchical_pmin``, ``flat`` through ``_min_all_reduce`` over
    both axes; every shard must get back the element-wise min of all
    contributions.

Both vertex partitions place the owned words.  One subprocess (the main
pytest process keeps seeing 1 device) runs every case and prints a
verdict line per case; each parametrized test reads its own line.
"""
import os
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from repro.util import respawn_with_host_devices  # noqa: E402

BFS_EXCHANGES = ("hier_or", "hier_gather", "flat")
SSSP_EXCHANGES = ("hier_min", "flat")
PARTITIONS = ("block", "word_cyclic")

CASES = """
import traceback
import numpy as np
import jax
from jax.sharding import PartitionSpec as P
from repro.comms import hierarchical as hier
from repro.core.hybrid_bfs import _exchange_delta, _shard_index
from repro.util import make_mesh

G, M = 2, 4
N_DEV, W_LOC = G * M, 6
W_PAD = N_DEV * W_LOC
V_PAD = W_PAD * 32
INF = np.uint32(0xFFFFFFFF)
mesh = make_mesh((G, M), ("group", "member"))
VA = ("group", "member")


def global_word(d, j, partition):
    # block: device d owns words [d*W_LOC, (d+1)*W_LOC); word_cyclic:
    # device d owns words {w : w % N_DEV == d}, local word j is d + j*N_DEV
    return d * W_LOC + j if partition == "block" else d + j * N_DEV


def spmd(local, x):
    fn = jax.jit(jax.shard_map(lambda a: local(a[0])[None], mesh=mesh,
                               in_specs=P(VA), out_specs=P(VA),
                               check_vma=False))
    return np.asarray(fn(x))


def bfs_case(exchange, partition, rng):
    dens = rng.uniform(0.05, 0.5, size=(N_DEV, W_LOC, 32))
    bits = rng.random((N_DEV, W_LOC, 32)) < dens
    deltas = (bits.astype(np.uint64) << np.arange(32, dtype=np.uint64)
              ).sum(axis=-1).astype(np.uint32)
    want = np.zeros(W_PAD, np.uint32)
    for d in range(N_DEV):
        for j in range(W_LOC):
            want[global_word(d, j, partition)] |= deltas[d, j]

    def local(delta):
        return _exchange_delta(
            delta, _shard_index("group", "member"), W_LOC, N_DEV,
            exchange=exchange, group_axis="group", member_axis="member",
            partition=partition)

    got = spmd(local, deltas)
    assert got.shape == (N_DEV, W_PAD), got.shape
    for d in range(N_DEV):
        assert np.array_equal(got[d], want), \
            (d, np.flatnonzero(got[d] != want))


def sssp_case(exchange, partition, rng):
    contrib = np.full((N_DEV, V_PAD), INF, np.uint32)
    for d in range(N_DEV):
        for j in range(W_LOC):
            w = global_word(d, j, partition)
            contrib[d, w * 32:(w + 1) * 32] = rng.integers(
                0, 2**31, 32, dtype=np.uint32)
        # stray contributions outside the owned slots: the exchange is a
        # min over everything, not a gather of the owned slots
        stray = rng.random(V_PAD) < 0.1
        contrib[d, stray] = np.minimum(
            contrib[d, stray],
            rng.integers(0, 2**31, int(stray.sum()), dtype=np.uint32))
    contrib[:, rng.random(V_PAD) < 0.05] = INF    # slots no shard reaches
    want = contrib.min(axis=0)

    if exchange == "hier_min":
        def local(x):
            return hier.hierarchical_pmin(x, "group", "member")
    else:
        def local(x):
            return hier._min_all_reduce(x, VA)

    got = spmd(local, contrib)
    assert got.shape == (N_DEV, V_PAD), got.shape
    for d in range(N_DEV):
        assert np.array_equal(got[d], want), \
            (d, np.flatnonzero(got[d] != want))


cases = ([("bfs", e, p) for e in BFS_EXCHANGES for p in PARTITIONS]
         + [("sssp", e, p) for e in SSSP_EXCHANGES for p in PARTITIONS])
for i, (kernel, exchange, partition) in enumerate(cases):
    rng = np.random.default_rng(1000 + i)
    try:
        case = bfs_case if kernel == "bfs" else sssp_case
        case(exchange, partition, rng)
        verdict = "OK"
    except Exception:
        verdict = "FAIL " + traceback.format_exc().replace(chr(10), " | ")
    print(f"CASE {kernel} {exchange} {partition} {verdict}", flush=True)
"""


@pytest.fixture(scope="module")
def verdicts() -> dict:
    out = respawn_with_host_devices(
        [sys.executable, "-c",
         f"BFS_EXCHANGES = {BFS_EXCHANGES!r}\n"
         f"SSSP_EXCHANGES = {SSSP_EXCHANGES!r}\n"
         f"PARTITIONS = {PARTITIONS!r}\n" + textwrap.dedent(CASES)], 8,
        pythonpath=(REPO_SRC,), capture=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = {}
    for line in out.stdout.splitlines():
        if line.startswith("CASE "):
            kernel, exchange, partition, rest = line[5:].split(" ", 3)
            got[(kernel, exchange, partition)] = rest
    return got


@pytest.mark.parametrize("partition", PARTITIONS)
@pytest.mark.parametrize("exchange", BFS_EXCHANGES)
def test_bfs_exchange_returns_or_of_all_shards(verdicts, exchange,
                                               partition):
    assert verdicts.get(("bfs", exchange, partition)) == "OK", \
        verdicts.get(("bfs", exchange, partition), "case did not run")


@pytest.mark.parametrize("partition", PARTITIONS)
@pytest.mark.parametrize("exchange", SSSP_EXCHANGES)
def test_sssp_distance_exchange_returns_min_of_contributions(
        verdicts, exchange, partition):
    assert verdicts.get(("sssp", exchange, partition)) == "OK", \
        verdicts.get(("sssp", exchange, partition), "case did not run")
