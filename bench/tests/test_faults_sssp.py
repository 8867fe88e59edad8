"""A run of the kernel-3 cell with its timed path broken underneath reads
``correct`` false; a sound one reads true.  The faults: a search that
returns its state unchanged, one distance altered, and one parent
altered, each where the answer is produced."""
import jax.numpy as jnp
import pytest

from repro.core.plan import CompiledBFS

from bench.tests.faults import drive, small

SCALE = 9
METRICS = ("setup_s", "bfs_teps")
real_bfs = CompiledBFS.bfs


def unchanged(self, roots):
    res = real_bfs(self, roots)
    r = jnp.asarray(roots, jnp.int32)
    v = res.parent.shape[-1]
    parent = jnp.full((v,), -1, jnp.int32).at[r].set(r)
    dist = jnp.full((v,), -1, jnp.int32).at[r].set(0)
    return res._replace(parent=parent, level=dist)


def distance_altered(self, roots):
    res = real_bfs(self, roots)
    far = jnp.argmax(res.level)            # the farthest vertex
    return res._replace(level=res.level.at[far].add(1))


def parent_altered(self, roots):
    res = real_bfs(self, roots)
    far = jnp.argmax(res.level)
    return res._replace(parent=res.parent.at[far].set(far))


def test_sound_run_is_correct():
    out = drive(small("g500-sssp-s18", "keys64", SCALE, METRICS))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 1
    assert out["metrics"]["bfs_teps"]["value"] > 0
    assert list(out["checks"]) == ["dist_mismatch", "parent_bad",
                                   "key_mismatch"]
    assert all(c["value"] == 0 for c in out["checks"].values())


@pytest.mark.parametrize("fault", [unchanged, distance_altered,
                                   parent_altered],
                         ids=["state_unchanged", "distance_altered",
                              "parent_altered"])
def test_fault_reads_incorrect(monkeypatch, fault):
    monkeypatch.setattr(CompiledBFS, "bfs", fault)
    out = drive(small("g500-sssp-s18", "keys64", SCALE, METRICS))
    assert out["correct"] is False
    assert out["failed"] > 0
