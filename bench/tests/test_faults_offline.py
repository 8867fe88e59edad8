"""A run of the Graph500 cell with its timed path broken underneath
reads ``correct`` false; a sound one reads true.  The cell has two of
the faults a run can have: a search that returns its state unchanged,
and an answer altered where it is produced (one chip, no batch: no
exchange to leave out and no batch to halve)."""
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
    level = jnp.full((v,), -1, jnp.int32).at[r].set(0)
    return res._replace(parent=parent, level=level)


def altered(self, roots):
    res = real_bfs(self, roots)
    far = jnp.argmax(res.level)            # a vertex at the deepest level
    return res._replace(parent=res.parent.at[far].set(far))


def test_sound_run_is_correct():
    out = drive(small("g500-bfs-s21", "keys64", SCALE, METRICS))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 1
    assert out["metrics"]["bfs_teps"]["value"] > 0
    assert list(out["checks"]) == ["level_mismatch", "parent_bad",
                                   "key_mismatch"]
    assert list(out)[-2:] == ["checks", "notes"]


@pytest.mark.parametrize("fault", [unchanged, altered],
                         ids=["state_unchanged", "answer_altered"])
def test_fault_reads_incorrect(monkeypatch, fault):
    monkeypatch.setattr(CompiledBFS, "bfs", fault)
    out = drive(small("g500-bfs-s21", "keys64", SCALE, METRICS))
    assert out["correct"] is False
    assert out["failed"] > 0
