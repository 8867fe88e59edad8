"""Main-path programs compile for a TPU v5e chip (no chip needed).

The TPU compiler compiles for a chip that is described, not attached, so
these tests catch what interpret mode cannot: Mosaic's block-shape rules,
fast-memory limits, and programs that no longer lower.  Widths are the
one-chip offline cell's (``chip_smoke.py``): scale 20, so a 32768-word
bitmap and the dense core's K.  The topology is described inside a
module fixture only — loading the TPU library at import time would give
pytest-xdist workers different test lists.
"""
import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import pipeline
from repro.core.heavy import padded_bitmap_words
from repro.core.plan import compile_plan
from repro.kernels import bitmap_ops, frontier_spmv
from repro.kernels import ops as kops

OFFLINE_SCALE = 20
BITMAP_WORDS = padded_bitmap_words(1 << OFFLINE_SCALE)   # 32768
CORE_K = 61440        # dense core rows at scale 20, threshold 100, seed 42
ROOTS = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("batch", [None, ROOTS], ids=["one", "vmap8"])
def test_frontier_update_compiles(one_chip, batch):
    fu = functools.partial(bitmap_ops.frontier_update, interpret=False)
    lead = () if batch is None else (batch,)
    x = _shape(lead + (BITMAP_WORDS,), jnp.uint32, one_chip)
    fn = fu if batch is None else jax.vmap(fu)
    assert "tpu_custom_call" in _compiled_text(fn, x, x)


@pytest.mark.parametrize("batch", [None, ROOTS], ids=["one", "vmap8"])
def test_core_spmv_compiles(one_chip, batch):
    spmv = functools.partial(frontier_spmv.core_spmv, interpret=False)
    a = _shape((CORE_K, CORE_K // 32), jnp.uint32, one_chip)
    lead = () if batch is None else (batch,)
    f = _shape(lead + (CORE_K // 32,), jnp.uint32, one_chip)
    fn = spmv if batch is None else jax.vmap(spmv, in_axes=(None, 0))
    assert "tpu_custom_call" in _compiled_text(fn, a, f)


def test_per_root_program_compiles(one_chip, monkeypatch):
    """One whole per-root ``_run_bitmap`` program, as ``compile_plan``
    builds it for the ``pre-g500`` rung, with compiled kernels."""
    cfg = pipeline.Graph500Config.ladder("pre-g500", scale=10, n_roots=1)
    compiled = compile_plan(cfg.to_plan(), pipeline.build(cfg))
    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    text = compiled.lower(0, sharding=one_chip).compile().as_text()
    assert text.count("tpu_custom_call") >= 2   # epilogue + dense core
