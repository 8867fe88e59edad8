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
import re

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


@pytest.fixture(scope="module")
def per_root_text(one_chip):
    """The compiled text of one whole per-root ``_run_bitmap`` program,
    as ``compile_plan`` builds it for the ``pre-g500`` rung, with
    compiled kernels."""
    cfg = pipeline.Graph500Config.ladder("pre-g500", scale=10, n_roots=1)
    compiled = compile_plan(cfg.to_plan(), pipeline.build(cfg))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kops, "interpret_mode", lambda: False)
        return compiled.lower(0, sharding=one_chip).compile().as_text()


def test_per_root_program_compiles(per_root_text):
    assert per_root_text.count("tpu_custom_call") >= 2   # epilogue + core


_CALLEES = re.compile(r"(?:body|condition|calls|to_apply)=%([\w.\-]+)"
                      r"|branch_computations=\{([^}]*)\}")


def _computations(text) -> dict:
    """Computation name -> its instruction lines, from compiled HLO text."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) ", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None and line.strip():
            comps[name].append(line.strip())
    return comps


def _loop_computations(text, comps) -> set:
    """Names of the level loop's body and of every computation it calls
    (branches, inner loops, fusions)."""
    entry = re.search(r"^ENTRY %(\S+) ", text, re.M).group(1)
    loop = [i for i in comps[entry] if " while(" in i]
    assert len(loop) == 1, loop
    todo, seen = [re.search(r"body=%([\w.\-]+)", loop[0]).group(1)], set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for inst in comps[name]:
            for one, many in _CALLEES.findall(inst):
                todo += [one] if one else [
                    c.strip().lstrip("%") for c in many.split(",")]
    return seen


def _level_loop_ops(text) -> list:
    """The fusions and custom calls in the level loop's body and in every
    computation it calls."""
    comps = _computations(text)
    return [i for name in _loop_computations(text, comps)
            for i in comps[name]
            if re.search(r" (fusion|custom-call)\(", i)]


def _op_path(inst) -> list:
    m = re.search(r'op_name="([^"]*)"', inst)
    return m.group(1).split("/") if m else []


def test_per_root_level_loop_ops_carry_bfs_scopes(per_root_text):
    """Every fusion and custom call the level loop runs names its engine
    phase, so a device trace can charge its time to one.  Instructions
    XLA makes itself carry no traced path (no op_name, or the bare name
    of a cached JAX lowering such as ``reduce_window_sum``): no program
    code can scope those, and a trace shows their time under no phase."""
    ops = _level_loop_ops(per_root_text)
    traced = [i for i in ops if len(_op_path(i)) > 1]
    assert len(traced) >= 20, len(traced)
    unscoped = [i[:160] for i in traced
                if not any(p.startswith("bfs.") for p in _op_path(i))]
    assert not unscoped, unscoped
    phases = {p for i in traced for p in _op_path(i) if p.startswith("bfs.")}
    assert phases == {"bfs.epilogue", "bfs.td_relax", "bfs.bu_core",
                      "bfs.bu_relax"}, phases


def test_per_root_kernels_keep_their_names(per_root_text):
    """The Pallas kernels compile to custom calls named after their
    wrappers, which the roofline readers match by name."""
    kernels = re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target="
                         r'"tpu_custom_call"', per_root_text)
    assert sorted(set(kernels)) == ["core_spmv", "frontier_update"], kernels


def _scatters(text, scope) -> list:
    """Instructions, fused ones included, that scatter under ``scope``."""
    return [i.strip()[:160] for i in text.splitlines()
            if scope in _op_path(i)
            and (" scatter(" in i
                 or any(p.startswith("scatter") for p in _op_path(i)))]


def test_per_root_bu_relax_has_no_scatter(per_root_text):
    """The bottom-up relax is a pull: a bit gather per slot and a dense
    segmented min, with no scatter.  The dense core's winners still
    scatter-min, which shows the search would see one."""
    assert any("bfs.bu_relax" in _op_path(i)
               for i in _level_loop_ops(per_root_text))
    assert not _scatters(per_root_text, "bfs.bu_relax"), \
        _scatters(per_root_text, "bfs.bu_relax")
    assert _scatters(per_root_text, "bfs.bu_core")


@pytest.fixture(scope="module")
def sssp_text(one_chip):
    """The compiled text of one per-root δ-stepping program, as
    ``compile_plan`` builds it for Graph500 kernel 3."""
    cfg = pipeline.Graph500Config.ladder("pre-g500", scale=10, n_roots=1,
                                         heavy_threshold=None, kernel="sssp")
    compiled = compile_plan(cfg.to_plan(), pipeline.build(cfg))
    return compiled.lower(0, sharding=one_chip).compile().as_text()


def test_sssp_round_loop_ops_carry_sssp_scopes(sssp_text):
    """Every fusion the δ-stepping round loop runs names its phase, so a
    device trace can charge its time to one; the kernel-2 kernels are not
    on this path."""
    ops = _level_loop_ops(sssp_text)
    traced = [i for i in ops if len(_op_path(i)) > 1]
    assert len(traced) >= 5, len(traced)
    unscoped = [i[:160] for i in traced
                if not any(p.startswith("sssp.") for p in _op_path(i))]
    assert not unscoped, unscoped
    phases = {p for i in traced for p in _op_path(i)
              if p.startswith("sssp.")}
    assert phases == {"sssp.select", "sssp.relax"}, phases
    assert "tpu_custom_call" not in sssp_text


@pytest.fixture(scope="module")
def sssp_sharded_text(topo):
    """The compiled text of the 2x2 vertex-sharded δ-stepping program
    (``hier_min``), lowered from shapes on the described chips."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.plan import vertex_sharded_program

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("group", "member"))
    n_dev, w_loc, n_chunks, chunk = 4, 8, 4, 1024
    step = vertex_sharded_program(
        mesh, w_loc=w_loc, n_dev=n_dev, exchange="hier_min", kernel="sssp",
        delta=1 << 19, max_rounds=512)
    rep = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P(("group", "member")))
    slots = (n_dev, n_chunks, chunk)
    args = [_shape((), jnp.int32, rep)] + [
        _shape(sh, dt, shard) for sh, dt in (
            (slots, jnp.int32), (slots, jnp.int32), (slots, jnp.bool_),
            ((n_dev, n_chunks), jnp.int32), ((n_dev, n_chunks), jnp.int32),
            (slots, jnp.uint32), ((n_dev, 32 * w_loc), jnp.int32))
    ] + [_shape((), jnp.int32, rep)]
    return _compiled_text(step, *args)


@pytest.mark.parametrize("engine", ["per_root", "sharded_2x2"])
def test_sssp_parents_built_once_after_the_round_loop(
        engine, sssp_text, sssp_sharded_text):
    """Pass B runs once per search: no instruction of the round loop
    carries ``sssp.parent``, and the min-source scatter under that scope
    sits outside the loop."""
    text = sssp_text if engine == "per_root" else sssp_sharded_text
    comps = _computations(text)
    in_loop = _loop_computations(text, comps)
    looped = [i[:160] for name in in_loop for i in comps[name]
              if "sssp.parent" in _op_path(i)]
    assert not looped, looped
    outside = [i for name, insts in comps.items() if name not in in_loop
               for i in insts]
    assert _scatters("\n".join(outside), "sssp.parent")
    assert any("sssp.relax" in _op_path(i) for name in in_loop
               for i in comps[name])


def test_batched_program_keeps_one_copy_of_the_core(one_chip):
    """Batched over roots, the bottom-up step reads the dense core as it
    is: no instruction holds a per-root copy of it, which a vmapped
    ``cond`` with each root's own direction would broadcast to the batch
    on every level."""
    cfg = pipeline.Graph500Config.ladder("pre-g500-batch", scale=10,
                                         n_roots=ROOTS)
    built = pipeline.build(cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kops, "interpret_mode", lambda: False)
        compiled = compile_plan(cfg.to_plan(), built)
        text = compiled.lower(list(range(ROOTS)),
                              sharding=one_chip).compile().as_text()
    per_root = ",".join(map(str, (ROOTS,) + built.core.a_core.shape))
    assert "tpu_custom_call" in text
    assert f"[{per_root}]" not in text
