"""Quickstart: the paper's pipeline end-to-end in ~30 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp

from repro.core import (
    Graph500Config, TraversalPlan, compile_plan, run, validate,
)

# 1. Reference configuration (no customizations) ---------------------------
base = Graph500Config.ladder("reference-3.0.0", scale=10, n_roots=4)
built_b, res_b = run(base)
print(f"reference-3.0.0 : {res_b.harmonic_mean_teps / 1e9:.5f} GTEPS "
      f"(valid={res_b.all_valid})")

# 2. The customized Pre-G500 configuration ---------------------------------
#    degree sorting (T2a) + heavy-vertex dense core (T2b) + Pallas bitmap
#    kernels (T1). T3 (monitor comm) appears in the distributed runner —
#    see examples/distributed_bfs.py.
pre = Graph500Config.ladder("pre-g500", scale=10, n_roots=4,
                            heavy_threshold=8)
built_p, res_p = run(pre)
print(f"pre-g500        : {res_p.harmonic_mean_teps / 1e9:.5f} GTEPS "
      f"(valid={res_p.all_valid})")
print(f"heavy core      : K={built_p.core.k} vertices, "
      f"{int(built_p.core.core_nnz)} edges in the dense corner")

# 3. Inspect one BFS run (the spec→plan→runner API, DESIGN.md §10) ---------
plan = TraversalPlan(engine="bitmap", layout=(), batch_roots=False)
res = compile_plan(plan, built_p).bfs(0)
lv = int(res.stats.levels)
print(f"BFS from root 0 : {lv} levels, directions "
      f"{[int(d) for d in res.stats.direction[:lv]]} (0=top-down 1=bottom-up)")
print(f"validation      : {bool(validate(built_p.ev, res, jnp.int32(0)).ok)}")
