"""Real cross-process BFS: 2 JAX processes x 2 devices over localhost.

    PYTHONPATH=src python examples/multiprocess_bfs.py

Everything else in this repo fakes its device count inside one process,
so the per-level frontier exchange is a memcpy.  This demo runs the
SAME ``compile_plan`` program on a worker gang spawned by
``repro.launch.multiprocess`` (DESIGN.md §15): each "node" is a real OS
process, ``jax.distributed.initialize`` forms the global 2x2 mesh over
localhost TCP, and the inter-group leg of the T3 monitor collective
crosses a process boundary.  Rank 0's payload carries the
:class:`~repro.core.teps.Graph500Run` bookkeeping, the bitwise-parity
verdict vs the single-device oracle, and the measured per-level
exchange seconds.

The same topology is also reachable through the pipeline config::

    from repro.core import pipeline
    cfg = pipeline.Graph500Config(scale=10, procs=2, devices_per_proc=2,
                                  batched=True, seed=1)
    built, g500 = pipeline.run(cfg)     # runs on 2 real processes
"""
import sys

from repro.launch.multiprocess import launch

SCALE = 10

print(f"launching 2 processes x 2 devices, scale {SCALE} "
      f"(rendezvous over localhost TCP)...")
payload = launch(2, 2, scale=SCALE, n_roots=4, seed=1, reps=2,
                 exchanges="hier_or,hier_gather", partitions="block")

assert payload["parents_bitwise_identical"] is True
print(f"workers: {payload['procs']} procs x {payload['devices_per_proc']} "
      f"devices, jax {payload['jax']} ({payload['backend']}), "
      f"rank logs in {payload['log_dir']}")

for name, rung in sorted(payload["rungs"].items()):
    assert rung["identical"], name
    assert rung["parent_sha256"] == payload["oracle_sha256"], name
    exch = rung["exchange_seconds"]
    print(f"\n{name}: parents bitwise-identical to the single-device "
          f"oracle, hmean {rung['harmonic_mean_teps']:.3g} TEPS")
    print(f"  measured exchange wall-clock over {exch['levels']} levels: "
          f"{exch['total_seconds']*1e3:.1f} ms")
    for lv in exch["per_level"]:
        print(f"    level {lv['level']}: frontier {lv['frontier']:>5} "
              f"measured {lv['seconds']*1e3:7.2f} ms")

print("\nOK: cross-process exchange measured, parity held on every rung")
sys.exit(0)
