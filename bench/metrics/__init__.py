"""Per-layer metric readers, one file per metric named as in
BENCHMARK.json.  Each gives ``LAYER``, ``UNIT``, ``MOVES`` and
``read(ctx)``, which returns the value or None when the run holds
nothing to read."""
