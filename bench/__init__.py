"""On-chip benchmark of the Graph500 traversal system.

One command runs one cell once (see ``bench/run.py``); ``BENCHMARK.json``
at the root of the checkout names the cells, configurations, traffic
mixes and metrics, and each of those lives in a file of its own here.
"""
