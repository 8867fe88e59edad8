"""Launchers: production mesh, dry-run, training CLI.

NOTE: do not import ``repro.launch.dryrun`` from library code — it sets
XLA_FLAGS for 512 host devices at import time (by design, per spec).
"""
from repro.launch import mesh

__all__ = ["mesh", "multiprocess"]


def __getattr__(name):
    # multiprocess imported lazily: the worker path must call
    # jax.distributed.initialize before any jax backend touch, so keep
    # this module's import side-effect-free for it.
    if name == "multiprocess":
        from repro.launch import multiprocess
        return multiprocess
    raise AttributeError(name)
