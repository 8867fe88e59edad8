"""Small shared utilities."""
from __future__ import annotations

import dataclasses
import math
import os
import subprocess

import jax


def make_mesh(shape, axis_names):
    """A device mesh with ``Auto`` axes over the first ``prod(shape)``
    visible devices."""
    shape = tuple(int(s) for s in shape)
    axis_names = tuple(axis_names)
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"mesh shape {shape} needs {n} devices, have {len(devices)}")
    return jax.make_mesh(
        shape, axis_names, devices=devices[:n],
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def use_compile_cache(checkout: str) -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    A directory named by ``JAX_COMPILATION_CACHE_DIR`` is JAX's own
    setting and is left alone; otherwise the cache lives at the fixed
    path ``<checkout>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def host_device_env(n_devices: int, *, extra_env: dict | None = None,
                    pythonpath=()) -> dict:
    """A child-process environment forcing ``n_devices`` XLA host devices.

    The ONE copy of the XLA_FLAGS surgery every "respawn with N fake
    devices" caller used to hand-roll: any existing
    ``--xla_force_host_platform_device_count`` flag is replaced (never
    appended after) so the child's device view is exactly ``n_devices``
    whatever the parent's was.  ``pythonpath`` entries are *prepended* to
    the inherited ``PYTHONPATH``; ``extra_env`` is applied last so a
    caller can still override anything (including XLA_FLAGS itself).
    """
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={int(n_devices)}")
    env["XLA_FLAGS"] = " ".join(flags)
    if isinstance(pythonpath, (str, bytes)):
        pythonpath = (pythonpath,)
    if pythonpath:
        entries = [str(p) for p in pythonpath]
        if env.get("PYTHONPATH"):
            entries.append(env["PYTHONPATH"])
        env["PYTHONPATH"] = os.pathsep.join(entries)
    env.update(extra_env or {})
    return env


def respawn_with_host_devices(argv, n_devices: int, *,
                              extra_env: dict | None = None,
                              pythonpath=(), capture: bool = False,
                              timeout: float | None = None, cwd=None,
                              background: bool = False,
                              stdout=None, stderr=None):
    """Run ``argv`` in a child process seeing ``n_devices`` forced XLA
    host devices (the parent's JAX keeps its own device view).

    The shared respawn machinery behind the tuner's ``--devices N``
    re-exec, the sharded/serve benchmark children, the subprocess test
    harnesses and the multi-process launcher's worker bring-up:

      * ``background=False`` (default) — blocking ``subprocess.run``;
        returns the ``CompletedProcess`` (``capture=True`` for
        text-mode captured stdout/stderr, ``timeout`` in seconds).
      * ``background=True`` — non-blocking ``subprocess.Popen`` with the
        given ``stdout``/``stderr`` handles; returns the ``Popen`` (the
        multi-process launcher spawns one per rank and owns the
        wait/kill policy).

    Forced host devices exist only on the CPU backend.  On an
    accelerator this process holds the chip, so a child could neither
    reach it nor stand in for it: the call fails instead.
    """
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"refusing to start a child with {n_devices} forced host "
            f"devices: this process runs on the {backend} backend and "
            f"holds its devices — run the work in this process on "
            f"jax.devices() instead")
    env = host_device_env(n_devices, extra_env=extra_env,
                          pythonpath=pythonpath)
    if background:
        return subprocess.Popen(list(argv), env=env, cwd=cwd,
                                stdout=stdout, stderr=stderr, text=True)
    return subprocess.run(list(argv), env=env, cwd=cwd,
                          capture_output=capture, text=True, timeout=timeout)


def pytree_dataclass(cls=None, *, meta: tuple[str, ...] = ()):
    """Frozen dataclass registered as a pytree with static ``meta`` fields."""

    def wrap(c):
        c = dataclasses.dataclass(frozen=True)(c)
        fields = [f.name for f in dataclasses.fields(c)]
        data_fields = [f for f in fields if f not in meta]
        jax.tree_util.register_dataclass(
            c, data_fields=data_fields, meta_fields=list(meta)
        )
        return c

    return wrap if cls is None else wrap(cls)
