"""Record the small CPU trace that ``bench/tests/test_trace.py`` reduces.

    JAX_PLATFORMS=cpu python bench/tests/data/record_trace.py

Three jitted sort calls under ``search`` spans inside a ``window`` span,
with host sleeps between them.  On the CPU the XLA ops run on several
host threads, so their intervals overlap; they stand in for one chip's
device ops.  Writes ``cpu_trace.json``: ops and spans as ``[name,
start_ns, end_ns]``, times relative to the window's start.
"""
import glob
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

HERE = os.path.dirname(os.path.abspath(__file__))
SKIP = ("end: ", "Threadpool", "ThunkExecutor")


def main():
    f = jax.jit(lambda x: jnp.sort(x * 2.0 + 1.0))
    x = jnp.arange(200_000, dtype=jnp.float32)
    f(x).block_until_ready()
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("search"):
                f(x).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    ops, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if line.name == "python" and e.name in ("window", "search"):
                    spans.append([e.name, e.start_ns, e.end_ns])
                elif (line.name.startswith("tf_XLA") and e.duration_ns > 0
                      and not e.name.startswith(SKIP)):
                    ops.append([e.name, e.start_ns, e.end_ns])
    t0 = min(a for n, a, _ in spans if n == "window")
    rel = lambda xs: sorted([n, a - t0, b - t0] for n, a, b in xs)
    with open(os.path.join(HERE, "cpu_trace.json"), "w") as fh:
        json.dump({"ops": rel(ops), "spans": rel(spans)}, fh, indent=0)


if __name__ == "__main__":
    main()
