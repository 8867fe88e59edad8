"""Device time per δ-stepping round: the busy time of the traced window
over the rounds its searches ran (``BFSStats.levels``, summed by the
kernel-3 driver)."""

LAYER = "engine"
UNIT = "ms"
MOVES = "bfs_teps"


def read(ctx):
    sssp = ctx.shapes.get("sssp")
    if ctx.trace is None or sssp is None or sssp["rounds"] <= 0:
        return None
    busy = ctx.trace.busy_s()
    return None if busy <= 0 else 1e3 * busy / sssp["rounds"]
