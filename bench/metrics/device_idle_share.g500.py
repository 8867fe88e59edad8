"""Share of the traced window in which no operation ran on the chip."""

LAYER = "device"
UNIT = "%"
MOVES = "bfs_teps"


def read(ctx):
    share = None if ctx.trace is None else ctx.trace.idle_share()
    return None if share is None else 100.0 * share
