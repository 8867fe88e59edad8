"""Share of the slots a δ-stepping round reads that its frontier needs:
the frontier's degree summed over the window's rounds
(``BFSStats.scanned_edges``) over rounds times valid edge slots.  The
flat relax reads every slot in every round, so the rest is waste."""

LAYER = "engine"
UNIT = "%"
MOVES = "bfs_teps"


def read(ctx):
    sssp = ctx.shapes.get("sssp")
    if ctx.trace is None or sssp is None:
        return None
    slots = sssp["rounds"] * sssp["valid_slots"]
    return None if slots <= 0 else 100.0 * sssp["scanned_edges"] / slots
