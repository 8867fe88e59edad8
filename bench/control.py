"""Read the control: the comparison of a cell, with the reference's
control answers (``reference.control_answer``: each search stops one
level short) in the program's place.

    python3 -m bench.control --workload g500-s21.keys64 --seeds 1,2,3

For each seed it builds the cell's graph at the cell's own size from the
benchmark's generator and compares control answers for the first
``--answers`` search keys (by default ``check_sample``, the most a run
compares).  Every number must come out above its limit of 0.  One JSON line per seed.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def roots_of(cell, seed: int, g, src, dst, cap: int):
    """The reference ids of the first ``cap`` search keys."""
    keys = cell.generator().make(cell.traffic, seed, src, dst, g.n)
    return [int(k) for k in g.new_from_old[keys][:cap]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--answers", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import gen, registry
    from bench.reference import HostGraph, compare, control_answer

    cell = registry.load_cell(args.workload)
    scale = int(cell.config["scale"])
    for seed in map(int, args.seeds.split(",")):
        src, dst = gen.kronecker_edges(seed, scale,
                                       int(cell.config["edge_factor"]))
        g = HostGraph(src, dst, 1 << scale)
        total = {"level_mismatch": 0, "parent_bad": 0}
        roots = roots_of(cell, seed, g, src, dst,
                         args.answers or int(cell.traffic["check_sample"]))
        least = None
        for r in roots:
            parent, level = control_answer(g, r)
            d = compare(g, r, parent, level, g.bfs_levels(r))
            for k in total:
                total[k] += d[k]
            bad = d["level_mismatch"] + d["parent_bad"]
            least = bad if least is None else min(least, bad)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "compared": len(roots), **total,
                          "least_per_answer": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
