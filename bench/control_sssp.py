"""Read the control of a kernel-3 cell: its comparison, with the
reference one bit coarser (``reference_sssp.control_answer``: weights
on the 2**-19 grid) in the program's place.

    python3 -m bench.control_sssp --workload g500-sssp-s18.keys64 --seeds 1,2

For each seed it builds the cell's graph at the cell's own size from the
benchmark's generator and compares control answers for the first
``--answers`` search keys (by default ``check_sample``).  ``dist_mismatch``
must come out above its limit of 0.  One JSON line per seed.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--answers", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import gen, reference_sssp, registry
    from bench.control import roots_of
    from bench.reference import HostGraph

    cell = registry.load_cell(args.workload)
    scale = int(cell.config["scale"])
    for seed in map(int, args.seeds.split(",")):
        src, dst = gen.kronecker_edges(seed, scale,
                                       int(cell.config["edge_factor"]))
        g = HostGraph(src, dst, 1 << scale)
        w = reference_sssp.slot_weights(g, seed)
        roots = roots_of(cell, seed, g, src, dst,
                         args.answers or int(cell.traffic["check_sample"]))
        ref = reference_sssp.shortest_distances(g, w, roots)
        total = {"dist_mismatch": 0, "parent_bad": 0}
        least = None
        for r, rd in zip(roots, ref):
            parent, dist = reference_sssp.control_answer(g, w, r)
            d = reference_sssp.compare(g, w, r, parent, dist, rd)
            for k in total:
                total[k] += d[k]
            least = (d["dist_mismatch"] if least is None
                     else min(least, d["dist_mismatch"]))
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "compared": len(roots), **total,
                          "least_dist_mismatch_per_answer": least}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
