"""The control of the correctness check: the reference put in the
program's place, with one guarantee of the configuration broken.

The guarantee broken is the BFS-tree rule that a parent lies one level
nearer the root.  The control searches level by level as the reference
does, but gives each newly reached vertex, as its parent, the
smallest-id neighbour already marked when the level ends, the vertices
marked in this same level among them: the race of a bottom-up sweep that
marks vertices in place instead of into a second frontier, which would
save a level's buffer.  The reached set and the depths are right; some
parents lie on the same level as their child.  The benchmark's check has
to call such trees wrong.

    python bench/control.py --workload <cell> --seeds 11 12 13 \\
        --searches 13

prints, for each seed, the numbers the check compares beside their
limits, for the first ``--searches`` keys the cell's traffic sends.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import harness
import reference
import traffic as traffic_gen


def control_parents(g: reference.Graph, root: int) -> np.ndarray:
    depth = reference.bfs_depths(g, root)
    rows = np.repeat(np.arange(g.n, dtype=np.int64), g.degree)
    cols = g.adj
    marked = (depth[rows] >= 0) & (depth[rows] <= depth[cols]) \
        & (cols != root)
    parent = np.full(g.n, g.n, np.int64)
    np.minimum.at(parent, cols[marked], rows[marked])
    parent[parent == g.n] = -1
    parent[root] = root
    return parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--searches", type=int, required=True)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    g = reference.generate(cell.config["generator"], cell.config["scale"])
    for seed in args.seeds:
        keys = traffic_gen.draw_keys(cell.pool, seed)
        roots = [int(keys[i % len(keys)]) for i in range(args.searches)]
        bad = harness.check(g, roots, [control_parents(g, r) for r in roots])
        print(json.dumps({
            "seed": seed, "searches": len(roots),
            "bad_vertices": {"value": int(sum(bad)), "limit": 0},
            "bad_searches": {"value": sum(b > 0 for b in bad), "limit": 0},
            "per_search": bad}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
