"""The one traffic generator: search keys for a closed loop, drawn from
the run's seed, the parameters of a traffic file, and the key pool of
the cell's graph.

A traffic file (``bench/traffic/<name>.json``) holds:

  loop        "closed": the next search is sent when the last returns
  in_flight   searches in flight at once (1)
  keys        search keys per seed (Graph500: 64)
  min_degree  a key has at least this many neighbours (Graph500: 1)
  pool        candidate keys in the pool, drawn uniformly among the
              vertices of at least ``min_degree`` neighbours
  pool_seed   seed of that draw
  order       "cycle": the window searches the keys in order, and
              starts again at the first when it has searched them all
  alpha, beta Beamer's direction switch (SC'12): a level runs bottom-up
              once the frontier's edges outweigh the unvisited ones'
              divided by ``alpha``, and top-down again once the
              frontier holds fewer than n / ``beta`` vertices
  margin      a candidate is left out where any switch it meets lies
              within this share of its threshold

A search's time is set by its levels and by the direction of each: a
bottom-up level costs more than a top-down one.  A key's work class is
that sequence, one letter a level ("TTBBBTT": seven levels, the third
to fifth bottom-up), as the switch above gives it from the frontier
sizes of the plain reference's search.  Keys drawn uniformly give every
seed another mix of classes, and a window of a few searches would then
measure the mix.  So every seed searches the same sequence of classes:
those of the pool's first ``keys`` candidates.  The run's seed draws,
for each of them in turn, a candidate of the same class.  The keys
differ from seed to seed; the work does not.  The margin keeps out the
few keys whose class rounding could tip.

The pool of a configuration's graph is data, made once by

    python bench/traffic.py --config bench/configs/<config>.json \\
        --traffic <traffic>

from the plain reference, and kept in ``bench/keys/<config>/<traffic>.json``
with the work class of every candidate and a warm-up key: a vertex of
least degree, whose search runs the same program in fewest levels.
A run reads it and searches no graph of its own before the window.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from collections import Counter

import numpy as np

import reference

BENCH = pathlib.Path(__file__).resolve().parent
TRAFFIC_DIR = BENCH / "traffic"
KEYS_DIR = BENCH / "keys"
_BATCH = 64       # work classes are found 64 candidates at a time


def load(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic file {path}")
    traffic = json.loads(path.read_text())
    traffic["name"] = name
    if traffic.get("loop") != "closed" or traffic.get("in_flight") != 1:
        raise ValueError(f"{name}: the generator drives a closed loop with "
                         f"one search in flight")
    if traffic.get("order") != "cycle":
        raise ValueError(f"{name}: unknown order {traffic.get('order')!r}")
    return traffic


def _graph_of(config: dict) -> dict:
    return {"generator": config["generator"], "scale": config["scale"]}


def work_class(depth: np.ndarray, degree: np.ndarray, alpha: float,
               beta: float) -> tuple[str, float]:
    """(class, margin) of the search that ``depth`` (the reference's
    depths from its root, -1 unreached) describes: one letter a level,
    "T" top-down or "B" bottom-up, by Beamer's switch read after each
    level, and the nearest that any switch read before the last level
    came to its threshold, as a share of it."""
    reached = depth >= 0
    d = depth[reached].astype(np.int64)
    levels = int(d.max()) + 1
    n_f = np.bincount(d, minlength=levels + 1).astype(np.float64)
    m_f = np.bincount(d, weights=degree[reached],
                      minlength=levels + 1).astype(np.float64)
    m_u = float(degree.sum()) - np.cumsum(m_f)   # unvisited after level L
    n_total = float(depth.size)
    mode, letters, margin = 0, [], np.inf
    go_bu, go_td = m_f[0] * alpha > m_u[0], False
    for lv in range(levels):
        mode = 1 if go_bu else 0 if go_td else mode
        letters.append("TB"[mode])
        if lv + 1 == levels:
            break
        if mode == 0:
            lhs, rhs = m_f[lv + 1] * alpha, m_u[lv + 1]
            go_bu, go_td = lhs > rhs, False
        else:
            lhs, rhs = n_f[lv + 1] * beta, n_total
            go_bu, go_td = False, lhs < rhs
        margin = min(margin, abs(lhs - rhs) / max(rhs, 1.0))
    return "".join(letters), float(margin)


def make_pool(config: dict, traffic: dict, g: reference.Graph) -> dict:
    """The key pool of ``config``'s graph ``g`` for ``traffic``."""
    eligible = np.flatnonzero(g.degree >= traffic["min_degree"])
    if eligible.size < traffic["pool"]:
        raise ValueError(f"{eligible.size} eligible keys, need "
                         f"{traffic['pool']}")
    cand = np.random.default_rng(traffic["pool_seed"]).choice(
        eligible, traffic["pool"], replace=False)
    degree = g.degree.astype(np.float64)
    classes = []
    for lo in range(0, cand.size, _BATCH):
        for depth in reference.bfs_depths_many(g, cand[lo:lo + _BATCH]):
            cls, margin = work_class(depth, degree, traffic["alpha"],
                                     traffic["beta"])
            classes.append(cls if margin >= traffic["margin"] else None)
    kept = [(int(v), c) for v, c in zip(cand, classes) if c is not None]
    sizes = [c for _, c in kept[:traffic["keys"]]]
    if len(sizes) < traffic["keys"]:
        raise ValueError(f"{len(kept)} candidates clear the margin, need "
                         f"{traffic['keys']}")
    groups = {}
    for v, c in kept:
        groups.setdefault(c, []).append(v)
    groups = {c: sorted(vs) for c, vs in sorted(groups.items())}
    least = np.flatnonzero(g.degree == g.degree.min())
    return {"graph": _graph_of(config),
            "traffic": {k: traffic[k] for k in
                        ("keys", "min_degree", "pool", "pool_seed", "alpha",
                         "beta", "margin")},
            "warmup_key": int(least[-1]),
            "sizes": sizes,
            "candidates": groups}


def pool_path(config_name: str, traffic_name: str) -> pathlib.Path:
    return KEYS_DIR / config_name / f"{traffic_name}.json"


def load_pool(config: dict, traffic: dict) -> dict:
    """The kept pool of ``config``'s graph, refused where it was made
    for another graph or other traffic parameters."""
    path = pool_path(config["name"], traffic["name"])
    if not path.is_file():
        raise FileNotFoundError(f"no key pool {path}; make it with "
                                f"bench/traffic.py")
    pool = json.loads(path.read_text())
    made_for = {k: traffic[k] for k in pool["traffic"]}
    if pool["graph"] != _graph_of(config) or pool["traffic"] != made_for:
        raise ValueError(f"{path} was made for another graph or traffic")
    return pool


def draw_keys(pool: dict, seed: int) -> np.ndarray:
    """The run's search keys, in the order the window sends them."""
    rng = np.random.default_rng(seed % 2**63)
    queues = {c: list(rng.permutation(pool["candidates"][c]))
              for c in sorted(pool["candidates"])}
    return np.array([queues[c].pop() for c in pool["sizes"]], np.int64)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="make a cell's key pool")
    ap.add_argument("--config", required=True, help="configuration file")
    ap.add_argument("--traffic", required=True)
    args = ap.parse_args(argv)
    config = json.loads(pathlib.Path(args.config).read_text())
    traffic = load(args.traffic)
    g = reference.generate(config["generator"], config["scale"])
    pool = make_pool(config, traffic, g)
    path = pool_path(config["name"], args.traffic)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(pool) + "\n")
    print(f"{path}: sizes {dict(Counter(pool['sizes']))}, candidates "
          f"{ {c: len(vs) for c, vs in pool['candidates'].items()} }",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
