"""Chip smoke test: the Graph500 traversal session on TPU.

Drives the main path through the entry points a user calls —
``dist_build`` (born-sharded Graph500 Kronecker graph, A=0.57,
B=C=0.19, edge factor 16), ``plan_bfs`` → ``BFSPlan.compile()`` →
``BFSEngine.run(root, validate=True)`` — and checks every answer:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four chips of one v5e host

One chip: dense local discovery in each decomposition (1d, 1ds, 2d) at
scale 18 (~262 k vertices, ~7.6 M directed edges after dedup), 8 roots
drawn with a fixed seed from non-isolated vertices, every tree checked
by the sharded Graph500 validator, and one root's per-vertex depths
compared with ``core/ref.py`` on a host edge list regenerated from the
same counter stream.  Then ``local_mode="kernel"`` (the Pallas kernels,
strip/block DCSC) in each decomposition on a host-built scale-14 graph,
whose parents must be bit-identical to the dense run on that graph.

Scale 22 fits one v5e chip (build peak ~8.5 GiB, search ~5.7 GiB) but
not the time: a dense search there takes about a minute, because every
level scans every edge, and a smoke run makes 25 of them per
decomposition.

Four chips: only the sharded path at scale 14 — 2d on a 2x2 mesh,
1d/1ds on p=4, with the build's owner-routing all_to_all across chips —
each run's depths and verdicts compared with a one-chip run of the same
graph in this process.  The one-chip graph is built on the host,
bit-identical to ``dist_build`` of the same counter stream, which saves
compiling the one-chip build programs (about a minute each).

One JSON record per phase goes to stdout; the last line is
``{"ok": true, "device": {...}}``.  Any failed check raises.  Without a
TPU the script exits with code 2 before building anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SCALE = 18
FOUR_CHIP_SCALE = 14
KERNEL_SCALE = 14
N_ROOTS = 8
ROOT_SEED = 20
DECOMPOSITIONS = ("1d", "1ds", "2d")
GRIDS = {1: (1, 1), 4: (2, 2)}


def _emit(record):
    print(json.dumps(record), flush=True)


def _check(cond, msg):
    if not cond:
        raise RuntimeError(msg)


def _mesh(decomposition, chips):
    from repro.launch.mesh import make_local_mesh, make_local_mesh_1d
    if decomposition == "2d":
        return make_local_mesh(*GRIDS[chips])
    return make_local_mesh_1d(chips)


def _build(spec, decomposition, chips):
    from repro.graph.dist_build import dist_build
    mesh = _mesh(decomposition, chips)
    graph, info = dist_build(spec, decomposition, mesh, GRIDS[chips])
    return graph, info, mesh


def _pick_roots(graph):
    from repro.graph.formats import input_degrees
    deg = input_degrees(graph)
    rng = np.random.default_rng(ROOT_SEED)
    return [int(r) for r in rng.choice(np.flatnonzero(deg > 0), N_ROOTS,
                                       replace=False)]


def _run(graph, info, mesh, decomposition, roots, local_mode="dense"):
    """Plan, compile, and search from every root: per-root traversal
    time (search only), then ``run(validate=True)`` for the verdict and
    the host parents."""
    from repro.configs.base import BFSConfig
    from repro.core.engine import plan_bfs
    from repro.core.metrics import harmonic_mean, teps

    cfg = BFSConfig(decomposition=decomposition, storage="dcsc",
                    instrument=False)
    engine = plan_bfs(graph, cfg, mesh, local_mode=local_mode).compile()
    engine.search(roots[0])[0].block_until_ready()     # first dispatch
    times, valid, parents = [], [], []
    t_val = 0.0
    for r in roots:
        t0 = time.perf_counter()
        engine.search(r)[0].block_until_ready()
        times.append(time.perf_counter() - t0)
        t1 = time.perf_counter()
        res = engine.run(r, validate=True)   # raises on an invalid tree
        t_val += time.perf_counter() - t1
        valid.append(bool(res.validation.ok))
        parents.append(res.parents)
    dev = mesh.devices.flat[0]
    record = {
        "decomposition": decomposition, "local_mode": local_mode,
        "scale": int(np.log2(graph.part.n_orig)),
        "chips": int(mesh.devices.size), "device_kind": dev.device_kind,
        "m_input": int(graph.m_input), "m": int(graph.m),
        "build_s": info["build_s"], "ship_s": engine.ship_s,
        "compile_s": engine.compile_s, "roots": roots, "root_s": times,
        "hmean_teps": harmonic_mean([teps(graph.m_input, t)
                                     for t in times]),
        "valid": valid, "validate_s": t_val,
        "devices": [str(d) for d in mesh.devices.flat],
    }
    return record, parents


def _depths(parents, root):
    from repro.core.ref import depths_from_parents
    return depths_from_parents(parents.shape[0], parents, root)


def _host_reference_depths(spec, root):
    """Per-vertex depths from ``core/ref.py`` on the host edge list of
    the same counter stream the device build consumed."""
    from repro.core.ref import bfs_depths
    from repro.graph.rmat import rmat_graph
    t0 = time.perf_counter()
    e = rmat_graph(spec.scale, spec.edge_factor, seed=spec.seed, a=spec.a,
                   b=spec.b, c=spec.c, generator="counter")
    return bfs_depths(e.n, e.src, e.dst, root), time.perf_counter() - t0


def _host_graph(edges, decomposition):
    """Host build of a one-chip graph, timed like ``dist_build``."""
    from repro.graph.formats import build_blocked, build_blocked_1d
    t0 = time.perf_counter()
    graph = build_blocked(edges, 1, 1) if decomposition == "2d" \
        else build_blocked_1d(edges, 1)
    return graph, {"build_s": time.perf_counter() - t0}


def one_chip():
    from repro.graph.dist_build import BuildSpec

    spec = BuildSpec(scale=SCALE, edge_factor=16, seed=1)
    roots, first = None, {}
    with ThreadPoolExecutor(max_workers=1) as pool:
        for decomposition in DECOMPOSITIONS:
            graph, info, mesh = _build(spec, decomposition, 1)
            if roots is None:
                roots = _pick_roots(graph)
                # the host reference overlaps the device work
                ref = pool.submit(_host_reference_depths, spec, roots[0])
            record, parents = _run(graph, info, mesh, decomposition, roots)
            del graph
            first[decomposition] = parents[0]
            _emit({"phase": "dense", **record})
            _check(all(record["valid"]), f"invalid tree: {record}")
        want, ref_s = ref.result()
    same = {d: bool(np.array_equal(_depths(p, roots[0]), want))
            for d, p in first.items()}
    _emit({"phase": "reference", "root": roots[0], "scale": SCALE,
           "ref_s": ref_s, "max_depth": int(want.max()),
           "reached": int((want >= 0).sum()), "depths_match": same})
    _check(all(same.values()), "depths differ from core/ref.py")

    # the kernel graph is built on the host (bit-identical to dist_build
    # on the same stream): a device build would spend minutes compiling
    # its sorts for a graph this small
    from repro.graph.rmat import rmat_graph
    edges = rmat_graph(KERNEL_SCALE, 16, seed=1, generator="counter")
    for decomposition in DECOMPOSITIONS:
        mesh = _mesh(decomposition, 1)
        graph, info = _host_graph(edges, decomposition)
        kroots = _pick_roots(graph)
        dense, p_dense = _run(graph, info, mesh, decomposition, kroots)
        kern, p_kern = _run(graph, info, mesh, decomposition, kroots,
                            local_mode="kernel")
        same = [bool(np.array_equal(a, b)) for a, b in zip(p_dense, p_kern)]
        kern["parents_match_dense"] = same
        kern["dense_root_s"] = dense["root_s"]
        _emit({"phase": "kernel", **kern})
        _check(all(dense["valid"]) and all(kern["valid"]),
               f"invalid tree: {kern}")
        _check(all(same), f"{decomposition}: kernel parents differ from "
                          f"dense")


def four_chips():
    from repro.graph.dist_build import BuildSpec
    from repro.graph.rmat import rmat_graph

    spec = BuildSpec(scale=FOUR_CHIP_SCALE, edge_factor=16, seed=1)
    edges = rmat_graph(spec.scale, spec.edge_factor, seed=spec.seed,
                       a=spec.a, b=spec.b, c=spec.c, generator="counter")
    graph, info = _host_graph(edges, "1d")
    del edges
    roots = _pick_roots(graph)
    base, p_base = _run(graph, info, _mesh("1d", 1), "1d", roots)
    del graph
    want = [_depths(p, r) for p, r in zip(p_base, roots)]
    _emit({"phase": "one_chip_reference", **base})
    _check(all(base["valid"]), f"invalid tree: {base}")
    for decomposition in DECOMPOSITIONS:
        graph, info, mesh = _build(spec, decomposition, 4)
        record, parents = _run(graph, info, mesh, decomposition, roots)
        del graph
        same = [bool(np.array_equal(_depths(p, r), w))
                for p, r, w in zip(parents, roots, want)]
        record["depths_match_one_chip"] = same
        record["valid_match_one_chip"] = record["valid"] == base["valid"]
        _emit({"phase": "sharded", **record})
        _check(all(record["valid"]), f"invalid tree: {record}")
        _check(all(same) and record["valid_match_one_chip"],
               f"{decomposition}: four-chip results differ from one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r} devices", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
