"""End-to-end Graph500-style driver (the paper's §7 methodology):
generate R-MAT, build the distributed graph + compile the search ONCE
(plan → compile → run, repro.core.engine), run BFS from 16 random
roots, report the harmonic-mean TEPS over pure per-root traversal time
(compile/ship reported separately), validate every tree, compare comm
volume to the §6 model.

    PYTHONPATH=src python examples/graph500_bfs.py --scale 13 --grid 1x1

On a TPU host the grid runs on the chips (``--grid 2x2`` on a four-chip
v5e host).  On the CPU, multi-device grids need forced host devices, and
the Pallas kernels run in the interpreter:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=16 \
        PYTHONPATH=src python examples/graph500_bfs.py --grid 4x4

``--decomposition 1d`` runs the paper's 1D row-strip baseline on
p = pr*pc strips of the same graph (the Eq. 2 comparison axis);
``--decomposition 1ds`` runs the sparse-exchange variant (capped
frontier-id buckets broadcast per level, dense-bitmap fallback on
overflow — Buluc & Madduri's formulation):
    ... examples/graph500_bfs.py --grid 4x4 --decomposition 1ds

``--local-mode kernel --storage dcsc`` selects the Pallas local-
discovery path with compressed pointers in either decomposition (1D =
the strip-DCSC kernel; the §5.1 CSR/DCSC axis of Fig. 6):
    ... --decomposition 1d --local-mode kernel --storage dcsc

``--born`` generates + formats the graph ON DEVICE (graph/dist_build:
per-shard counter R-MAT stream, owner-routed all_to_all, shard-local
dedup) — the host never materializes the edge list, so scales beyond
host memory fit; tree validation needs the host edge list and is
skipped.  ``--store DIR`` persists graph + compiled executable to a
GraphStore (and reloads both on the next identical run — disk to first
traversal in seconds):
    ... --grid 16x1 --decomposition 1d --born --store /tmp/gstore --fast
"""
import argparse
import time

import numpy as np

from repro.configs.base import BFSConfig
from repro.core import comm_model
from repro.core.engine import plan_bfs
from repro.core.metrics import harmonic_mean, teps
from repro.core.ref import validate_parents
from repro.graph.formats import build_blocked, build_blocked_1d
from repro.graph.rmat import random_source, rmat_graph
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh, make_local_mesh_1d


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=13)
    ap.add_argument("--grid", default="1x1")
    ap.add_argument("--roots", type=int, default=16)
    ap.add_argument("--no-diropt", action="store_true")
    ap.add_argument("--decomposition", choices=("1d", "1ds", "2d"),
                    default="2d")
    ap.add_argument("--local-mode", choices=("dense", "kernel"),
                    default="dense")
    ap.add_argument("--storage", choices=("csr", "dcsc"), default="csr")
    ap.add_argument("--fast", action="store_true",
                    help="instrument=False: compile out counters/stats "
                         "for the latency-lean level pipeline (TEPS "
                         "runs; the comm-volume report is skipped)")
    ap.add_argument("--born", action="store_true",
                    help="device-side distributed build (graph/"
                         "dist_build): no host edge list, validation "
                         "skipped")
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="GraphStore directory: persist graph + AOT "
                         "executable; identical reruns reload from disk")
    args = ap.parse_args()
    use_compile_cache()
    pr, pc = map(int, args.grid.split("x"))

    store = None
    if args.store:
        from repro.ckpt.graph_store import GraphStore
        store = GraphStore(args.store)

    edges = None
    if args.born:
        from repro.graph.dist_build import BuildSpec, dist_build
        spec = BuildSpec(scale=args.scale, edge_factor=16, seed=1)
        mesh = make_local_mesh_1d(pr * pc) \
            if args.decomposition in ("1d", "1ds") else make_local_mesh(pr, pc)
        name = f"s{args.scale}-{args.decomposition}"
        graph = None
        if store is not None:
            try:                       # identical rerun: reload from disk
                t0 = time.perf_counter()
                graph = store.load_graph(name, mesh=mesh, expect_spec=spec)
                print(f"store load: {time.perf_counter() - t0:.3f}s "
                      f"(graph shards from {args.store})")
            except FileNotFoundError:
                pass
        if graph is None:
            graph, info = dist_build(spec, args.decomposition, mesh,
                                     (pr, pc))
            print(f"born-sharded build: {info['build_s']:.3f}s "
                  f"({info['build_teps']:.3e} edges/s input rate; "
                  f"m={info['m']}, no host edge materialization)")
            if store is not None:
                t0 = time.perf_counter()
                store.save_graph(name, graph, spec=spec)
                print(f"store save: {time.perf_counter() - t0:.3f}s -> "
                      f"{args.store}")
    else:
        edges = rmat_graph(args.scale, 16, seed=1)
        if args.decomposition in ("1d", "1ds"):
            graph = build_blocked_1d(
                edges, pr * pc, align=32,
                with_col_ptr=(args.local_mode == "kernel"
                              and args.storage == "csr"))
            mesh = make_local_mesh_1d(pr * pc)
        else:
            graph = build_blocked(edges, pr, pc, align=32)
            mesh = make_local_mesh(pr, pc)
    cfg = BFSConfig(decomposition=args.decomposition, storage=args.storage,
                    direction_optimizing=not args.no_diropt,
                    instrument=not args.fast)
    rng = np.random.default_rng(0)

    # plan + compile once; every root below is pure traversal (the §7
    # methodology: harmonic-mean TEPS must not be smeared by compilation)
    engine = plan_bfs(graph, cfg, mesh,
                      local_mode=args.local_mode).compile(store=store)
    engine.search(0)[0].block_until_ready()    # untimed first-dispatch warmup
    src = "store (deserialized)" if engine.exec_from_store else "XLA"
    print(f"compile: {engine.compile_s:.3f}s ({src}; exec_load "
          f"{engine.exec_load_s:.3f}s), graph ship: "
          f"{engine.ship_s:.3f}s (paid once, reused for {args.roots} roots)")

    # born graphs have no host edge list: draw roots from the degree
    # vector instead of random_source(edges)
    deg_global = None
    if edges is None:
        from repro.graph.formats import input_degrees
        deg_global = np.flatnonzero(input_degrees(graph) > 0)
    rates, res = [], None
    for i in range(args.roots):
        root = int(rng.choice(deg_global)) if edges is None \
            else random_source(edges, rng)
        # time the device search only; host-side result conversion and
        # validation stay outside the timed region (worker.py methodology)
        t0 = time.perf_counter()
        out = engine.search(root)
        out[0].block_until_ready()
        dt = time.perf_counter() - t0
        res = engine.to_result(out)
        if edges is not None:
            ok, msg = validate_parents(edges.n, edges.src, edges.dst, root,
                                       res.parents)
            assert ok, msg
            valid = "valid"
        else:
            valid = "validation skipped (born-sharded: no host edges)"
        rates.append(teps(graph.m_input, dt))
        print(f"root {root:>8}: {res.n_levels} levels, {dt*1e3:8.2f} ms, "
              f"{rates[-1]:.3e} TEPS, {valid}")
    print(f"\nharmonic-mean TEPS over {args.roots} roots "
          f"(traversal only): {harmonic_mean(rates):.3e}")
    if args.fast:
        # counters are compiled out of the fast program — there is no
        # comm-volume accounting to report (run without --fast for it)
        return
    useful = sum(v for k, v in res.counters.items() if k.startswith('use_'))
    if args.decomposition in ("1d", "1ds"):
        wt = comm_model.topdown_1d_words(graph.m, pr * pc)
        we = comm_model.expand_1d_words(graph.part.n, pr * pc, res.n_levels)
        # "1d" must reproduce the dense closed form exactly; "1ds" ships
        # sparse ids, so the dense volume is its per-search upper bound
        rel = "vs model" if args.decomposition == "1d" \
            else "vs dense-bitmap bound"
        print(f"useful words (last search): {useful:.3e}  "
              f"({args.decomposition} top-down model w={wt:.3e}; "
              f"wire_expand measured {res.counters['wire_expand']:.3e} "
              f"{rel} {we:.3e})")
    else:
        wt = comm_model.topdown_words(graph.part.n, graph.m, pr, pc)
        print(f"useful words (last search): {useful:.3e}  "
              f"(pure top-down model w_t={wt:.3e})")


if __name__ == "__main__":
    main()
