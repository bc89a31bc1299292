"""Subprocess entry for multi-device distributed-BFS tests.

Run as:  python tests/_dist_bfs_main.py <n_devices> <mode>
(sets XLA_FLAGS *before* importing jax, so pytest's process keeps 1 dev).
"""
import os
import sys

n_dev = int(sys.argv[1])
mode = sys.argv[2]
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.configs.base import BFSConfig  # noqa: E402
from repro.core.bfs import run_bfs  # noqa: E402
from repro.core.ref import depths_from_parents, validate_parents  # noqa: E402
from repro.graph.formats import build_blocked, build_blocked_1d  # noqa: E402
from repro.graph.rmat import rmat_graph  # noqa: E402
from repro.launch.mesh import make_local_mesh, make_local_mesh_1d  # noqa: E402


def check(edges, pr, pc, cfg, local_mode="dense", roots=(5,)):
    g = build_blocked(edges, pr, pc, align=32, cap_pad=32)
    mesh = make_local_mesh(pr, pc)
    deg = edges.out_degrees()
    for root in roots:
        root = int(root) if deg[int(root)] > 0 else int(np.flatnonzero(deg)[0])
        res = run_bfs(g, root, cfg, mesh, local_mode=local_mode)
        ok, msg = validate_parents(edges.n, edges.src, edges.dst, root,
                                   res.parents)
        assert ok, (pr, pc, cfg.fold_mode, cfg.direction_optimizing,
                    local_mode, msg)
    return res


def main():
    if mode == "grids":
        edges = rmat_graph(9, edge_factor=8, seed=3)
        for pr, pc in [(2, 2), (1, 4), (4, 1), (2, 1), (4, 4), (2, 8),
                       (8, 2), (1, 16), (16, 1)]:
            if pr * pc > n_dev:
                continue
            for fold in ("alltoall", "reduce"):
                for diro in (False, True):
                    check(edges, pr, pc,
                          BFSConfig(fold_mode=fold, direction_optimizing=diro))
        print("OK grids")
    elif mode == "kernel":
        edges = rmat_graph(9, edge_factor=8, seed=5)
        for storage in ("csr", "dcsc"):
            check(edges, 2, 2, BFSConfig(storage=storage),
                  local_mode="kernel")
        print("OK kernel")
    elif mode == "counters":
        edges = rmat_graph(12, edge_factor=16, seed=1)
        pr = pc = 4
        r_td = check(edges, pr, pc, BFSConfig(direction_optimizing=False),
                     roots=(1,))
        r_do = check(edges, pr, pc, BFSConfig(direction_optimizing=True),
                     roots=(1,))
        u = lambda r: sum(v for k, v in r.counters.items()
                          if k.startswith("use_"))
        # the paper's claim: direction-optimizing sends ~an order of
        # magnitude less useful data and examines far fewer edges
        assert u(r_do) < 0.5 * u(r_td), (u(r_do), u(r_td))
        assert (r_do.counters["edges_useful"]
                < 0.3 * r_td.counters["edges_useful"]), (
            r_do.counters["edges_useful"], r_td.counters["edges_useful"])
        # bottom-up was actually used in the middle levels
        modes = r_do.level_stats[: r_do.n_levels, 2]
        assert modes.max() == 1.0 and modes[0] == 0.0
        print("OK counters")
    elif mode == "optimized":
        # beyond-paper variants must stay oracle-valid.  NOTE: only the
        # runtime configs (capacity fallbacks compiled in) are validated;
        # the *_pure variants are roofline-lowering artifacts that drop
        # over-capacity winners by design (EXPERIMENTS.md §Perf).
        import dataclasses as dc
        from repro.configs.base import get_config
        edges = rmat_graph(11, edge_factor=16, seed=2)
        i2_rt = dc.replace(get_config("bfs-rmat-i2"), fold_mode="bitmap")
        for cfg in (get_config("bfs-rmat-opt-rt"), i2_rt):
            check(edges, 4, 4, cfg, roots=(3, 500))
            check(edges, 2, 8, cfg, roots=(3,))
        print("OK optimized")
    elif mode == "oned":
        # the tentpole acceptance case: on >=3 R-MAT scales under a
        # 16-strip mesh, the 1D decomposition must (a) produce valid
        # trees, (b) match the 2D depths exactly, and (c) report
        # wire_expand equal to the comm_model closed form (and no
        # fold/transpose wire at all — those phases don't exist in 1D).
        from repro.core import comm_model
        p = n_dev
        for scale, diro in ((9, True), (10, False), (11, True)):
            edges = rmat_graph(scale, edge_factor=8, seed=scale)
            deg = edges.out_degrees()
            root = int(np.flatnonzero(deg)[0])
            g1 = build_blocked_1d(edges, p, align=32, cap_pad=32)
            r1 = run_bfs(g1, root,
                         BFSConfig(decomposition="1d",
                                   direction_optimizing=diro),
                         make_local_mesh_1d(p))
            ok, msg = validate_parents(edges.n, edges.src, edges.dst,
                                       root, r1.parents)
            assert ok, (scale, msg)
            g2 = build_blocked(edges, 4, 4, align=32, cap_pad=32)
            r2 = run_bfs(g2, root,
                         BFSConfig(direction_optimizing=diro),
                         make_local_mesh(4, 4))
            d1 = depths_from_parents(edges.n, r1.parents, root)
            d2 = depths_from_parents(edges.n, r2.parents, root)
            assert np.array_equal(d1, d2), (scale, int((d1 != d2).sum()))
            want = comm_model.expand_1d_words(g1.part.n, p, r1.n_levels)
            got = r1.counters["wire_expand"]
            assert got > 0 and abs(got - want) <= 1e-5 * want, (got, want)
            for k in ("wire_transpose", "wire_fold", "wire_rotate",
                      "wire_updates"):
                assert r1.counters[k] == 0.0, (k, r1.counters[k])
        # LocalOps acceptance: the 1D strip kernels (CSR gather and the
        # strip-DCSC Pallas SpMSV) must match the serial oracle and the
        # 2D depths on the same graph
        edges = rmat_graph(9, edge_factor=8, seed=9)
        root = int(np.flatnonzero(edges.out_degrees())[0])
        g1 = build_blocked_1d(edges, p, align=32, cap_pad=32,
                              with_col_ptr=True)
        g2 = build_blocked(edges, 4, 4, align=32, cap_pad=32)
        r2 = run_bfs(g2, root, BFSConfig(), make_local_mesh(4, 4))
        d2 = depths_from_parents(edges.n, r2.parents, root)
        for storage in ("dcsc", "csr"):
            r1 = run_bfs(g1, root,
                         BFSConfig(decomposition="1d", storage=storage),
                         make_local_mesh_1d(p), local_mode="kernel")
            ok, msg = validate_parents(edges.n, edges.src, edges.dst,
                                       root, r1.parents)
            assert ok, (storage, msg)
            d1 = depths_from_parents(edges.n, r1.parents, root)
            assert np.array_equal(d1, d2), (storage, int((d1 != d2).sum()))
        print("OK oned")
    elif mode == "onedsparse":
        # the "1ds" tentpole acceptance: on 16 strips the sparse
        # owner-directed exchange must (a) produce valid trees matching
        # the 1d/2d depths, (b) measure wire_expand within 2x of the
        # Buluc & Madduri closed form topdown_1d_words when the buckets
        # never overflow, (c) beat the dense bitmap on the first two
        # (small-frontier) levels, and (d) never ship MORE than the
        # bitmap when the planned hybrid capacity is in force.
        from repro.core import comm_model
        p = n_dev
        for scale, diro in ((9, True), (10, False)):
            edges = rmat_graph(scale, edge_factor=8, seed=scale)
            deg = edges.out_degrees()
            root = int(np.flatnonzero(deg)[0])
            g1 = build_blocked_1d(edges, p, align=32, cap_pad=32)
            cfg_s = BFSConfig(decomposition="1ds",
                              direction_optimizing=diro)
            rs = run_bfs(g1, root, cfg_s, make_local_mesh_1d(p))
            ok, msg = validate_parents(edges.n, edges.src, edges.dst,
                                       root, rs.parents)
            assert ok, (scale, msg)
            r1 = run_bfs(g1, root,
                         BFSConfig(decomposition="1d",
                                   direction_optimizing=diro),
                         make_local_mesh_1d(p))
            g2 = build_blocked(edges, 4, 4, align=32, cap_pad=32)
            r2 = run_bfs(g2, root,
                         BFSConfig(direction_optimizing=diro),
                         make_local_mesh(4, 4))
            ds = depths_from_parents(edges.n, rs.parents, root)
            assert np.array_equal(
                ds, depths_from_parents(edges.n, r1.parents, root)), scale
            assert np.array_equal(
                ds, depths_from_parents(edges.n, r2.parents, root)), scale
            for k in ("wire_transpose", "wire_fold", "wire_rotate",
                      "wire_updates"):
                assert rs.counters[k] == 0.0, (k, rs.counters[k])

        # codec + sieve acceptance: parents bit-identical with the
        # packed codec and the visited sieve on vs off, across local
        # modes (dense / Pallas kernel), storages (csr / dcsc) and both
        # instrument modes, on 16 strips
        from repro.core.engine import plan_bfs
        edges = rmat_graph(10, edge_factor=8, seed=10)
        root = int(np.flatnonzero(edges.out_degrees())[0])
        gk = build_blocked_1d(edges, p, align=32, cap_pad=32,
                              with_col_ptr=True)
        base = None
        for codec in ("none", "packed"):
            for lm in ("dense", "kernel"):
                for storage in ("csr", "dcsc"):
                    for instr in (True, False):
                        r = plan_bfs(
                            gk, BFSConfig(decomposition="1ds",
                                          storage=storage,
                                          frontier_codec=codec,
                                          instrument=instr),
                            make_local_mesh_1d(p),
                            local_mode=lm).compile().run(root)
                        if base is None:
                            base = r.parents
                            ok, msg = validate_parents(
                                edges.n, edges.src, edges.dst, root,
                                r.parents)
                            assert ok, msg
                        assert np.array_equal(r.parents, base), (
                            codec, lm, storage, instr)
                        if not instr:
                            assert r.counters == {}, (codec, lm, storage)

        # (b)+(c): scale-14, pure top-down, overflow disabled
        # (cap_x = chunk), a typical low-degree root.  The raw-id runs
        # pin the UNCOMPRESSED closed forms, so codec="none" here; the
        # packed counterpart follows below.
        edges = rmat_graph(14, edge_factor=4, seed=14)
        deg = edges.out_degrees()
        root = int(np.flatnonzero((deg > 0) & (deg <= 32))[0])
        g1 = build_blocked_1d(edges, p, align=32, cap_pad=32)
        cfg = BFSConfig(decomposition="1ds", direction_optimizing=False,
                        frontier_codec="none")
        r = run_bfs(g1, root, cfg, make_local_mesh_1d(p),
                    cap_x=g1.part.chunk)
        ok, msg = validate_parents(edges.n, edges.src, edges.dst, root,
                                   r.parents)
        assert ok, msg
        got = r.counters["wire_expand"]
        want = comm_model.topdown_1d_words(edges.m, p)
        assert 0.5 * want <= got <= 2.0 * want, (got, want)
        # per-level measured words (stats col 4): every non-overflow
        # level matches the sparse closed form on that level's frontier
        sizes = r.level_stats[: r.n_levels, 0]
        wires = r.level_stats[: r.n_levels, 4]
        model = np.array([comm_model.sparse_expand_1d_words(s, p)
                          for s in sizes])
        assert np.allclose(wires, model, rtol=1e-5), (wires, model)
        # the first two levels beat the dense bitmap by a wide margin
        dense_lvl = comm_model.expand_1d_level_words(g1.part.n, p)
        assert wires[0] < dense_lvl and wires[1] < dense_lvl, (
            wires[:2], dense_lvl)
        # ... while the dense "1d" run pays dense_lvl on EVERY level
        r1 = run_bfs(g1, root, BFSConfig(decomposition="1d",
                                         direction_optimizing=False),
                     make_local_mesh_1d(p))
        assert np.allclose(r1.level_stats[: r1.n_levels, 4], dense_lvl)
        assert np.array_equal(r1.parents, r.parents)

        # (d): the planned hybrid cap never ships an overflowing sparse
        # level — every level's words are either the sparse form (fits)
        # or exactly the dense bitmap (fallback), totalling no more than
        # a small factor of the pure-dense volume
        rh = run_bfs(g1, root, cfg, make_local_mesh_1d(p))
        assert np.array_equal(rh.parents, r.parents)
        wires_h = rh.level_stats[: rh.n_levels, 4]
        sizes_h = rh.level_stats[: rh.n_levels, 0]
        for s, w in zip(sizes_h, wires_h):
            sparse_w = comm_model.sparse_expand_1d_words(s, p)
            assert (abs(w - sparse_w) <= 1e-5 * max(sparse_w, 1)
                    or abs(w - dense_lvl) <= 1e-5 * dense_lvl), (s, w)
        assert wires_h.sum() <= r1.counters["wire_expand"] + 1e-3, (
            wires_h.sum(), r1.counters["wire_expand"])

        # packed-codec acceptance on the same pinned scale-14/p=16
        # config: parents unchanged, every level's measured words match
        # the compressed closed form (fit) or the dense bitmap
        # (fallback), and the TOTAL wire_expand is strictly below the
        # raw-id hybrid baseline above (the PR 5 figure)
        bits = comm_model.codec_bits(g1.part.chunk)
        cfg_p = BFSConfig(decomposition="1ds",
                          direction_optimizing=False)  # packed default
        rp = run_bfs(g1, root, cfg_p, make_local_mesh_1d(p))
        assert np.array_equal(rp.parents, r.parents)
        wires_p = rp.level_stats[: rp.n_levels, 4]
        sizes_p = rp.level_stats[: rp.n_levels, 0]
        n_sparse_p = 0
        for s, w in zip(sizes_p, wires_p):
            packed_w = comm_model.compressed_expand_1d_words(s, p, bits)
            if abs(w - packed_w) <= 1e-5 * max(packed_w, 1):
                n_sparse_p += 1
            else:
                assert abs(w - dense_lvl) <= 1e-5 * dense_lvl, (s, w)
        # the bits-aware plan admits more sparse levels than the raw one
        n_sparse_raw = sum(
            1 for s, w in zip(sizes_h, wires_h)
            if abs(w - comm_model.sparse_expand_1d_words(s, p))
            <= 1e-5 * max(comm_model.sparse_expand_1d_words(s, p), 1))
        assert n_sparse_p >= n_sparse_raw, (n_sparse_p, n_sparse_raw)
        # and the headline: packed total strictly below the raw total
        assert wires_p.sum() < wires_h.sum(), (
            wires_p.sum(), wires_h.sum())
        print("codec totals: packed", float(wires_p.sum()),
              "raw", float(wires_h.sum()),
              "dense", float(dense_lvl * r1.n_levels))
        print("OK onedsparse")
    elif mode == "podheur":
        # per-slice direction heuristic regression: two pod-batched
        # roots of different eccentricity must switch modes on their
        # OWN frontier sizes — the batched program's per-root
        # level_stats (n_f, m_f, mode) must be bit-identical to each
        # root's single-root run.  (The old loop state fed the
        # cross-pod pmax'd n_f back into the go_td heuristic, so the
        # pod with the smaller frontier switched on its lockstep
        # partner's numbers — and its stats recorded them.)  Runs in
        # the sparse-exchange "1ds" entry, doubling as the multi-device
        # run_batch coverage for the third registry entry.
        import jax
        from repro.core.engine import plan_bfs
        assert n_dev >= 8
        edges = rmat_graph(9, edge_factor=8, seed=9)
        deg = edges.out_degrees()
        roots = np.flatnonzero(deg > 0)[:8].astype(np.int32)
        g1 = build_blocked_1d(edges, 4, align=32, cap_pad=32)
        devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
        mesh = jax.sharding.Mesh(devs, ("pod", "data"))
        eng = plan_bfs(g1, BFSConfig(decomposition="1ds"), mesh).compile()
        singles = [eng.run(int(r)) for r in roots]
        diff = [(i, j) for i in range(len(roots))
                for j in range(i + 1, len(roots))
                if not np.array_equal(singles[i].level_stats,
                                      singles[j].level_stats)]
        assert diff, "need two roots with different frontier trajectories"
        # prefer different eccentricity: the searches must also switch
        # back to top-down / terminate at different levels
        a, b = max(diff, key=lambda ij: abs(singles[ij[0]].n_levels
                                            - singles[ij[1]].n_levels))
        pair = np.array([roots[a], roots[b]], dtype=np.int32)
        bp = eng.run_batch(pair)         # one root per pod, in lockstep
        for i, j in enumerate((a, b)):
            s = singles[j]
            ok, msg = validate_parents(edges.n, edges.src, edges.dst,
                                       int(pair[i]), bp.parents[i])
            assert ok, (i, msg)
            # lockstep trip count = the slower search's level count
            assert bp.n_levels[i] == max(singles[a].n_levels,
                                         singles[b].n_levels)
            got = bp.level_stats[i][: s.n_levels, :3]
            want = s.level_stats[: s.n_levels, :3]
            assert np.array_equal(got, want), (
                int(pair[i]), got[:, (0, 2)], want[:, (0, 2)])
            # levels past this root's own search stay empty
            assert (bp.level_stats[i][s.n_levels:, 0] == 0).all()
        print("OK podheur")
    elif mode == "fastpath":
        # instrument=False acceptance on 16 devices, all three
        # decompositions: the latency-lean program (one fused scalar
        # reduction per level, batched bottom-up update exchange,
        # counters compiled out) must return bit-identical parents to
        # the instrumented program and oracle-valid trees, including
        # under direction switching and the compact-update exchange.
        from repro.core.engine import plan_bfs
        edges = rmat_graph(10, edge_factor=8, seed=9)
        deg = edges.out_degrees()
        roots = np.flatnonzero(deg > 0)[:3]
        g1 = build_blocked_1d(edges, n_dev, align=32, cap_pad=32)
        g2 = build_blocked(edges, 4, 4, align=32, cap_pad=32)
        cases = [("1d", g1, make_local_mesh_1d(n_dev), {}),
                 ("1ds", g1, make_local_mesh_1d(n_dev), {}),
                 ("2d", g2, make_local_mesh(4, 4), {}),
                 ("2d", g2, make_local_mesh(4, 4),
                  {"fold_mode": "alltoall", "compact_updates": True})]
        for decomp, g, mesh, kw in cases:
            ref = plan_bfs(g, BFSConfig(decomposition=decomp, **kw),
                           mesh).compile()
            fast = plan_bfs(g, BFSConfig(decomposition=decomp,
                                         instrument=False, **kw),
                            mesh).compile()
            # the fast program really is leaner: at most 2 all-reduces
            # survive in the compiled search (the fused init + loop
            # reductions; compact updates add their overflow pmax) vs
            # the instrumented counter schedule
            cf = fast.collective_counts()
            ci = ref.collective_counts()
            ar_budget = 3 if kw.get("compact_updates") else 2
            assert cf.get("all-reduce", 0) <= ar_budget, (decomp, kw, cf)
            assert cf["total"] < ci["total"], (decomp, kw, cf, ci)
            for root in roots:
                ri = ref.run(int(root))
                rf = fast.run(int(root))
                ok, msg = validate_parents(edges.n, edges.src, edges.dst,
                                           int(root), rf.parents)
                assert ok, (decomp, kw, int(root), msg)
                assert np.array_equal(rf.parents, ri.parents), (
                    decomp, kw, int(root))
                assert rf.n_levels == ri.n_levels, (decomp, kw, int(root))
                # fast runs carry NO counters — zeros here would read
                # as measured wire volumes in mode-mixing aggregates
                assert rf.counters == {}

        # pod-batched fast path: the fused lockstep pmax (and, for 2d,
        # the sync_modes decision riding it as go_bu / 1-go_td) only
        # executes under a pod axis — cross-check run_batch parents
        # against the single-root fast program in both families
        import jax
        pair = roots[:2].astype(np.int32)
        g2s = build_blocked(edges, 2, 2, align=32, cap_pad=32)
        pods2d = jax.sharding.Mesh(
            np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
            ("pod", "data", "model"))
        pods1d = jax.sharding.Mesh(
            np.asarray(jax.devices()[:8]).reshape(2, 4), ("pod", "data"))
        g1s = build_blocked_1d(edges, 4, align=32, cap_pad=32)
        for decomp, g, mesh in (("1ds", g1s, pods1d), ("2d", g2s, pods2d)):
            eng = plan_bfs(g, BFSConfig(decomposition=decomp,
                                        instrument=False), mesh).compile()
            bp = eng.run_batch(pair)
            for i, root in enumerate(pair):
                single = eng.run(int(root))
                ok, msg = validate_parents(edges.n, edges.src, edges.dst,
                                           int(root), bp.parents[i])
                assert ok, ("batch", decomp, int(root), msg)
                assert np.array_equal(bp.parents[i], single.parents), (
                    "batch", decomp, int(root))
        print("OK fastpath")
    elif mode == "pipelined":
        # software-pipelined expand acceptance on 16 devices: for every
        # decomposition x expand_chunks in {2, 4}, the chunked program
        # must return BIT-IDENTICAL parents to expand_chunks=1 (the
        # chunked gather reorders the exchange, never the
        # (select-source, min) semiring result), keep the identical
        # per-level direction-mode sequence when instrumented, and the
        # instrument=False fast path must agree too.  Scale 11 over 16
        # strips packs each strip to 4 words, so 4 is the deepest
        # chunking this mesh admits.
        from repro.core.engine import plan_bfs
        edges = rmat_graph(11, edge_factor=8, seed=11)
        deg = edges.out_degrees()
        roots = [int(r) for r in np.flatnonzero(deg > 0)[:2]]
        g1 = build_blocked_1d(edges, n_dev, align=32, cap_pad=32)
        g2 = build_blocked(edges, 4, 4, align=32, cap_pad=32)
        cases = [("1d", g1, make_local_mesh_1d(n_dev), {}),
                 ("1ds", g1, make_local_mesh_1d(n_dev), {}),
                 ("1ds", g1, make_local_mesh_1d(n_dev),
                  {"frontier_codec": "none"}),
                 ("2d", g2, make_local_mesh(4, 4), {})]
        for decomp, g, mesh, kw in cases:
            ref = plan_bfs(g, BFSConfig(decomposition=decomp, **kw),
                           mesh).compile()
            refs = [ref.run(r) for r in roots]
            ok, msg = validate_parents(edges.n, edges.src, edges.dst,
                                       roots[0], refs[0].parents)
            assert ok, (decomp, kw, msg)
            for ec in (2, 4):
                eng = plan_bfs(g, BFSConfig(decomposition=decomp,
                                            expand_chunks=ec, **kw),
                               mesh).compile()
                fast = plan_bfs(g, BFSConfig(decomposition=decomp,
                                             expand_chunks=ec,
                                             instrument=False, **kw),
                                mesh).compile()
                for i, root in enumerate(roots):
                    r = eng.run(root)
                    rf = fast.run(root)
                    key = (decomp, kw, ec, root)
                    assert np.array_equal(r.parents, refs[i].parents), key
                    assert r.n_levels == refs[i].n_levels, key
                    # the chunked exchange must not perturb a single
                    # direction decision: stats cols (n_f, m_f, mode,
                    # used) identical; wire (col 4) may differ only for
                    # "1ds" (per-sub-range overflow -> dense fallback)
                    assert np.array_equal(
                        r.level_stats[:, :4],
                        refs[i].level_stats[:, :4]), key
                    if decomp != "1ds":
                        assert np.array_equal(
                            r.level_stats, refs[i].level_stats), key
                    assert np.array_equal(rf.parents, refs[i].parents), key
                    assert rf.n_levels == refs[i].n_levels, key
                    assert rf.counters == {}, key
        print("OK pipelined")
    elif mode == "born":
        # born-sharded graphs on 16 devices: the device-side distributed
        # build must be BIT-IDENTICAL to the host builders on the same
        # counter stream in every decomposition (arrays, capacities,
        # degree distribution, m/m_input), traverse to the same parents,
        # and a scale-18 graph must build end-to-end on device (no
        # host-side edge materialization), round-trip the graph store,
        # and traverse.
        import tempfile
        from dataclasses import replace
        from repro.ckpt.graph_store import GraphStore, plan_bfs_from_store
        from repro.core.engine import plan_bfs
        from repro.graph.dist_build import (BuildSpec, build_relabel,
                                            dist_build)
        from repro.graph.formats import input_degrees
        from repro.graph.rmat import relabel_edges

        spec = BuildSpec(scale=10, edge_factor=16, seed=3)
        edges = rmat_graph(spec.scale, edge_factor=spec.edge_factor,
                           seed=spec.seed, generator="counter")
        # the host twins relabel the stream as the 16-shard build does
        relabel = build_relabel(spec, n_dev)
        relabeled = relabel_edges(edges, relabel)
        gh1 = replace(build_blocked_1d(relabeled, n_dev, align=32,
                                       cap_pad=32), relabel=relabel)
        gh2 = replace(build_blocked(relabeled, 4, 4, align=32, cap_pad=32),
                      relabel=relabel)
        mesh1 = make_local_mesh_1d(n_dev)
        mesh2 = make_local_mesh(4, 4)
        gd1, _ = dist_build(spec, "1d", mesh1, n_dev, align=32, cap_pad=32)
        gd2, _ = dist_build(spec, "2d", mesh2, (4, 4), align=32,
                            cap_pad=32)
        for gd, gh in ((gd1, gh1), (gd2, gh2)):
            assert gd.m == gh.m and gd.m_input == gh.m_input
            assert (gd.cap, gd.maxdeg_col) == (gh.cap, gh.maxdeg_col)
            ha = gh.device_arrays()
            for k, v in gd.device_arrays().items():
                assert np.array_equal(np.asarray(v), np.asarray(ha[k])), k
        assert np.array_equal(                 # degree histogram over V
            np.bincount(np.asarray(gd1.deg_A).ravel()),
            np.bincount(np.asarray(gh1.deg_A).ravel()))
        for decomp, gd, gh, mesh in (("1d", gd1, gh1, mesh1),
                                     ("1ds", gd1, gh1, mesh1),
                                     ("2d", gd2, gh2, mesh2)):
            cfg = BFSConfig(decomposition=decomp)
            rd = plan_bfs(gd, cfg, mesh).compile().run(5)
            rh = plan_bfs(gh, cfg, mesh).compile().run(5)
            assert np.array_equal(rd.parents, rh.parents), decomp
            ok, msg = validate_parents(edges.n, edges.src, edges.dst, 5,
                                       rd.parents)
            assert ok, (decomp, msg)

        spec18 = BuildSpec(scale=18, edge_factor=16, seed=1)
        g18, info = dist_build(spec18, "1d", mesh1, n_dev)
        assert info["m"] > spec18.m_input      # symmetrized unique edges
        store = GraphStore(tempfile.mkdtemp())
        store.save_graph("s18", g18, spec=spec18)
        plan = plan_bfs_from_store(
            store, "s18", BFSConfig(decomposition="1d", instrument=False),
            mesh1, expect_spec=spec18)
        res = plan.compile(store=store).run(
            int(np.argmax(input_degrees(g18))))
        assert int((res.parents >= 0).sum()) > spec18.n // 4
        print("OK born")
    elif mode == "multiroot":
        edges = rmat_graph(10, edge_factor=8, seed=9)
        rng = np.random.default_rng(0)
        deg = edges.out_degrees()
        roots = rng.choice(np.flatnonzero(deg > 0), size=8, replace=False)
        check(edges, 2, 2, BFSConfig(), roots=roots)
        print("OK multiroot")
    elif mode == "multipod":
        # pod-axis batched multi-source BFS through the engine, in BOTH
        # decompositions (a named ROADMAP scenario): graph replicated
        # per pod, roots sharded, level loops in lockstep.  Legacy
        # make_multiroot_bfs_fn path also exercised for compat.
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.bfs import make_multiroot_bfs_fn
        from repro.core.engine import plan_bfs
        edges = rmat_graph(10, edge_factor=8, seed=9)
        deg = edges.out_degrees()
        roots = np.flatnonzero(deg > 0)[:8].astype(np.int32)

        # 2D checkerboard under 2 pods x (2 x 2): 8 devices
        pods, pr, pc = 2, 2, 2
        g = build_blocked(edges, pr, pc, align=32, cap_pad=32)
        devs = np.asarray(jax.devices()[: pods * pr * pc]).reshape(
            pods, pr, pc)
        mesh3 = jax.sharding.Mesh(devs, ("pod", "data", "model"))
        eng2 = plan_bfs(g, BFSConfig(), mesh3).compile()
        b2 = eng2.run_batch(roots)       # 4 searches per pod
        for i, root in enumerate(roots):
            ok, msg = validate_parents(edges.n, edges.src, edges.dst,
                                       int(root), b2.parents[i])
            assert ok, ("2d", i, msg)

        # 1D row strips under 2 pods x 8 strips: all 16 devices; depths
        # must match the 2D batch root-for-root
        g1 = build_blocked_1d(edges, 8, align=32, cap_pad=32)
        devs1 = np.asarray(jax.devices()[:16]).reshape(2, 8)
        mesh1 = jax.sharding.Mesh(devs1, ("pod", "data"))
        eng1 = plan_bfs(g1, BFSConfig(decomposition="1d"), mesh1).compile()
        b1 = eng1.run_batch(roots)
        for i, root in enumerate(roots):
            ok, msg = validate_parents(edges.n, edges.src, edges.dst,
                                       int(root), b1.parents[i])
            assert ok, ("1d", i, msg)
            d1 = depths_from_parents(edges.n, b1.parents[i], int(root))
            d2 = depths_from_parents(edges.n, b2.parents[i], int(root))
            assert np.array_equal(d1, d2), (i, int((d1 != d2).sum()))

        # legacy builder still works over the registry path
        fn, keys = make_multiroot_bfs_fn(mesh3, g.part, BFSConfig(),
                                         g.cap_seg, n_roots=pods,
                                         maxdeg=g.maxdeg_col)
        arrs = g.device_arrays()
        sh = NamedSharding(mesh3, P("data", "model"))
        gdev = {k: jax.device_put(np.asarray(arrs[k]), sh) for k in keys}
        pis, levels, _ = fn(gdev, jax.device_put(
            roots[:pods], NamedSharding(mesh3, P("pod"))))
        pis = np.asarray(pis)            # (pr, pc, n_roots, chunk)
        for r in range(pods):
            pi = pis[:, :, r, :].reshape(g.part.n)[: g.part.n_orig]
            ok, msg = validate_parents(edges.n, edges.src, edges.dst,
                                       int(roots[r]), pi)
            assert ok, (r, msg)
        print("OK multipod")
    else:
        raise SystemExit(f"unknown mode {mode}")


if __name__ == "__main__":
    main()
