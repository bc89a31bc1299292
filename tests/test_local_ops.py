"""LocalOps registry: the (decomposition, local_mode, storage) parity
matrix, strip-DCSC builder invariants, and the §5.1 storage accounting
for the 1D strip formats."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import BFSConfig
from repro.core import comm_model, local_ops
from repro.core.bfs import run_bfs
from repro.core.ref import bfs_depths, depths_from_parents, validate_parents
from repro.core.steps import COUNTER_KEYS
from repro.graph.formats import build_blocked, build_blocked_1d
from repro.graph.rmat import preprocess, rmat_graph
from repro.launch.mesh import make_local_mesh, make_local_mesh_1d


def test_registry_covers_fig6_grid():
    combos = set(local_ops.registered_combos())
    for decomp in ("1d", "1ds", "2d"):
        for lm in ("dense", "kernel"):
            for st_ in ("csr", "dcsc"):
                assert (decomp, lm, st_) in combos
    with pytest.raises(ValueError, match="no LocalOps registered"):
        local_ops.get_local_ops("1d", "nope", "csr")
    # every entry ships the arrays the shared search loop reads
    for combo in combos:
        ops = local_ops.get_local_ops(*combo)
        assert "deg_A" in ops.keys and "nnz" in ops.keys, combo
    # "1ds" mirrors the "1d" entries exactly (same strips, same kernels)
    for lm in ("dense", "kernel"):
        for st_ in ("csr", "dcsc"):
            a = local_ops.get_local_ops("1d", lm, st_)
            b = local_ops.get_local_ops("1ds", lm, st_)
            assert a.keys == b.keys and a.topdown is b.topdown
            assert a.bottomup is b.bottomup


# ---------------------------------------------------------------------------
# Parity matrix: every registered combo on the same fixed R-MAT graph
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fixed_graph():
    e = rmat_graph(8, edge_factor=8, seed=4)
    # with_col_ptr: the matrix includes the 1d/kernel/csr cell
    return (e, build_blocked_1d(e, 1, align=32, cap_pad=32,
                                with_col_ptr=True),
            build_blocked(e, 1, 1, align=32, cap_pad=32))


def test_parity_matrix(fixed_graph):
    """On one device the candidate-min semantics are identical in every
    combo, so not just depths but the parent arrays must agree — and the
    local format must not change what goes on the wire: all COUNTER_KEYS
    totals except edges_examined (dense deliberately scans all nnz where
    the kernels scan only frontier segments) match within a
    decomposition; edges_examined itself matches across the two kernel
    storages."""
    e, g1, g2 = fixed_graph
    root = int(np.flatnonzero(e.out_degrees())[0])
    d_ref = bfs_depths(e.n, e.src, e.dst, root)
    res = {}
    for decomp, lm, st_ in local_ops.registered_combos():
        g = g2 if decomp == "2d" else g1       # 1d/1ds share the strips
        mesh = make_local_mesh(1, 1) if decomp == "2d" \
            else make_local_mesh_1d(1)
        cfg = BFSConfig(decomposition=decomp, storage=st_)
        r = run_bfs(g, root, cfg, mesh, local_mode=lm)
        ok, msg = validate_parents(e.n, e.src, e.dst, root, r.parents)
        assert ok, (decomp, lm, st_, msg)
        assert np.array_equal(
            depths_from_parents(e.n, r.parents, root), d_ref), (decomp, lm, st_)
        res[(decomp, lm, st_)] = r

    combos = list(res)
    base = res[combos[0]].parents
    for c in combos[1:]:
        assert np.array_equal(res[c].parents, base), c

    wire_keys = [k for k in COUNTER_KEYS if k != "edges_examined"]
    for decomp in ("1d", "1ds", "2d"):
        group = [c for c in combos if c[0] == decomp]
        r0 = res[group[0]]
        for c in group[1:]:
            for k in wire_keys:
                assert res[c].counters[k] == pytest.approx(
                    r0.counters[k], rel=1e-6), (c, k)
        kern = [c for c in group if c[1] == "kernel"]
        assert (res[kern[0]].counters["edges_examined"]
                == pytest.approx(res[kern[1]].counters["edges_examined"]))


# Every dense combo on 4 forced host devices: 2d on 2x2 and 1x4 meshes
# (pc > 1, so a bottom-up sub-step reads segments other than its own and
# rebases edge_dst by seg_id * chunk), 1d/1ds on 4 strips.  Each combo
# runs twice: as registered (rows read from edge_dst) and with its
# bottom-up closure forced onto the oracle's rp_seg search.
_DENSE_MESH_MAIN = """
import dataclasses, json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.configs.base import BFSConfig
from repro.core import local_ops
from repro.core.engine import plan_bfs
from repro.core.ref import bfs_depths, depths_from_parents, validate_parents
from repro.graph.formats import build_blocked, build_blocked_1d
from repro.graph.rmat import rmat_graph
from repro.launch.mesh import make_local_mesh, make_local_mesh_1d

def searching(rp_seg, ue_win, f_words, cvec, col_offset, n_edges, ve_win,
              args):
    return local_ops._bu_ref(rp_seg, ue_win, f_words, cvec, col_offset,
                             n_edges, None, args)

def run(graph, cfg, mesh, roots):
    eng = plan_bfs(graph, cfg, mesh).compile()
    return [eng.run(int(r)) for r in roots]

e = rmat_graph(9, edge_factor=8, seed=3)
roots = np.flatnonzero(e.out_degrees())[[0, 7, 41]]
g1 = build_blocked_1d(e, 4, align=32, cap_pad=32)
out = {}
for dc, lm, st in local_ops.registered_combos():
    if lm != "dense":
        continue
    meshes = ([("2x2", build_blocked(e, 2, 2, align=32, cap_pad=32),
                make_local_mesh(2, 2)),
               ("1x4", build_blocked(e, 1, 4, align=32, cap_pad=32),
                make_local_mesh(1, 4))] if dc == "2d"
              else [("p4", g1, make_local_mesh_1d(4))])
    ops = local_ops.get_local_ops(dc, lm, st)
    cfg = BFSConfig(decomposition=dc, storage=st, instrument=False)
    rows = []
    for name, g, mesh in meshes:
        got = run(g, cfg, mesh, roots)
        local_ops.unregister_local_ops(dc, lm, st)
        local_ops.register_local_ops(dataclasses.replace(
            ops, bottomup=searching))
        try:
            want = run(g, cfg, mesh, roots)
        finally:
            local_ops.unregister_local_ops(dc, lm, st)
            local_ops.register_local_ops(ops)
        for r, a, b in zip(roots, got, want):
            ok, msg = validate_parents(e.n, e.src, e.dst, int(r), a.parents)
            rows.append(dict(
                mesh=name, root=int(r), valid=bool(ok), msg=msg,
                depths=bool(np.array_equal(
                    depths_from_parents(e.n, a.parents, int(r)),
                    bfs_depths(e.n, e.src, e.dst, int(r)))),
                same=bool(np.array_equal(a.parents, b.parents)),
                bu_levels=int((a.level_stats[:, 2] == 1).sum())))
    out["-".join((dc, lm, st))] = rows
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def dense_mesh_runs():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, "-c", _DENSE_MESH_MAIN],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("combo", [
    "-".join(c) for c in local_ops.registered_combos() if c[1] == "dense"])
def test_dense_parity_on_multi_device_mesh(dense_mesh_runs, combo):
    """Dense bottom-up discovery reads each edge's row from edge_dst; on
    a mesh with more than one column (2d) or strip (1d/1ds) its trees
    hold to the oracle and equal, bit for bit, those of the rp_seg
    search, with bottom-up levels run."""
    rows = dense_mesh_runs[combo]
    assert rows
    for row in rows:
        assert row["valid"], (combo, row)
        assert row["depths"] and row["same"], (combo, row)
    for mesh in {row["mesh"] for row in rows}:
        assert sum(row["bu_levels"] for row in rows
                   if row["mesh"] == mesh) > 0, (combo, mesh)


def test_multiroot_routes_through_registry():
    """make_multiroot_bfs_fn must honour local_mode instead of always
    shipping the dense key set."""
    from repro.core.bfs import make_multiroot_bfs_fn
    from repro.core.partition import make_partition
    part = make_partition(256, 1, 1, align=32)
    import jax
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
        ("pod", "data", "model"))
    _, keys = make_multiroot_bfs_fn(mesh, part, BFSConfig(storage="dcsc"),
                                    cap_seg=32, n_roots=1, maxdeg=16,
                                    local_mode="kernel")
    assert "jc" in keys and "edge_src" not in keys
    _, keys_d = make_multiroot_bfs_fn(mesh, part, BFSConfig(), cap_seg=32,
                                      n_roots=1)
    assert "edge_src" in keys_d and "jc" not in keys_d


# ---------------------------------------------------------------------------
# Strip-DCSC builder invariants
# ---------------------------------------------------------------------------


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_strip_dcsc_roundtrips_to_edge_list(seed):
    """(jc, cp, row_idx) per strip reconstructs exactly the dense edge
    list, jc is strictly increasing over non-empty GLOBAL columns, and
    the segment walk agrees with the uncompressed col_ptr."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(3, 80))
    m = int(rng.integers(1, 4 * n))
    p = int(rng.integers(1, 5))
    e = preprocess(rng.integers(0, n, m), rng.integers(0, n, m), n,
                   symmetrize=True)
    if e.m == 0:
        return
    g = build_blocked_1d(e, p, align=32, cap_pad=32, with_col_ptr=True)
    part = g.part
    got = set()
    maxseg = 0
    for b in range(p):
        k, nz = int(g.nnz[b]), int(g.nzc[b])
        jc, cp = g.jc[b], g.cp[b]
        assert (jc[nz:] == part.n).all() and (cp[nz:] == k).all()
        cols = jc[:nz].astype(np.int64)
        if nz > 1:
            assert (np.diff(cols) > 0).all()
        for s in range(nz):
            lo, hi = int(cp[s]), int(cp[s + 1])
            assert hi > lo                        # non-empty by definition
            maxseg = max(maxseg, hi - lo)
            for t in range(lo, hi):
                got.add((int(cols[s]), int(g.row_idx[b, t]) + b * part.chunk))
        # uncompressed col_ptr agrees with the compressed walk
        deg = np.diff(g.col_ptr[b].astype(np.int64))
        assert np.array_equal(np.flatnonzero(deg), cols)
    assert got == set(zip(e.src.tolist(), e.dst.tolist()))
    assert g.maxdeg_col == maxseg


def test_strip_storage_words_match_closed_forms():
    """storage_words(mode) minus the shared bottom-up row_ptr equals the
    comm_model closed forms, and DCSC wins by a growing margin as p
    grows at fixed n (the §5.1 asymptotics, 1D edition)."""
    e = rmat_graph(10, edge_factor=2, seed=4)
    ratios = []
    for p in (2, 8):
        g = build_blocked_1d(e, p, align=32, cap_pad=32)
        bu = (g.part.chunk + 1) * p
        csr = g.storage_words("csr")["pointer_i32"] - bu
        dcsc = g.storage_words("dcsc")["pointer_i32"] - bu
        assert csr == comm_model.strip_csr_pointer_words(g.part.n, p)
        assert dcsc == comm_model.strip_dcsc_pointer_words(
            int(g.nzc.sum()), p)
        assert g.storage_words("csr")["index_i32"] \
            == g.storage_words("dcsc")["index_i32"]
        ratios.append(csr / dcsc)
    assert ratios[1] > ratios[0] > 1.0, ratios
    with pytest.raises(ValueError):
        g.storage_words("nope")


def test_build_without_col_ptr_gates_csr_kernel():
    e = rmat_graph(8, edge_factor=8, seed=1)
    g = build_blocked_1d(e, 1, align=32, cap_pad=32)   # default: no blow-up
    assert g.col_ptr is None and "col_ptr" not in g.device_arrays()
    root = int(np.flatnonzero(e.out_degrees())[0])
    mesh = make_local_mesh_1d(1)
    with pytest.raises(ValueError, match="lacks arrays"):
        run_bfs(g, root, BFSConfig(decomposition="1d"), mesh,
                local_mode="kernel")
    # dcsc kernel path needs no col_ptr at all
    r = run_bfs(g, root, BFSConfig(decomposition="1d", storage="dcsc"),
                mesh, local_mode="kernel")
    ok, msg = validate_parents(e.n, e.src, e.dst, root, r.parents)
    assert ok, msg
