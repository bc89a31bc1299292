"""The plan → compile → run session API (repro.core.engine) and the
decomposition registry (repro.core.decomp): parity with the one-shot
``run_bfs`` across the full combo matrix, compile-once/ship-once
guarantees, plan-validation error paths, and pod-batched multi-source
runs in both decompositions."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.base import BFSConfig
from repro.core import decomp, local_ops
from repro.core.bfs import run_bfs
from repro.core.engine import BFSEngine, plan_bfs, plan_for_part
from repro.core.partition import make_partition, make_partition_1d
from repro.core.ref import bfs_depths, depths_from_parents, validate_parents
from repro.graph.formats import build_blocked, build_blocked_1d
from repro.graph.rmat import rmat_graph
from repro.launch.mesh import make_local_mesh, make_local_mesh_1d


@pytest.fixture(scope="module")
def fixed_graph():
    e = rmat_graph(8, edge_factor=8, seed=4)
    # with_col_ptr: the matrix includes the 1d/kernel/csr cell
    return (e, build_blocked_1d(e, 1, align=32, cap_pad=32,
                                with_col_ptr=True),
            build_blocked(e, 1, 1, align=32, cap_pad=32))


def _mesh_for(d, **kw):
    return make_local_mesh(1, 1, **kw) if d == "2d" \
        else make_local_mesh_1d(1, **kw)


def _graph_for(d, g1, g2):
    return g2 if d == "2d" else g1      # 1d and 1ds share the strip format


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_decomp_registry():
    assert decomp.registered_decompositions() == ("1d", "1ds", "2d")
    with pytest.raises(ValueError, match="no decomposition registered"):
        decomp.get_decomposition("1.5d")
    for name in decomp.registered_decompositions():
        entry = decomp.get_decomposition(name)
        assert entry.n_axes == len(entry.axis_sizes(
            make_partition(64, 1, 1, align=32) if name == "2d"
            else make_partition_1d(64, 1, align=32)))


@pytest.mark.parametrize("d", ["1d", "1ds", "2d"])
def test_plan_on_cpu_mesh_interprets_kernels(fixed_graph, d):
    """A plan reads the interpret switch off its mesh: on a CPU mesh
    the Pallas kernels run in the interpreter, and the flag reaches the
    level steps (tests/test_tpu_compile.py pins the TPU side)."""
    e, g1, g2 = fixed_graph
    plan = plan_bfs(_graph_for(d, g1, g2),
                    BFSConfig(decomposition=d, storage="dcsc"),
                    _mesh_for(d), local_mode="kernel")
    assert plan.statics.interpret and plan.level_args().interpret


def test_unknown_decomposition_rejected_at_plan(fixed_graph):
    e, g1, g2 = fixed_graph
    with pytest.raises(ValueError, match="no decomposition registered"):
        plan_bfs(g2, BFSConfig(decomposition="3d"), make_local_mesh(1, 1))


# ---------------------------------------------------------------------------
# Parity vs run_bfs across the full combo matrix
# ---------------------------------------------------------------------------


def test_engine_parity_matrix(fixed_graph):
    """engine.run must return bit-identical parents AND counters to the
    one-shot run_bfs in every (decomposition, local_mode, storage)
    combo — the engine only changes WHEN compilation happens."""
    e, g1, g2 = fixed_graph
    root = int(np.flatnonzero(e.out_degrees())[0])
    for dc, lm, st_ in local_ops.registered_combos():
        g = _graph_for(dc, g1, g2)
        mesh = _mesh_for(dc)
        cfg = BFSConfig(decomposition=dc, storage=st_)
        ref = run_bfs(g, root, cfg, mesh, local_mode=lm)
        eng = plan_bfs(g, cfg, mesh, local_mode=lm).compile()
        res = eng.run(root)
        assert np.array_equal(res.parents, ref.parents), (dc, lm, st_)
        assert res.n_levels == ref.n_levels, (dc, lm, st_)
        assert res.counters == ref.counters, (dc, lm, st_)
        assert np.array_equal(res.level_stats, ref.level_stats), (dc, lm, st_)


def test_instrument_off_parity_matrix(fixed_graph):
    """The instrument=False fast path (one fused scalar reduction per
    level, counters compiled out) must return bit-identical parents and
    level counts to the instrumented program in every (decomposition,
    local_mode, storage) combo; an uninstrumented run carries NO
    counters (not zeros that read as measurements), level_stats columns
    0-3 (n_f, m_f, mode, used) bit-identical to the instrumented
    program's, and NaN in column 4 (expand words it does not
    measure)."""
    e, g1, g2 = fixed_graph
    root = int(np.flatnonzero(e.out_degrees())[0])
    for dc, lm, st_ in local_ops.registered_combos():
        g = _graph_for(dc, g1, g2)
        mesh = _mesh_for(dc)
        ref = plan_bfs(g, BFSConfig(decomposition=dc, storage=st_), mesh,
                       local_mode=lm).compile().run(root)
        eng = plan_bfs(g, BFSConfig(decomposition=dc, storage=st_,
                                    instrument=False),
                       mesh, local_mode=lm).compile()
        assert eng.instrument is False
        res = eng.run(root)
        assert np.array_equal(res.parents, ref.parents), (dc, lm, st_)
        assert res.n_levels == ref.n_levels, (dc, lm, st_)
        assert res.counters == {}, (dc, lm, st_)
        assert np.array_equal(res.level_stats[:, :4],
                              ref.level_stats[:, :4]), (dc, lm, st_)
        assert np.isnan(res.level_stats[:, 4]).all(), (dc, lm, st_)


def test_instrument_off_direction_switching(fixed_graph):
    """The fast path reads the direction heuristics off the previous
    level's fused reduction — the mode sequence must still match the
    instrumented program's level_stats (asserted via identical depths
    AND identical level counts on a graph that actually switches)."""
    e, g1, g2 = fixed_graph
    root = int(np.flatnonzero(e.out_degrees())[0])
    for diro in (False, True):
        cfg_i = BFSConfig(direction_optimizing=diro)
        cfg_f = BFSConfig(direction_optimizing=diro, instrument=False)
        ri = plan_bfs(g2, cfg_i, make_local_mesh(1, 1)).compile().run(root)
        rf = plan_bfs(g2, cfg_f, make_local_mesh(1, 1)).compile().run(root)
        assert np.array_equal(ri.parents, rf.parents), diro
        assert ri.n_levels == rf.n_levels, diro
    # with diropt the instrumented run really used bottom-up somewhere
    modes = ri.level_stats[: ri.n_levels, 2]
    assert modes.max() == 1.0


@pytest.mark.parametrize("ec", [2, 4])
def test_pipelined_expand_parity_matrix(fixed_graph, ec):
    """expand_chunks > 1 (the software-pipelined expand) must return
    bit-identical parents to the unpipelined program in every
    decomposition x local_mode x storage combo (plus the raw-id "1ds"
    codec), instrumented AND fast — chunking reorders the gather, never
    the (select-source, min) semiring result.  Instrumented runs must
    also keep the identical per-level mode sequence."""
    e, g1, g2 = fixed_graph
    root = int(np.flatnonzero(e.out_degrees())[0])
    cases = [(dc, lm, st_, None) for dc, lm, st_
             in local_ops.registered_combos()]
    cases += [("1ds", "dense", "csr", "none")]
    for dc, lm, st_, codec in cases:
        g = _graph_for(dc, g1, g2)
        mesh = _mesh_for(dc)
        kw = {} if codec is None else {"frontier_codec": codec}
        ref = plan_bfs(g, BFSConfig(decomposition=dc, storage=st_, **kw),
                       mesh, local_mode=lm).compile().run(root)
        res = plan_bfs(g, BFSConfig(decomposition=dc, storage=st_,
                                    expand_chunks=ec, **kw),
                       mesh, local_mode=lm).compile().run(root)
        key = (dc, lm, st_, codec, ec)
        assert np.array_equal(res.parents, ref.parents), key
        assert res.n_levels == ref.n_levels, key
        # identical direction decisions: stats cols (n_f, m_f, mode,
        # used); wire_expand (col 4) may legitimately differ for "1ds"
        # (per-sub-range overflow can flip a level to the dense
        # fallback) and the 2d ring pays its extra G-chain permutes
        assert np.array_equal(res.level_stats[:, :4],
                              ref.level_stats[:, :4]), key
        if dc != "1ds":
            assert np.array_equal(res.level_stats, ref.level_stats), key
        resf = plan_bfs(g, BFSConfig(decomposition=dc, storage=st_,
                                     expand_chunks=ec, instrument=False,
                                     **kw),
                        mesh, local_mode=lm).compile().run(root)
        assert np.array_equal(resf.parents, ref.parents), key
        assert resf.n_levels == ref.n_levels, key
        assert resf.counters == {}, key


# ---------------------------------------------------------------------------
# Compile-once / ship-once
# ---------------------------------------------------------------------------


def test_run_many_compiles_once_ships_once(fixed_graph, monkeypatch):
    """The acceptance bar: over >=4 roots, exactly one jit trace and one
    graph shipment (one device_put per shipped key, all during
    compile(), none during run)."""
    e, g1, g2 = fixed_graph
    roots = np.flatnonzero(e.out_degrees() > 0)[:4]
    assert len(roots) >= 4
    puts = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **kw: puts.append(1) or real_put(*a, **kw))
    plan = plan_bfs(g2, BFSConfig(), make_local_mesh(1, 1))
    eng = plan.compile()
    assert len(puts) == len(plan.keys)          # graph shipped exactly once
    assert eng.trace_count == 1                 # one jit trace at compile()
    ref = [run_bfs(g2, int(r), BFSConfig(), make_local_mesh(1, 1))
           for r in roots]
    n_puts_after_compile = len(puts)
    results = eng.run_many(roots)
    assert len(puts) == n_puts_after_compile    # no re-shipping per root
    assert eng.trace_count == 1                 # no re-tracing per root
    for got, want, r in zip(results, ref, roots):
        assert np.array_equal(got.parents, want.parents), int(r)
        assert got.counters == want.counters, int(r)
        assert got.n_levels == want.n_levels, int(r)


# ---------------------------------------------------------------------------
# Plan-validation error paths
# ---------------------------------------------------------------------------


def test_plan_rejects_mismatched_graph(fixed_graph):
    e, g1, g2 = fixed_graph
    with pytest.raises(TypeError, match="does not match"):
        plan_bfs(g2, BFSConfig(decomposition="1d"), make_local_mesh_1d(1))
    with pytest.raises(TypeError, match="does not match"):
        plan_bfs(g1, BFSConfig(), make_local_mesh(1, 1))


def test_plan_rejects_mismatched_partition():
    part1 = make_partition_1d(256, 1, align=32)
    with pytest.raises(TypeError, match="needs a Partition2D"):
        plan_for_part(part1, BFSConfig(), make_local_mesh(1, 1), cap_seg=32)


def test_plan_rejects_mesh_geometry_mismatch():
    e = rmat_graph(8, edge_factor=8, seed=1)
    g = build_blocked_1d(e, 2, align=32, cap_pad=32)   # 2 strips...
    with pytest.raises(ValueError, match="mesh axis"):
        plan_bfs(g, BFSConfig(decomposition="1d"),
                 make_local_mesh_1d(1))                # ...1-device mesh
    part = make_partition(256, 1, 1, align=32)
    with pytest.raises(ValueError, match="mesh has no"):
        plan_for_part(part, BFSConfig(), make_local_mesh(1, 1),
                      cap_seg=32, row_axis="nope")


def test_plan_rejects_missing_cap_seg():
    part = make_partition(256, 1, 1, align=32)
    with pytest.raises(ValueError, match="cap_seg"):
        plan_for_part(part, BFSConfig(), make_local_mesh(1, 1))


def test_plan_rejects_missing_kernel_arrays():
    e = rmat_graph(8, edge_factor=8, seed=1)
    g = build_blocked_1d(e, 1, align=32, cap_pad=32)   # no col_ptr
    with pytest.raises(ValueError, match="lacks arrays"):
        plan_bfs(g, BFSConfig(decomposition="1d", storage="csr"),
                 make_local_mesh_1d(1), local_mode="kernel")


def test_engine_requires_concrete_graph():
    part = make_partition(256, 1, 1, align=32)
    plan = plan_for_part(part, BFSConfig(), make_local_mesh(1, 1), cap_seg=32)
    with pytest.raises(ValueError, match="no graph attached"):
        BFSEngine(plan)


def test_plan_rejects_missing_cap_x():
    """Graph-less "1ds" plans must pass cap_x explicitly (plan_bfs
    derives it from the graph degree stats)."""
    part = make_partition_1d(256, 1, align=32)
    with pytest.raises(ValueError, match="cap_x"):
        plan_for_part(part, BFSConfig(decomposition="1ds"),
                      make_local_mesh_1d(1))
    with pytest.raises(ValueError, match="exceeds the owned chunk"):
        plan_for_part(part, BFSConfig(decomposition="1ds"),
                      make_local_mesh_1d(1), cap_x=part.chunk + 32)
    plan_for_part(part, BFSConfig(decomposition="1ds"),
                  make_local_mesh_1d(1), cap_x=32)   # explicit cap is fine


def test_plan_rejects_bad_expand_chunks():
    """The software-pipelined expand needs expand_chunks >= 1, dividing
    the strip's packed word count (1d/1ds) and cap_x (1ds) — a ragged
    sub-chunk would silently mis-align the owner-major gather layout,
    so the plan must fail loudly instead."""
    part = make_partition_1d(256, 1, align=32)     # chunk=256 -> 8 words
    with pytest.raises(ValueError, match="expand_chunks"):
        plan_for_part(part, BFSConfig(decomposition="1d",
                                      expand_chunks=0),
                      make_local_mesh_1d(1))
    with pytest.raises(ValueError, match="does not divide the per-device"):
        plan_for_part(part, BFSConfig(decomposition="1d",
                                      expand_chunks=3),
                      make_local_mesh_1d(1))
    with pytest.raises(ValueError, match="does not divide the per-device"):
        plan_for_part(part, BFSConfig(decomposition="1ds",
                                      expand_chunks=16),
                      make_local_mesh_1d(1), cap_x=32)
    with pytest.raises(ValueError, match="does not divide cap_x"):
        plan_for_part(part, BFSConfig(decomposition="1ds",
                                      expand_chunks=4),
                      make_local_mesh_1d(1), cap_x=34)
    # divisors of both are fine, in every decomposition
    for dc, kw in (("1d", {}), ("1ds", dict(cap_x=32))):
        plan_for_part(part, BFSConfig(decomposition=dc, expand_chunks=4),
                      make_local_mesh_1d(1), **kw)
    part2 = make_partition(256, 1, 1, align=32)
    plan_for_part(part2, BFSConfig(expand_chunks=2), make_local_mesh(1, 1),
                  cap_seg=32)                      # 2d: any >= 1


# ---------------------------------------------------------------------------
# Root validation at the engine boundary
# ---------------------------------------------------------------------------


def test_engine_rejects_out_of_range_roots():
    """Graphs are padded up to p*chunk: a root in the ghost range (or
    negative) used to silently traverse nothing and return an all-empty
    parents array.  run/run_many/run_batch must all reject it."""
    from repro.graph.rmat import preprocess
    rng = np.random.default_rng(0)
    n = 300                              # NOT a multiple of the quantum
    e = preprocess(rng.integers(0, n, 600), rng.integers(0, n, 600), n,
                   symmetrize=True)
    g1 = build_blocked_1d(e, 1, align=32, cap_pad=32)
    g2 = build_blocked(e, 1, 1, align=32, cap_pad=32)
    for dc in ("2d", "1d", "1ds"):
        g = _graph_for(dc, g1, g2)
        eng = plan_bfs(g, BFSConfig(decomposition=dc),
                       _mesh_for(dc, pods=1)).compile()
        n_orig, n_pad = g.part.n_orig, g.part.n
        assert n_pad > n_orig            # the ghost range exists
        for bad in (-1, n_orig, n_pad - 1, n_pad):
            with pytest.raises(ValueError, match="out of range"):
                eng.run(bad)
        with pytest.raises(ValueError, match="out of range"):
            eng.run_many([0, n_orig])
        with pytest.raises(ValueError, match="out of range"):
            eng.run_batch([0, n_orig])
        # in-range roots still work after the rejects
        assert eng.run(0).parents.shape == (n_orig,)


# ---------------------------------------------------------------------------
# Pod-batched multi-source runs (both decompositions)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dc", ["1d", "1ds", "2d"])
def test_run_batch_valid_multisource(fixed_graph, dc):
    """run_batch must produce valid trees with oracle depths from every
    root, in the 1D decompositions as well as 2D (the pod axis batches
    whole searches; pods=1 exercises the full program shape)."""
    e, g1, g2 = fixed_graph
    g = _graph_for(dc, g1, g2)
    roots = np.flatnonzero(e.out_degrees() > 0)[:4]
    eng = plan_bfs(g, BFSConfig(decomposition=dc),
                   _mesh_for(dc, pods=1)).compile()
    batch = eng.run_batch(roots)
    assert batch.parents.shape == (len(roots), e.n)
    assert batch.level_stats.shape == (len(roots), decomp.MAX_LEVELS, 5)
    for i, r in enumerate(roots):
        ok, msg = validate_parents(e.n, e.src, e.dst, int(r),
                                   batch.parents[i])
        assert ok, (dc, int(r), msg)
        d = bfs_depths(e.n, e.src, e.dst, int(r))
        assert np.array_equal(
            depths_from_parents(e.n, batch.parents[i], int(r)), d), (dc, r)
        assert batch.n_levels[i] >= d[d >= 0].max()
    # batched program compiled once, cached for repeat calls
    n_traces = eng.trace_count
    eng.run_batch(roots)
    assert eng.trace_count == n_traces


def test_run_batch_errors(fixed_graph):
    e, g1, g2 = fixed_graph
    eng = plan_bfs(g2, BFSConfig(), make_local_mesh(1, 1)).compile()
    with pytest.raises(ValueError, match="no 'pod' axis"):
        eng.run_batch([0, 1])
    eng_p = plan_bfs(g2, BFSConfig(), make_local_mesh(1, 1, pods=1)).compile()
    with pytest.raises(ValueError, match="do not split evenly"):
        eng_p.run_batch([])


# ---------------------------------------------------------------------------
# Relabeled graphs: roots and parents in input labels
# ---------------------------------------------------------------------------


def test_one_device_program_has_no_relabel(fixed_graph):
    """A graph stored under its input labels (every one-shard build)
    compiles the same search as before the relabel existed: the plan
    carries none, and no op of the program lies under bfs.relabel."""
    from repro.core import scopes
    from repro.graph.dist_build import BuildSpec, dist_build
    spec = BuildSpec(scale=8, edge_factor=8, seed=4)
    g, _ = dist_build(spec, "2d", make_local_mesh(1, 1), (1, 1),
                      align=32, cap_pad=32)
    plan = plan_bfs(g, BFSConfig(decomposition="2d", instrument=False),
                    make_local_mesh(1, 1))
    assert plan.statics.relabel is None
    assert scopes.RELABEL not in set(plan.compile().op_scopes().values())


# every dense combo on 4 forced host devices (2d on 2x2 and 1x4, 1d/1ds
# on four strips), over a graph built under the relabel: each root's
# parents from run_many (validated on the device), run_batch and, for a
# broken copy, validate_parents; the oracle is the same program on the
# relabeled edge list stored as it stands, its parents mapped back
_RELABEL_MAIN = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
sys.path.insert(0, {bench!r})
import reference
from repro.configs.base import BFSConfig
from repro.core import local_ops
from repro.core.engine import plan_bfs
from repro.core.validate import validate_parents
from dataclasses import replace
from repro.graph.formats import build_blocked, build_blocked_1d
from repro.graph.rmat import VertexRelabel, relabel_edges, rmat_graph
from repro.launch.mesh import make_local_mesh, make_local_mesh_1d

e = rmat_graph(9, edge_factor=8, seed=3)
h = VertexRelabel.from_seed(9, 3)
eh = relabel_edges(e, h)
g_ref = reference.undirected_graph(e.n, e.src, e.dst)
roots = np.flatnonzero(e.out_degrees())[[0, 7, 41, 60]]
stored = h.apply(np.arange(e.n))
out = {{}}
for dc, lm, st in local_ops.registered_combos():
    if lm != "dense":
        continue
    if dc == "2d":
        meshes = [(f"{{pr}}x{{pc}}", build_blocked(eh, pr, pc),
                   make_local_mesh(pr, pc, pods=1))
                  for pr, pc in ((2, 2), (1, 4))]
    else:
        meshes = [("p4", build_blocked_1d(eh, 4),
                   make_local_mesh_1d(4, pods=1))]
    cfg = BFSConfig(decomposition=dc, storage=st, instrument=False)
    rows = []
    for name, g_stored, mesh in meshes:
        g = replace(g_stored, relabel=h)
        eng = plan_bfs(g, cfg, mesh).compile()
        oracle = plan_bfs(g_stored, cfg, mesh).compile()
        got = eng.run_many(roots, validate=True)
        batch = eng.run_batch(roots)
        for i, (r, res) in enumerate(zip(roots, got)):
            want = oracle.run(int(stored[r])).parents
            mapped = h.invert(want[stored])
            depth = reference.bfs_depths(g_ref, int(r))
            broken = res.parents.copy()
            v = int(np.flatnonzero((broken >= 0) & (np.arange(e.n) != r))[0])
            broken[v] = v
            rows.append(dict(
                mesh=name, root=int(r), same=bool(np.array_equal(
                    res.parents, mapped)),
                batch=bool(np.array_equal(batch.parents[i], res.parents)),
                bad=reference.bad_vertices(g_ref, int(r), res.parents, depth),
                validated=bool(res.validation.ok),
                caught=not validate_parents(eng, int(r), broken).ok,
                bu_levels=int((res.level_stats[:, 2] == 1).sum())))
    out["-".join((dc, lm, st))] = rows
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def relabeled_runs():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    code = _RELABEL_MAIN.format(bench=os.path.join(here, "..", "bench"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("combo", [
    "-".join(c) for c in local_ops.registered_combos() if c[1] == "dense"])
def test_relabeled_search_returns_input_labels(relabeled_runs, combo):
    """On a relabeled graph, search/run_many/run_batch return parents in
    input labels: the oracle's on the relabeled graph, mapped back, bit
    for bit; valid under the benchmark reference's rules; validated on
    the device; and a broken parent is caught there."""
    rows = relabeled_runs[combo]
    assert rows
    for row in rows:
        assert row["same"] and row["batch"], (combo, row)
        assert row["bad"] == 0 and row["validated"], (combo, row)
        assert row["caught"], (combo, row)
    assert sum(row["bu_levels"] for row in rows) > 0, combo


# ---------------------------------------------------------------------------
# Compat wrappers still honour the registry
# ---------------------------------------------------------------------------


def test_make_bfs_fn_1d_overrides_decomposition():
    """make_bfs_fn_1d must build the 1D program even when handed a cfg
    whose decomposition field still says 2d (pre-engine behavior)."""
    from repro.core.bfs import make_bfs_fn_1d
    part = make_partition_1d(256, 1, align=32)
    _, keys = make_bfs_fn_1d(make_local_mesh_1d(1), part,
                             BFSConfig(decomposition="2d"))
    assert "seg_ptr" not in keys          # 1D key set, not 2D


def test_compat_builders_accept_cap_x():
    """The legacy builders must be able to build "1ds" programs — cap_x
    has no graph to be planned from there, so they pass it through."""
    import jax
    from repro.core.bfs import make_bfs_fn, make_multiroot_bfs_fn
    part = make_partition_1d(256, 1, align=32)
    _, keys = make_bfs_fn(make_local_mesh_1d(1), part,
                          BFSConfig(decomposition="1ds"), cap_x=32)
    assert "edge_src" in keys
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1, 1), ("pod", "data"))
    _, keys = make_multiroot_bfs_fn(mesh, part,
                                    BFSConfig(decomposition="1ds"),
                                    cap_seg=0, n_roots=1, cap_x=32)
    assert "edge_src" in keys


def test_cfg_decomposition_read_directly(fixed_graph):
    """BFSConfig declares the field; a cfg object lacking it is a bug,
    not something the engine papers over with getattr defaults."""
    e, g1, g2 = fixed_graph

    class NotACfg:
        storage = "csr"
    with pytest.raises(AttributeError):
        plan_bfs(g2, NotACfg(), make_local_mesh(1, 1))
