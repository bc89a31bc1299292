"""The level program's named scopes (core/scopes.py) and what reads them
back: ``op_scope`` on op_name paths, ``hlo_op_scopes`` /
``BFSEngine.op_scopes`` on compiled HLO, the fast path's level_stats in
the pod-batched program, where the exchange sits on a multi-device
mesh, and the build's compile/run split."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs.base import BFSConfig
from repro.core import local_ops, scopes
from repro.core.engine import hlo_op_scopes, plan_bfs
from repro.graph import dist_build as db
from repro.graph.formats import build_blocked, build_blocked_1d
from repro.graph.rmat import rmat_graph
from repro.launch.mesh import make_local_mesh, make_local_mesh_1d


@pytest.fixture(scope="module")
def fixed_graph():
    e = rmat_graph(8, edge_factor=8, seed=4)
    return (e, build_blocked_1d(e, 1, align=32, cap_pad=32,
                                with_col_ptr=True),
            build_blocked(e, 1, 1, align=32, cap_pad=32))


def _plan(fixed_graph, dc, lm="dense", st="csr", pods=None, **cfg):
    e, g1, g2 = fixed_graph
    kw = {} if pods is None else {"pods": pods}
    mesh = make_local_mesh(1, 1, **kw) if dc == "2d" \
        else make_local_mesh_1d(1, **kw)
    return plan_bfs(g2 if dc == "2d" else g1,
                    BFSConfig(decomposition=dc, storage=st, **cfg), mesh,
                    local_mode=lm)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/while/body/cond/branch_1_fun/bfs.bottomup/discover/"
     "edge_rows/jit(searchsorted)/while/body/lt", "bfs.bottomup/discover/"
     "edge_rows"),
    ("jit(f)/shard_map/while/body/cond/branch_0_fun/bfs.topdown/expand/"
     "cond/branch_1_fun/all_gather", "bfs.topdown/expand"),
    ("jit(f)/while/body/cond/branch_0_fun/bfs.topdown/cond/branch_1_fun/"
     "discover/min", "bfs.topdown/discover"),
    ("jit(f)/while/body/bfs.reduce/psum", "bfs.reduce"),
    ("jit(f)/while/body/bfs.topdown/scatter", "bfs.topdown"),
    ("jit(f)/while/cond/lt", None),
    ("jit(f)/expand_dims", None),
])
def test_op_scope_reads_the_vocabulary(op_name, scope):
    assert scopes.op_scope(op_name) == scope


def test_hlo_op_scopes_parses_compiled_text():
    """Instruction names come without their ``%``, ROOT lines count,
    and instructions outside every scope (or without metadata) are
    left out."""
    hlo = (
        '  %fusion.32 = s32[8]{0} fusion(s32[9]{0} %p), kind=kLoop, '
        'calls=%fc.32, metadata={op_name="jit(f)/while/body/cond/'
        'branch_1_fun/bfs.bottomup/discover/edge_rows/searchsorted" '
        'source_file="ref.py" source_line=32}\n'
        '  ROOT %all-reduce.3 = f32[4]{0} all-reduce(f32[4]{0} %s), '
        'metadata={op_name="jit(f)/while/body/bfs.reduce/psum"}\n'
        '  %copy.8 = s32[8]{0} copy(s32[8]{0} %fusion.32)\n'
        '  %lt.1 = pred[] compare(s32[] %a, s32[] %b), direction=LT, '
        'metadata={op_name="jit(f)/while/cond/lt"}\n'
        # lowered with debug info: no `%`
        '  ppermute.9 = u32[2]{0} collective-permute(reduce_sum.64), '
        'metadata={op_name="while/body/cond/branch_1_fun/bfs.bottomup/'
        'expand/ppermute" stack_frame_id=31}\n')
    assert hlo_op_scopes(hlo) == {
        "fusion.32": "bfs.bottomup/discover/edge_rows",
        "all-reduce.3": "bfs.reduce", "ppermute.9": "bfs.bottomup/expand"}


@pytest.mark.parametrize("combo", local_ops.registered_combos(),
                         ids=lambda c: "-".join(c))
def test_op_scopes_cover_every_combo(fixed_graph, combo):
    """The timed (instrument=False) search of every registered
    (decomposition, local_mode, storage) combo names its discovery
    phases and its reduction on the device."""
    dc, lm, st = combo
    eng = _plan(fixed_graph, dc, lm, st, instrument=False).compile()
    found = set(eng.op_scopes().values())
    for want in (f"{scopes.TOPDOWN}/{scopes.DISCOVER}",
                 f"{scopes.BOTTOMUP}/{scopes.DISCOVER}", scopes.REDUCE):
        assert any(s == want or s.startswith(want + "/") for s in found), \
            (combo, want, sorted(found))


def test_dense_bottomup_names_its_row_lookup(fixed_graph):
    """Dense local discovery looks each edge's row up per edge; that
    lookup has a scope of its own under bottom-up discovery."""
    eng = _plan(fixed_graph, "2d", instrument=False).compile()
    assert "bfs.bottomup/discover/edge_rows" in set(eng.op_scopes().values())


@pytest.mark.parametrize("combo", [c for c in local_ops.registered_combos()
                                   if c[1] == "dense"],
                         ids=lambda c: "-".join(c))
def test_dense_bottomup_reads_rows_without_searching(fixed_graph, combo):
    """Dense bottom-up discovery reads each edge's row from the shipped
    edge_dst: its row lookup compiles to no loop, and no while of the
    timed search lies under a level-program scope (the level loop
    itself lies outside them)."""
    dc, lm, st = combo
    found = _plan(fixed_graph, dc, lm, st,
                  instrument=False).compile().op_scopes()
    rows = f"{scopes.BOTTOMUP}/{scopes.DISCOVER}/{scopes.EDGE_ROWS}"
    assert rows in set(found.values()), combo
    loops = sorted((name, scope) for name, scope in found.items()
                   if name.startswith("while"))
    assert not loops, (combo, loops)


@pytest.mark.parametrize("dc", ["1d", "1ds", "2d"])
def test_fast_batch_level_stats_match_single_runs(fixed_graph, dc):
    """Pod-batched fast searches carry each root's own level_stats:
    columns 0-3 equal the instrumented single-root program's, and
    column 4 (not measured) is NaN."""
    e = fixed_graph[0]
    roots = np.flatnonzero(e.out_degrees() > 0)[:2]
    eng = _plan(fixed_graph, dc, pods=1).compile()
    fast = _plan(fixed_graph, dc, pods=1, instrument=False).compile()
    batch = fast.run_batch(roots)
    for i, r in enumerate(roots):
        ref = eng.run(int(r))
        assert np.array_equal(batch.level_stats[i][:, :4],
                              ref.level_stats[:, :4]), (dc, int(r))
        assert np.isnan(batch.level_stats[i][:, 4]).all(), (dc, int(r))


# every budget case (core/decomp.py registry) lowered on 8 forced host
# devices, instrument off and on: (collective kind, scope) of each
# collective instruction, read from the lowered HLO's op_name metadata
_EXCHANGE_MAIN = """
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax.numpy as jnp
from repro.analysis import registry
from repro.core.engine import _COLLECTIVE_OP_RE, hlo_op_scopes

out = {}
for case in registry.budget_cases():
    for mode, instr in (("fast", False), ("instrumented", True)):
        plan = registry.plan_case(case.decomposition, case.overrides,
                                  instrument=instr)
        hlo = plan.build_fn().lower(registry._graph_sds(plan),
                                    jnp.int32(0)).as_text(
            dialect="hlo", debug_info=True)
        rows = []
        for line in hlo.splitlines():
            m = _COLLECTIVE_OP_RE.search(line)
            if m and m.group(1):
                rows.append([m.group(1),
                             next(iter(hlo_op_scopes(line).values()), None)])
        out.setdefault(case.name, {})[mode] = rows
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def exchange_scopes():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, "-c", _EXCHANGE_MAIN],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.splitlines()[-1])


def _under_discover(scope) -> bool:
    return scope is not None and scope.split("/")[1:2] == [scopes.DISCOVER]


def test_no_exchange_under_discover(exchange_scopes):
    """Discovery is local work: no collective of the timed program, and
    no collective-permute of the instrumented one, lies under a
    ``discover`` scope, so a trace never counts exchange as discovery."""
    for case, modes in exchange_scopes.items():
        for mode, rows in modes.items():
            for kind, scope in rows:
                if mode == "fast" or kind == "collective-permute":
                    assert not _under_discover(scope), \
                        (case, mode, kind, scope)


def test_2d_ring_rotation_runs_under_expand(exchange_scopes):
    """The 2d bottom-up level's ring rotation (pc - 1 ppermutes, or
    twice that pipelined) is bottom-up expand, not discovery."""
    cases = [c for c in exchange_scopes if c.startswith("2d")]
    assert cases
    for case in cases:
        for mode, rows in exchange_scopes[case].items():
            bu = [scope for kind, scope in rows
                  if kind == "collective-permute" and scope is not None
                  and scope.startswith(scopes.BOTTOMUP)]
            assert bu, (case, mode)
            assert set(bu) == {f"{scopes.BOTTOMUP}/{scopes.EXPAND}"}, \
                (case, mode, sorted(set(bu)))


# the timed search of a relabeled graph on 4 forced host devices (2d on
# 2x2, 1d on four strips), and of the same edges on one device: the
# compiled instructions under each scope
_RELABEL_SCOPES_MAIN = """
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from repro.configs.base import BFSConfig
from repro.core.engine import plan_bfs
from dataclasses import replace
from repro.graph.formats import build_blocked, build_blocked_1d
from repro.graph.rmat import VertexRelabel, relabel_edges, rmat_graph
from repro.launch.mesh import make_local_mesh, make_local_mesh_1d

e = rmat_graph(8, edge_factor=8, seed=4)
h = VertexRelabel.from_seed(8, 4)
eh = relabel_edges(e, h)
out = {}
for name, dc, g, mesh in (
        ("2d-2x2", "2d", replace(build_blocked(eh, 2, 2), relabel=h),
         make_local_mesh(2, 2)),
        ("1d-p4", "1d", replace(build_blocked_1d(eh, 4), relabel=h),
         make_local_mesh_1d(4)),
        ("2d-1x1", "2d", build_blocked(e, 1, 1), make_local_mesh(1, 1))):
    eng = plan_bfs(g, BFSConfig(decomposition=dc, instrument=False),
                   mesh).compile()
    out[name] = sorted(eng.op_scopes().items())
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def relabel_scopes():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, "-c", _RELABEL_SCOPES_MAIN],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("name, gathers", [("2d-2x2", 2), ("1d-p4", 1)])
def test_relabel_runs_under_its_scope(relabel_scopes, name, gathers):
    """The four-device program of a relabeled graph gathers the parents
    (one all-gather per mesh axis) and maps them under bfs.relabel,
    outside every level scope."""
    found = dict(relabel_scopes[name])
    under = sorted(k for k, v in found.items() if v == scopes.RELABEL)
    is_gather = [k.replace("_", "-").startswith("all-gather") for k in under]
    assert sum(is_gather) == gathers, under
    assert not all(is_gather), under


def test_one_device_program_has_no_relabel_ops(relabel_scopes):
    found = set(dict(relabel_scopes["2d-1x1"]).values())
    assert found and scopes.RELABEL not in found


def test_build_compile_s_sums_every_attempt(monkeypatch):
    """``info["compile_s"]`` is the phase compiles of every attempt: a
    build whose routing overflows twice compiles phase 1 three times
    and phase 2 once, and reports all four."""
    seen = []
    real = db._aot

    def spy(fn, *args, spent):
        out = real(fn, *args, spent=spent)
        seen.append(spent[-1])
        return out

    monkeypatch.setattr(db, "_aot", spy)
    spec = db.BuildSpec(scale=8, edge_factor=8, seed=3)
    mesh = make_local_mesh_1d(1)
    graph, info = db.dist_build(spec, "1d", mesh, 1, route_slack=0.3,
                                align=32, cap_pad=32)
    attempts = len(info["retry_log"])
    assert attempts == 3                # two overflows, then a clean build
    assert len(seen) == attempts + 1    # phase 1 each attempt, phase 2 once
    assert info["compile_s"] == pytest.approx(sum(seen))
    one, info1 = db.dist_build_1d(spec, 1, mesh, align=32, cap_pad=32)
    assert 0 < info1["compile_s"] <= info1["build_s"]
