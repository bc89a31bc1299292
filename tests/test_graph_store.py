"""Graph + executable checkpoint store, and the config_hash it rides on:
cross-process hash stability, loud failures on stale specs / wrong
meshes / unhashable configs, save->load->traverse round trips, atomicity
under an interrupted save, retention.  Single-device fast-lane cases;
the multi-device disk->traversal lane is benchmarks/worker.py."""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.ckpt.checkpoint import config_hash
from repro.configs.base import BFSConfig
from repro.graph.dist_build import BuildSpec
from repro.graph.formats import build_blocked_1d
from repro.graph.rmat import rmat_graph

SPEC = BuildSpec(scale=8, edge_factor=8, seed=3)


# ---------------------------------------------------------------------------
# config_hash (satellite: repr()-hashing replaced by canonical JSON)
# ---------------------------------------------------------------------------


def test_config_hash_key_order_invariant():
    assert config_hash({"a": 1, "b": [2, 3]}) == \
        config_hash({"b": (2, 3), "a": np.int64(1)})


def test_config_hash_distinguishes_values():
    assert config_hash(SPEC) != config_hash(
        dataclasses.replace(SPEC, seed=4))


def test_config_hash_rejects_arbitrary_objects():
    """repr() fallbacks embedded id() memory addresses; now it's a loud
    error instead of a hash that never matches across processes."""
    with pytest.raises(TypeError, match="memory address"):
        config_hash(object())
    with pytest.raises(TypeError):
        config_hash({"f": lambda: 0})


def test_config_hash_stable_across_processes():
    """The regression the canonical-JSON rewrite exists for: the same
    dataclass must hash identically in a fresh interpreter."""
    code = (
        "import sys; sys.path.insert(0, {src!r})\n"
        "from repro.ckpt.checkpoint import config_hash\n"
        "from repro.graph.dist_build import BuildSpec\n"
        "print(config_hash(BuildSpec(scale=8, edge_factor=8, seed=3)))\n"
    ).format(src=os.path.join(os.path.dirname(__file__), "..", "src"))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == config_hash(SPEC)


# ---------------------------------------------------------------------------
# graph round trips
# ---------------------------------------------------------------------------


def _host_graph(p=1):
    edges = rmat_graph(SPEC.scale, edge_factor=SPEC.edge_factor,
                       seed=SPEC.seed, generator="counter")
    return build_blocked_1d(edges, p, align=32, cap_pad=32)


def _mesh1():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def test_graph_round_trip_arrays_and_parents(tmp_path):
    from repro.ckpt.graph_store import GraphStore, plan_bfs_from_store
    from repro.core.engine import plan_bfs
    store = GraphStore(str(tmp_path))
    g = _host_graph()
    store.save_graph("g", g, spec=SPEC)
    loaded = store.load_graph("g", expect_spec=SPEC)
    assert type(loaded) is type(g)
    assert (loaded.cap, loaded.cap_nzc, loaded.maxdeg_col, loaded.m,
            loaded.m_input) == (g.cap, g.cap_nzc, g.maxdeg_col, g.m,
                                g.m_input)
    ha = g.device_arrays()
    for k, v in loaded.device_arrays().items():
        assert np.array_equal(np.asarray(v), np.asarray(ha[k])), k
    # disk -> traversal: parents identical to the in-memory graph's
    mesh = _mesh1()
    cfg = BFSConfig(decomposition="1d", instrument=False)
    ra = plan_bfs(g, cfg, mesh).compile().run(5)
    rb = plan_bfs_from_store(store, "g", cfg, mesh,
                             expect_spec=SPEC).compile().run(5)
    assert np.array_equal(ra.parents, rb.parents)
    assert ra.n_levels == rb.n_levels


def test_relabeled_graph_round_trip(tmp_path):
    """A graph stored under relabeled ids keeps its relabel through the
    store, so a loaded session still answers in input labels."""
    from repro.ckpt.graph_store import GraphStore, plan_bfs_from_store
    from repro.core.engine import plan_bfs
    from repro.graph.rmat import VertexRelabel, relabel_edges
    store = GraphStore(str(tmp_path))
    edges = rmat_graph(SPEC.scale, edge_factor=SPEC.edge_factor,
                       seed=SPEC.seed, generator="counter")
    h = VertexRelabel.from_seed(SPEC.scale, SPEC.seed)
    g = dataclasses.replace(
        build_blocked_1d(relabel_edges(edges, h), 1, align=32, cap_pad=32),
        relabel=h)
    store.save_graph("r", g, spec=SPEC)
    assert store.load_graph("r").relabel == h
    cfg = BFSConfig(decomposition="1d", instrument=False)
    ra = plan_bfs(g, cfg, _mesh1()).compile().run(5)
    rb = plan_bfs_from_store(store, "r", cfg, _mesh1(),
                             expect_spec=SPEC).compile().run(5)
    assert np.array_equal(ra.parents, rb.parents)
    assert ra.parents[5] == 5


def test_stale_spec_hash_fails_loudly(tmp_path):
    from repro.ckpt.graph_store import GraphStore
    store = GraphStore(str(tmp_path))
    store.save_graph("g", _host_graph(), spec=SPEC)
    with pytest.raises(ValueError, match="spec_hash"):
        store.load_graph("g", expect_spec=dataclasses.replace(SPEC, seed=9))


def test_mesh_mismatch_fails_loudly(tmp_path):
    from repro.ckpt.graph_store import GraphStore
    store = GraphStore(str(tmp_path))
    store.save_graph("g2", _host_graph(p=2), spec=SPEC)  # built for p=2
    with pytest.raises(ValueError, match="partitioned for"):
        store.load_graph("g2", mesh=_mesh1())            # mesh has data=1
    # without a mesh the p=2 shards load fine (host-side inspection)
    assert store.load_graph("g2").part.p == 2


def test_interrupted_save_is_atomic(tmp_path, monkeypatch):
    """Killing the writer mid-save must leave the previous step intact
    and publish nothing partial."""
    from repro.ckpt import checkpoint
    from repro.ckpt.graph_store import GraphStore
    store = GraphStore(str(tmp_path))
    g = _host_graph()
    store.save_graph("g", g, spec=SPEC)
    before = checkpoint.latest_step(os.path.join(str(tmp_path),
                                                 "graphs", "g"))

    real_savez = np.savez

    def dying_savez(*a, **kw):
        raise OSError("simulated crash mid-write")

    monkeypatch.setattr(np, "savez", dying_savez)
    with pytest.raises(OSError):
        store.save_graph("g", g, spec=SPEC)
    monkeypatch.setattr(np, "savez", real_savez)

    gdir = os.path.join(str(tmp_path), "graphs", "g")
    assert checkpoint.latest_step(gdir) == before
    assert not [d for d in os.listdir(gdir) if d.startswith(".tmp_")]
    loaded = store.load_graph("g", expect_spec=SPEC)    # survivor readable
    assert loaded.m == g.m


def test_retention_keeps_newest(tmp_path):
    from repro.ckpt.graph_store import GraphStore
    store = GraphStore(str(tmp_path), keep=2)
    g = _host_graph()
    for _ in range(5):
        store.save_graph("g", g, spec=SPEC)
    gdir = os.path.join(str(tmp_path), "graphs", "g")
    steps = sorted(d for d in os.listdir(gdir) if d.startswith("step_"))
    assert steps == ["step_0000000003", "step_0000000004"]
    assert store.load_graph("g").m == g.m


# ---------------------------------------------------------------------------
# executable round trips
# ---------------------------------------------------------------------------


def test_executable_store_hit(tmp_path):
    from repro.ckpt.graph_store import GraphStore
    from repro.core.engine import plan_bfs
    store = GraphStore(str(tmp_path))
    g = _host_graph()
    mesh = _mesh1()
    cfg = BFSConfig(decomposition="1d", instrument=False)
    e1 = plan_bfs(g, cfg, mesh).compile(store=store)
    assert not e1.exec_from_store and e1.compile_s > 0
    e2 = plan_bfs(g, cfg, mesh).compile(store=store)
    assert e2.exec_from_store and e2.compile_s == 0.0
    assert np.array_equal(e1.run(5).parents, e2.run(5).parents)
    # a different plan misses (its hash differs) and compiles fresh
    e3 = plan_bfs(g, dataclasses.replace(cfg, alpha=7.0),
                  mesh).compile(store=store)
    assert not e3.exec_from_store


def test_build_spec_registry_round_trips():
    from repro.configs.build_specs import (BUILD_SPECS, get_build_spec,
                                           store_name)
    for name, spec in BUILD_SPECS.items():
        spec.validate()
        assert get_build_spec(name) is spec
        assert json.dumps({"h": config_hash(spec)})   # canonicalizable
    assert store_name("g500-s14", "1ds") == "g500-s14-1d"
    assert store_name("g500-s14", "2d") == "g500-s14-2d"
    with pytest.raises(KeyError):
        get_build_spec("nope")
