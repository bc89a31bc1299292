"""Device-side distributed build: counter-stream twins, shard-count
independence, p=1 bit-parity with the host builders, capacity overflow
loudness, the vertex relabel of builds over several shards, and the
datasets.py de-clamping.  Single-device fast-lane cases, and one
four-device subprocess for the relabeled 2x2 and four-strip builds; the
16-device parity sweep is tests/_dist_bfs_main.py mode "born"
(test_bfs_distributed.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.graph.rmat import (VertexRelabel, rmat_edges_counter,
                              rmat_edges_counter_jax,
                              rmat_edges_counter_kernel, rmat_graph)

SCALE, EF, SEED = 9, 8, 3


def test_counter_twins_bit_identical():
    """numpy / jnp / Pallas generators are the same pure function of
    (seed, edge index)."""
    count = 1 << 10
    su, sv = rmat_edges_counter(SCALE, EF, seed=SEED, start=0, count=count)
    ju, jv = rmat_edges_counter_jax(SCALE, count, 0, edge_factor=EF,
                                    seed=SEED)
    ku, kv = rmat_edges_counter_kernel(SCALE, count, 0, edge_factor=EF,
                                       seed=SEED, interpret=True)
    assert np.array_equal(su, np.asarray(ju))
    assert np.array_equal(sv, np.asarray(jv))
    assert np.array_equal(su, np.asarray(ku))
    assert np.array_equal(sv, np.asarray(kv))


def test_counter_offset_slices():
    """A slice at an arbitrary offset equals that window of the full
    stream (the property the per-device slicing depends on)."""
    full_u, full_v = rmat_edges_counter(SCALE, EF, seed=SEED)
    u, v = rmat_edges_counter(SCALE, EF, seed=SEED, start=777, count=333)
    assert np.array_equal(u, full_u[777:1110])
    assert np.array_equal(v, full_v[777:1110])
    ku, kv = rmat_edges_counter_kernel(SCALE, 333, 777, edge_factor=EF,
                                       seed=SEED, interpret=True)
    assert np.array_equal(np.asarray(ku), full_u[777:1110])
    assert np.array_equal(np.asarray(kv), full_v[777:1110])


@pytest.mark.parametrize("p", [1, 2, 4, 7])
def test_counter_shard_count_independent(p):
    """Concatenating p per-shard slices reproduces the full stream for
    ANY p — shard k of p is reproducible independent of p."""
    m = EF << SCALE
    full_u, full_v = rmat_edges_counter(SCALE, EF, seed=SEED)
    m_per = -(-m // p)
    parts = [rmat_edges_counter(SCALE, EF, seed=SEED, start=k * m_per,
                                count=min(m_per, m - k * m_per))
             for k in range(p)]
    assert np.array_equal(np.concatenate([a for a, _ in parts]), full_u)
    assert np.array_equal(np.concatenate([b for _, b in parts]), full_v)


def test_rmat_graph_generator_arg():
    legacy = rmat_graph(SCALE, edge_factor=EF, seed=SEED)
    again = rmat_graph(SCALE, edge_factor=EF, seed=SEED,
                       generator="numpy")
    assert np.array_equal(legacy.src, again.src)   # pinned graphs intact
    counter = rmat_graph(SCALE, edge_factor=EF, seed=SEED,
                         generator="counter")
    assert counter.m_input == legacy.m_input
    assert not np.array_equal(legacy.src, counter.src)  # distinct streams
    with pytest.raises(ValueError):
        rmat_graph(SCALE, edge_factor=EF, generator="bogus")


def _single_device_mesh():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("data",)), \
        Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def test_p1_build_parity_1d():
    """Device build at p=1 is bit-identical to the host builder on the
    counter-generated edge list: every device array, every capacity."""
    from repro.graph.dist_build import BuildSpec, dist_build_1d
    from repro.graph.formats import build_blocked_1d
    mesh1, _ = _single_device_mesh()
    spec = BuildSpec(scale=SCALE, edge_factor=EF, seed=SEED)
    gd, info = dist_build_1d(spec, 1, mesh1, align=32, cap_pad=32)
    edges = rmat_graph(SCALE, edge_factor=EF, seed=SEED,
                       generator="counter")
    gh = build_blocked_1d(edges, 1, align=32, cap_pad=32)
    assert (gd.cap, gd.cap_nzc, gd.maxdeg_col, gd.m, gd.m_input) == \
        (gh.cap, gh.cap_nzc, gh.maxdeg_col, gh.m, gh.m_input)
    ha = gh.device_arrays()
    for k, v in gd.device_arrays().items():
        assert np.array_equal(np.asarray(v), ha[k]), k
    assert info["m"] == gh.m and info["build_teps"] > 0


def test_p1_build_parity_2d():
    from repro.graph.dist_build import BuildSpec, dist_build_2d
    from repro.graph.formats import build_blocked
    _, mesh2 = _single_device_mesh()
    spec = BuildSpec(scale=SCALE, edge_factor=EF, seed=SEED)
    gd, _ = dist_build_2d(spec, 1, 1, mesh2, align=32, cap_pad=32)
    edges = rmat_graph(SCALE, edge_factor=EF, seed=SEED,
                       generator="counter")
    gh = build_blocked(edges, 1, 1, align=32, cap_pad=32)
    assert (gd.cap, gd.cap_seg, gd.maxdeg_col, gd.m) == \
        (gh.cap, gh.cap_seg, gh.maxdeg_col, gh.m)
    ha = gh.device_arrays()
    for k, v in gd.device_arrays().items():
        assert np.array_equal(np.asarray(v), ha[k]), k


# ---------------------------------------------------------------------------
# the vertex relabel of builds over several shards
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scale", range(1, 17))
def test_relabel_is_a_bijection(scale):
    """Every id of [0, 2**scale) maps to a distinct id of that range,
    ``invert`` undoes ``apply``, the jnp twin agrees bit for bit, and
    ids outside the range pass through."""
    import jax.numpy as jnp
    h = VertexRelabel.from_seed(scale, SEED)
    x = np.arange(1 << scale)
    y = h.apply(x)
    assert np.array_equal(np.sort(y), x)
    assert np.array_equal(h.invert(y), x)
    assert np.array_equal(np.asarray(h.apply(jnp.asarray(x, jnp.int32),
                                             jnp)), y)
    assert np.array_equal(np.asarray(h.invert(jnp.asarray(y, jnp.int32),
                                              jnp)), x)
    out = np.array([-1, 1 << scale, (1 << scale) + 5])
    assert np.array_equal(h.apply(out), out)
    assert np.array_equal(h.invert(out), out)


@pytest.mark.parametrize("scale", range(21, 27))
def test_relabel_inverts_at_deployment_scales(scale):
    n = 1 << scale
    h = VertexRelabel.from_seed(scale, 1)
    x = np.concatenate([np.arange(64), n - 1 - np.arange(64),
                        np.random.default_rng(scale).choice(
                            n, 1 << 16, replace=False)])
    x = np.unique(x)
    y = h.apply(x)
    assert ((y >= 0) & (y < n)).all() and np.unique(y).size == x.size
    assert np.array_equal(h.invert(y), x)
    assert np.array_equal(h.apply(h.invert(x)), x)
    assert not np.array_equal(y, x)


def test_counter_twins_relabel_identically():
    """The device build relabels the jnp stream and the store's shard
    regeneration the numpy stream: both twins give the same ids."""
    import jax.numpy as jnp
    h = VertexRelabel.from_seed(SCALE, SEED)
    count = 1 << 10
    su, sv = rmat_edges_counter(SCALE, EF, seed=SEED, start=5, count=count)
    ju, jv = rmat_edges_counter_jax(SCALE, count, 5, edge_factor=EF,
                                    seed=SEED)
    for s, j in ((su, ju), (sv, jv)):
        assert np.array_equal(h.apply(s), np.asarray(h.apply(j, jnp)))
        assert not np.array_equal(h.apply(s), s)


def test_one_shard_build_is_not_relabeled():
    """The relabel is derived from the shard count: none on one shard,
    so one-chip graphs keep the input labels."""
    from repro.graph.dist_build import BuildSpec, build_relabel
    spec = BuildSpec(scale=SCALE, edge_factor=EF, seed=SEED)
    assert build_relabel(spec, 1) is None
    assert build_relabel(spec, 4) == VertexRelabel.from_seed(SCALE, SEED)
    mesh1, mesh2 = _single_device_mesh()
    from repro.graph.dist_build import dist_build
    for dc, mesh, grid in (("1d", mesh1, 1), ("2d", mesh2, (1, 1))):
        g, info = dist_build(spec, dc, mesh, grid, align=32, cap_pad=32)
        assert g.relabel is None and info["retry_log"] == [], dc


# dist_build on four forced host devices at scale 14: the relabeled 2x2
# and four-strip builds, their host twins, the shards the store would
# regenerate, and each block's share of the stored edges with and
# without the relabel
_FOUR_MAIN = """
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.graph import dist_build as db
from dataclasses import replace
from repro.graph.formats import build_blocked, build_blocked_1d
from repro.graph.rmat import relabel_edges, rmat_graph
from repro.launch.mesh import make_local_mesh, make_local_mesh_1d

spec = db.BuildSpec(scale=14, edge_factor=16, seed=1)
e = rmat_graph(14, 16, seed=1, generator="counter")
h = db.build_relabel(spec, 4)
eh = relabel_edges(e, h)
out = {"plain_shares": (build_blocked(e, 2, 2).nnz.ravel() / e.m).tolist()}
g2, i2 = db.dist_build(spec, "2d", make_local_mesh(2, 2), (2, 2))
g1, i1 = db.dist_build(spec, "1d", make_local_mesh_1d(4), 4)
same = {}
for name, gd, gh in (("2d", g2, replace(build_blocked(eh, 2, 2), relabel=h)),
                     ("1d", g1, replace(build_blocked_1d(eh, 4), relabel=h))):
    ha = gh.device_arrays()
    same[name] = (gd.relabel == h == gh.relabel
                  and (gd.m, gd.cap) == (gh.m, gh.cap)
                  and all(np.array_equal(np.asarray(v), ha[k])
                          for k, v in gd.device_arrays().items()))
regen = []
for k in range(4):
    i, j = divmod(k, 2)
    want = {f: np.asarray(v)[i, j] for f, v in g2.device_arrays().items()}
    got = db.regen_shard_2d(spec, g2.part, i, j, cap=g2.cap,
                            cap_seg=g2.cap_seg,
                            cap_nzc=want["jc"].shape[-1],
                            cap_nzr=want["jr"].shape[-1])
    regen.append(all(np.array_equal(got[f], want[f]) for f in want))
    want = {f: np.asarray(v)[k] for f, v in g1.device_arrays().items()}
    got = db.regen_shard_1d(spec, g1.part, k, cap=g1.cap,
                            cap_nzc=g1.cap_nzc)
    regen.append(all(np.array_equal(got[f], want[f]) for f in want))
out.update(
    shares=(np.asarray(g2.nnz).ravel() / g2.m).tolist(),
    cap_share=g2.cap / g2.m, seg_share=2 * g2.cap_seg / g2.m,
    strip_shares=(np.asarray(g1.nnz).ravel() / g1.m).tolist(),
    retry=[i2["retry_log"], i1["retry_log"]], same=same, regen=regen)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_shard_builds():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, "-c", _FOUR_MAIN],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.splitlines()[-1])


def test_relabel_balances_the_2x2_blocks(four_shard_builds):
    """The plain stream puts most edges in block (0,0); relabeled, each
    block holds a quarter of them, within 3 points, and so does each
    chip's padded scan (cap, and pc * cap_seg bottom-up)."""
    out = four_shard_builds
    assert out["plain_shares"][0] > 0.45
    for share in out["shares"] + out["strip_shares"]:
        assert abs(share - 0.25) <= 0.03, out
    assert out["cap_share"] <= 0.28 and out["seg_share"] <= 0.28, out
    assert out["retry"] == [[], []]


def test_relabeled_builds_match_their_host_twins(four_shard_builds):
    """At p > 1 the device build stays bit-identical to the host
    builders given the same relabel, and every shard the store would
    regenerate equals the built one."""
    assert four_shard_builds["same"] == {"2d": True, "1d": True}
    assert all(four_shard_builds["regen"])


def test_route_caps_fit_the_relabeled_deployment_stream():
    """At the four-chip cell's size (scale 21 on 2x2) the relabeled
    stream's heaviest bucket of each routing hop fits its cap, so the
    build needs no retry, and fills more than half of it: the caps are
    sized for the balanced stream, not for R-MAT's skew.  Counted on the
    host from the counter stream each device generates."""
    from repro.core.partition import make_partition
    from repro.graph.dist_build import (BuildSpec, build_relabel,
                                        route_caps_2d)
    spec = BuildSpec(scale=21, edge_factor=16, seed=1)
    pr = pc = 2
    part = make_partition(spec.n, pr, pc, 128)
    h = build_relabel(spec, pr * pc)
    m_per = -(-spec.m_input // (pr * pc))
    hop1 = np.zeros((pr, pc, pc), np.int64)       # device (i, j) -> bj
    hop2 = np.zeros((pr, pc, pr), np.int64)       # device (i, bj) -> bi
    for k in range(pr * pc):
        i, j = divmod(k, pc)
        u, v = rmat_edges_counter(spec.scale, spec.edge_factor, spec.a,
                                  spec.b, spec.c, spec.seed,
                                  start=k * m_per, count=m_per)
        u, v = h.apply(u), h.apply(v)
        keep = u != v
        ru = np.concatenate([u[keep], v[keep]])
        rv = np.concatenate([v[keep], u[keep]])
        bj, bi = ru // part.nc, rv // part.nr
        hop1[i, j] = np.bincount(bj, minlength=pc)
        hop2[i] += np.bincount(bj * pr + bi,
                               minlength=pc * pr).reshape(pc, pr)
    cap_r1, cap_r2 = route_caps_2d(spec, pr, pc)
    assert cap_r1 / 2 < hop1.max() <= cap_r1, (hop1, cap_r1)
    assert cap_r2 / 2 < hop2.max() <= cap_r2, (hop2, cap_r2)


def test_route_overflow_is_loud():
    """Starving the routing buckets must raise, never truncate edges."""
    from repro.graph.dist_build import BuildSpec, dist_build_1d
    mesh1, _ = _single_device_mesh()
    spec = BuildSpec(scale=SCALE, edge_factor=EF, seed=SEED)
    with pytest.raises(RuntimeError, match="route_slack"):
        dist_build_1d(spec, 1, mesh1, align=32, cap_pad=32,
                      route_slack=0.01)


def test_build_spec_validation():
    from repro.graph.dist_build import BuildSpec
    with pytest.raises(ValueError, match="int32"):
        BuildSpec(scale=31).validate()
    with pytest.raises(ValueError, match="uint32"):
        BuildSpec(scale=30, edge_factor=8).validate()
    BuildSpec(scale=18).validate()


def test_build_wire_closed_forms():
    from repro.core import comm_model
    assert comm_model.build_route_1d_words(1000, 4) == \
        pytest.approx(2 * 1000 * 3 / 4)
    assert comm_model.build_route_2d_words(1000, 2, 2) == \
        pytest.approx(2 * 1000 * (0.5 + 0.5))
    # padded volume dominates the measured minimum
    share = comm_model.relabeled_route_share(4, scale=10)
    cap = comm_model.plan_cap_route(1000, 4, share)
    assert comm_model.build_route_padded_words(4, cap) >= \
        comm_model.build_route_1d_words(1000, 4)
    assert 0.25 < share < 0.35
    assert comm_model.relabeled_route_share(1, scale=10) == 1.0


# ---------------------------------------------------------------------------
# datasets.py de-clamping
# ---------------------------------------------------------------------------


def test_edges_for_small_path_unchanged():
    from repro.graph.datasets import _edges_for
    s, d = _edges_for(512, 4096, seed=0)
    assert s.size == 4096 and d.size == 4096
    assert s.max() < 512 and d.max() < 512


def test_edges_for_large_scale_uses_counter_not_clamp():
    """A scale-17 request previously clamped to scale 16 silently; now
    it comes from the counter stream at the TRUE scale."""
    from repro.graph.datasets import _edges_for
    n_nodes, n_edges = 1 << 17, 4096
    s, d = _edges_for(n_nodes, n_edges, seed=0)
    assert s.size == n_edges
    su, _ = rmat_edges_counter(17, 1, seed=0, start=0, count=n_edges)
    assert np.array_equal(s, (su % n_nodes).astype(np.int32))


def test_edges_for_impossible_request_raises():
    from repro.graph.datasets import _edges_for
    with pytest.raises(ValueError, match="dist_build"):
        _edges_for(1 << 31, 1 << 36, seed=0)
