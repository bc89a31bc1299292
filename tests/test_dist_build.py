"""Device-side distributed build: counter-stream twins, shard-count
independence, p=1 bit-parity with the host builders, capacity overflow
loudness, and the datasets.py de-clamping.  All single-device fast-lane
cases; the 16-device parity sweep is tests/_dist_bfs_main.py mode
"born" (test_bfs_distributed.py)."""
import numpy as np
import pytest

from repro.graph.rmat import (rmat_edges_counter, rmat_edges_counter_jax,
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
    cap = comm_model.plan_cap_route(1000, 4)
    assert comm_model.build_route_padded_words(4, cap) >= \
        comm_model.build_route_1d_words(1000, 4)
    assert 0 < comm_model.rmat_strip_skew(16) < 1


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
