"""The benchmark's plain reference: its copy of the edge stream matches
the program's device build, and its searches and tree check agree with
straightforward definitions."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import reference  # noqa: E402

KRON = (0.57, 0.19, 0.19)
URAND = (0.25, 0.25, 0.25)


def _gen(abc, scale=8, seed=1):
    a, b, c = abc
    return {"kind": "kronecker", "a": a, "b": b, "c": c, "edge_factor": 16,
            "seed": seed}


@pytest.mark.parametrize("abc", [KRON, URAND], ids=["kron", "urand"])
def test_stream_copy_matches_program_stream(abc):
    from repro.graph.rmat import rmat_edges_counter
    got = reference.counter_edges(9, 16, *abc, seed=3, start=100,
                                  count=5000)
    want = rmat_edges_counter(9, 16, *abc, seed=3, start=100, count=5000)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("abc", [KRON, URAND], ids=["kron", "urand"])
def test_graph_matches_device_build(abc):
    """The reference graph is the edge set ``dist_build`` stores (one
    1x1 block: local ids are global ids)."""
    from repro.graph.dist_build import BuildSpec, dist_build
    from repro.launch.mesh import make_local_mesh
    scale = 8
    g = reference.generate(_gen(abc), scale)
    a, b, c = abc
    graph, _ = dist_build(BuildSpec(scale=scale, edge_factor=16, seed=1,
                                    a=a, b=b, c=c), "2d",
                          make_local_mesh(1, 1), (1, 1))
    nnz = int(np.asarray(graph.nnz).reshape(-1)[0])
    u = np.asarray(graph.edge_src).reshape(-1)[:nnz].astype(np.int64)
    v = np.asarray(graph.row_idx).reshape(-1)[:nnz].astype(np.int64)
    np.testing.assert_array_equal(np.sort(u * g.n + v), g.keys)
    assert graph.m == g.m and graph.m_input == g.m_input


def test_urand_endpoints_are_uniform():
    src, dst = reference.counter_edges(10, 16, *URAND, seed=7)
    for x in (src, dst):
        counts = np.bincount(x, minlength=1 << 10)
        assert counts.max() < 3 * counts.mean()


def test_many_source_depths_equal_single_source():
    g = reference.generate(_gen(KRON, scale=10), 10)
    isolated = np.flatnonzero(g.degree == 0)
    roots = [0, 5, 77, 1023] + list(isolated[:1])
    many = reference.bfs_depths_many(g, roots)
    for r, row in zip(roots, many):
        np.testing.assert_array_equal(row, reference.bfs_depths(g, r))


def _valid_parents(g, root):
    depth = reference.bfs_depths(g, root)
    parent = np.full(g.n, -1, np.int64)
    parent[root] = root
    rows = np.repeat(np.arange(g.n), g.degree)
    ok = (depth[rows] >= 0) & (depth[g.adj] == depth[rows] + 1)
    parent[g.adj[ok]] = rows[ok]
    return parent, depth


def test_tree_check_accepts_a_bfs_tree_and_counts_each_fault():
    g = reference.generate(_gen(KRON, scale=10), 10)
    root = 3
    parent, depth = _valid_parents(g, root)
    assert reference.bad_vertices(g, root, parent, depth) == 0
    v = int(np.flatnonzero(depth == 2)[0])

    unreached = parent.copy()
    unreached[v] = -1
    assert reference.bad_vertices(g, root, unreached, depth) == 1

    not_an_edge = parent.copy()
    not_an_edge[v] = next(u for u in range(g.n)
                          if not reference.has_edges(g, np.array([u]),
                                                     np.array([v]))[0]
                          and depth[u] == depth[v] - 1)
    assert reference.bad_vertices(g, root, not_an_edge, depth) == 1

    wrong_root = parent.copy()
    wrong_root[root] = -1
    assert reference.bad_vertices(g, root, wrong_root, depth) == 1

    out_of_range = parent.copy()
    out_of_range[v] = g.n + 5
    assert reference.bad_vertices(g, root, out_of_range, depth) == 1

    assert reference.bad_vertices(g, root, parent[:-1], depth) == g.n
