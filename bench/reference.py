"""The plain reference: the Graph500 edge stream and breadth-first search
in straightforward numpy, independent of the system under test.

``counter_edges`` is the counter-based R-MAT stream: edge ``e``'s
quadrant path is a pure function of (seed, e, level) through a uint32
mixing hash, so any slice of the stream can be drawn on its own.  The
system under test builds its graph from the same stream; a test in
``bench/tests`` holds the two equal.  With A=B=C=0.25 the stream gives
independent uniform endpoints (a uniform random graph).

``Graph`` is the undirected graph Graph500 searches: self loops
dropped, every edge in both directions, duplicates removed, in CSR
order.  ``bfs_depths`` is level-synchronous search from one root, and
``bad_vertices`` counts the vertices at which a parent array breaks the
Graph500 validation rules against those depths.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

_M32 = 0xFFFFFFFF
_GOLDEN = 0x9E3779B9
_CHUNK = 1 << 22          # edges drawn per numpy pass: bounds host memory


def _mix_int(x: int) -> int:
    x &= _M32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & _M32
    x ^= x >> 15
    x = (x * 0x846CA68B) & _M32
    x ^= x >> 16
    return x


def level_salt(seed: int, level: int) -> int:
    return _mix_int((int(seed) * 0x85EBCA6B + level * 0xC2B2AE35
                     + 0x27D4EB2F) & _M32)


def thresholds(a: float, b: float, c: float) -> tuple[int, int, int]:
    """Cumulative quadrant thresholds as uint32 comparands: a draw u
    picks quadrant a/b/c/d by u < t1 / t2 / t3 / else."""
    t1 = min(int(round(a * 2.0 ** 32)), _M32)
    t2 = min(int(round((a + b) * 2.0 ** 32)), _M32)
    t3 = min(int(round((a + b + c) * 2.0 ** 32)), _M32)
    return t1, t2, t3


def counter_edges(scale: int, edge_factor: int, a: float, b: float,
                  c: float, seed: int, start: int = 0,
                  count: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Edges [start, start+count) of the stream of edge_factor * 2**scale
    edges, as int64 (src, dst)."""
    m_input = edge_factor << scale
    count = m_input - start if count is None else count
    if not 0 <= start <= start + count <= m_input:
        raise ValueError(f"slice [{start}, {start + count}) outside the "
                         f"{m_input}-edge stream")
    t1, t2, t3 = (np.uint32(t) for t in thresholds(a, b, c))
    salts = [np.uint32(level_salt(seed, lv)) for lv in range(scale)]
    src = np.zeros(count, np.int64)
    dst = np.zeros(count, np.int64)
    for lo in range(0, count, _CHUNK):
        hi = min(count, lo + _CHUNK)
        idx = (np.arange(lo, hi, dtype=np.uint64) + start).astype(np.uint32)
        idx *= np.uint32(_GOLDEN)
        s = np.zeros(hi - lo, np.int64)
        d = np.zeros(hi - lo, np.int64)
        for level, salt in enumerate(salts):
            x = idx ^ salt
            x ^= x >> np.uint32(16)
            x *= np.uint32(0x7FEB352D)
            x ^= x >> np.uint32(15)
            x *= np.uint32(0x846CA68B)
            x ^= x >> np.uint32(16)
            s |= (x >= t2).astype(np.int64) << level
            d |= (((x >= t1) & (x < t2)) | (x >= t3)).astype(np.int64) \
                << level
        src[lo:hi], dst[lo:hi] = s, d
    return src, dst


@dataclass(frozen=True)
class Graph:
    """Undirected, deduplicated graph in CSR order: the neighbours of u
    are ``adj[ptr[u]:ptr[u+1]]``, ascending."""
    n: int
    ptr: np.ndarray       # int64[n+1]
    adj: np.ndarray       # int64[m]
    keys: np.ndarray      # int64[m], u * n + v for each edge, ascending
    m_input: int          # edges in the stream, the TEPS numerator

    @property
    def m(self) -> int:
        return int(self.adj.shape[0])

    @property
    def degree(self) -> np.ndarray:
        return np.diff(self.ptr)


def undirected_graph(n: int, src: np.ndarray, dst: np.ndarray) -> Graph:
    m_input = int(src.shape[0])
    keep = src != dst
    u = np.concatenate([src[keep], dst[keep]])
    v = np.concatenate([dst[keep], src[keep]])
    key = np.unique(u * np.int64(n) + v)
    ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=ptr[1:])
    return Graph(n=n, ptr=ptr, adj=key % n, keys=key, m_input=m_input)


def generate(gen: dict, scale: int) -> Graph:
    """The graph a configuration's ``generator`` block defines."""
    if gen["kind"] != "kronecker":
        raise ValueError(f"unknown generator kind {gen['kind']!r}")
    src, dst = counter_edges(scale, gen["edge_factor"], gen["a"], gen["b"],
                             gen["c"], gen["seed"])
    return undirected_graph(1 << scale, src, dst)


def _neighbours(g: Graph, frontier: np.ndarray) -> np.ndarray:
    lo, hi = g.ptr[frontier], g.ptr[frontier + 1]
    counts = hi - lo
    total = int(counts.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    offsets = np.repeat(lo - np.cumsum(counts) + counts, counts)
    return g.adj[offsets + np.arange(total)]


def bfs_depths(g: Graph, root: int) -> np.ndarray:
    """Depth of every vertex from ``root`` (-1 where unreachable)."""
    depth = np.full(g.n, -1, np.int64)
    depth[root] = 0
    frontier = np.array([root], np.int64)
    d = 0
    while frontier.size:
        nbrs = _neighbours(g, frontier)
        new = np.unique(nbrs[depth[nbrs] < 0])
        d += 1
        depth[new] = d
        frontier = new
    return depth


def bfs_depths_many(g: Graph, roots) -> np.ndarray:
    """``bfs_depths`` from each of ``roots``, as rows of an int16 array:
    up to 64 searches at a time advance together, one bit of a uint64
    per search, and each level ORs every vertex's neighbours' bits."""
    roots = np.asarray(roots, np.int64)
    out = np.full((roots.size, g.n), -1, np.int16)
    rows = np.flatnonzero(g.degree > 0)
    for lo in range(0, roots.size, 64):
        batch = roots[lo:lo + 64]
        k = batch.size
        bit = np.uint64(1) << np.arange(k, dtype=np.uint64)
        frontier = np.zeros(g.n, np.uint64)
        np.bitwise_or.at(frontier, batch, bit)
        seen = frontier.copy()
        depth = np.full((g.n, k), -1, np.int16)
        depth[batch, np.arange(k)] = 0
        d = 0
        while True:
            reach = np.zeros(g.n, np.uint64)
            if rows.size:
                reach[rows] = np.bitwise_or.reduceat(frontier[g.adj],
                                                     g.ptr[rows])
            new = reach & ~seen
            hit = np.flatnonzero(new)
            if hit.size == 0:
                break
            d += 1
            seen |= new
            got = ((new[hit, None] >> np.arange(k, dtype=np.uint64))
                   & np.uint64(1)).astype(bool)
            block = depth[hit]
            block[got] = d
            depth[hit] = block
            frontier = new
        out[lo:lo + k] = depth.T
    return out


def has_edges(g: Graph, u: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Whether each (u[i], v[i]) is an edge of ``g``."""
    if g.m == 0:
        return np.zeros(u.shape, bool)
    want = u * np.int64(g.n) + v
    return g.keys[np.minimum(g.keys.searchsorted(want), g.m - 1)] == want


def bad_vertices(g: Graph, root: int, parents: np.ndarray,
                 depth: np.ndarray) -> int:
    """Vertices at which ``parents`` breaks the Graph500 BFS-tree rules,
    given the reference depths from ``root``: the root is its own
    parent; a vertex has a parent exactly when the reference reaches
    it; every other reached vertex's parent is a neighbour one level
    nearer the root.  0 means the tree is a valid BFS tree."""
    parents = np.asarray(parents, np.int64)
    if parents.shape != (g.n,):
        return g.n
    bad = (parents >= 0) != (depth >= 0)
    bad[root] |= parents[root] != root
    vs = np.flatnonzero((parents >= 0) & (depth >= 0))
    vs = vs[vs != root]
    ps = parents[vs]
    inside = ps < g.n
    ok = np.zeros(vs.shape, bool)
    ok[inside] = (depth[ps[inside]] == depth[vs[inside]] - 1) \
        & has_edges(g, ps[inside], vs[inside])
    bad[vs[~ok]] = True
    return int(bad.sum())
