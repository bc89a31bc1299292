"""Graph500 R-MAT generator (Chakrabarti et al.) + preprocessing.

Parameters follow the paper (§7.2): a,b,c,d = 0.57,0.19,0.19,0.05 and
edge factor (average degree) 16 unless stated.  ``scale`` means 2**scale
vertices.  Preprocessing prunes self loops and duplicate edges (the paper
does the same); graphs are used undirected, so edges are symmetrized.

Two generators coexist:

  * ``rmat_edges`` — the original sequential ``np.random.default_rng``
    level-draw generator; kept verbatim so every pinned bench/test
    graph is unchanged.
  * ``rmat_edges_counter`` (+ jax/Pallas twins) — a STATELESS
    counter-based generator: edge e's quadrant path is a pure function
    of (seed, e, level) through a uint32 bit-mixing hash, so any slice
    [start, start+count) of the edge stream is reproducible
    independently of how many shards the stream is split over.  This is
    the reproducibility contract the distributed device-side build
    (graph/dist_build.py) relies on: shard k of p generates edges
    [k*m/p, (k+1)*m/p) and the union is bit-identical for every p.
    The numpy and jnp implementations are bit-identical (pure uint32
    wrapping arithmetic, thresholds precomputed as Python ints).

The counter stream keeps R-MAT's skew in the vertex ids: high-degree
vertices sit at low ids, so on a mesh of several shards the first block
of a 1D or 2D partition holds most of the edges.  ``VertexRelabel`` is
the seeded bijection the distributed build applies to both endpoints
whenever the mesh has more than one shard (Graph500's ``scramble`` in
32-bit form; CombBLAS permutes the matrix the same way before its BFS),
and the search maps roots in and parents out through it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np

_M32 = 0xFFFFFFFF
_GOLDEN = 0x9E3779B9          # counter -> hash stream spreading constant


def _mix_int(x: int) -> int:
    """fmix32-style avalanche on a Python int (mod 2**32)."""
    x &= _M32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & _M32
    x ^= x >> 15
    x = (x * 0x846CA68B) & _M32
    x ^= x >> 16
    return x


def level_salt(seed: int, level: int) -> int:
    """Per-(seed, level) salt for the counter hash — a Python int so the
    numpy / jnp / Pallas twins consume literally the same constant."""
    return _mix_int((int(seed) * 0x85EBCA6B + level * 0xC2B2AE35
                     + 0x27D4EB2F) & _M32)


def rmat_thresholds(a: float, b: float, c: float) -> Tuple[int, int, int]:
    """Cumulative quadrant thresholds as exact uint32 comparands: a draw
    u ~ U[0, 2**32) picks quadrant a/b/c/d by u < t1 / t2 / t3 / else."""
    t1 = min(int(round(a * 2.0 ** 32)), _M32)
    t2 = min(int(round((a + b) * 2.0 ** 32)), _M32)
    t3 = min(int(round((a + b + c) * 2.0 ** 32)), _M32)
    return t1, t2, t3


def _counter_u32_np(idx: np.ndarray, salt: int) -> np.ndarray:
    """One uint32 hash draw per counter (numpy twin of the jnp mixer)."""
    x = (idx * np.uint32(_GOLDEN)) ^ np.uint32(salt)
    x ^= x >> np.uint32(16)
    x = x * np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x = x * np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return x


def _reverse_low_bits(v, bits: int):
    """The low ``bits`` bits of uint32 ``v`` in reverse order."""
    for shift, mask in ((1, 0x55555555), (2, 0x33333333),
                        (4, 0x0F0F0F0F), (8, 0x00FF00FF)):
        s, m = np.uint32(shift), np.uint32(mask)
        v = ((v >> s) & m) | ((v & m) << s)
    v = (v >> np.uint32(16)) | (v << np.uint32(16))
    return v >> np.uint32(32 - bits)


@dataclass(frozen=True)
class VertexRelabel:
    """A seeded bijection ``h`` on the vertex ids [0, 2**scale): two
    rounds of an add, an odd multiply and a reversal of the low
    ``scale`` bits (Graph500's ``scramble``, in uint32).  Ids outside
    that range (-1 for "no parent", padded ghost vertices) pass through
    unchanged, so ``apply`` and ``invert`` are bijections on any padded
    id range too.  Both take numpy or jnp integer arrays (``xp`` names
    the module) and return the input's dtype."""
    scale: int
    adds: Tuple[int, int]
    muls: Tuple[int, int]      # odd

    @classmethod
    def from_seed(cls, scale: int, seed: int) -> "VertexRelabel":
        if not 1 <= scale <= 30:
            raise ValueError(f"relabel scale={scale} outside [1, 30]")
        keys = [_mix_int(int(seed) * 0x2545F491 + k * 0x9E3779B1
                         + 0x6A09E667) for k in range(4)]
        return cls(scale=scale, adds=(keys[0], keys[2]),
                   muls=(keys[1] | 1, keys[3] | 1))

    def _map(self, x, xp, forward: bool):
        inside = (x >= 0) & (x < (1 << self.scale))
        v = xp.where(inside, x, 0).astype(np.uint32)
        if forward:
            for add, mul in zip(self.adds, self.muls):
                v = (v + np.uint32(add)) * np.uint32(mul)
                v = _reverse_low_bits(v, self.scale)
        else:
            low = np.uint32((1 << self.scale) - 1)
            for add, mul in zip(self.adds[::-1], self.muls[::-1]):
                v = _reverse_low_bits(v, self.scale)
                v = (v * np.uint32(pow(mul, -1, 1 << 32))
                     - np.uint32(add)) & low
        return xp.where(inside, v.astype(x.dtype), x)

    def apply(self, x, xp=np):
        """h(x): input label -> the label the graph is stored under."""
        return self._map(x, xp, True)

    def invert(self, x, xp=np):
        """h^-1(x): stored label -> input label."""
        return self._map(x, xp, False)


def rmat_edges_counter(scale: int, edge_factor: int = 16, a: float = 0.57,
                       b: float = 0.19, c: float = 0.19, seed: int = 1,
                       start: int = 0, count: int | None = None,
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Edges [start, start+count) of the counter-based R-MAT stream of
    m_input = edge_factor * 2**scale edges, as int64 (src, dst).

    The slice is a pure function of (scale, ef, a, b, c, seed, start,
    count): generating the full stream in one call or in any shard
    split yields bit-identical edges."""
    m_input = edge_factor << scale
    if count is None:
        count = m_input - start
    if not 0 <= start <= start + count <= m_input:
        raise ValueError(f"slice [{start}, {start + count}) outside the "
                         f"{m_input}-edge stream")
    t1, t2, t3 = rmat_thresholds(a, b, c)
    idx = (np.arange(count, dtype=np.uint32)
           + np.uint32(start & _M32))          # counter mod 2**32
    src = np.zeros(count, dtype=np.int64)
    dst = np.zeros(count, dtype=np.int64)
    for level in range(scale):
        u = _counter_u32_np(idx, level_salt(seed, level))
        src_bit = u >= np.uint32(t2)
        dst_bit = ((u >= np.uint32(t1)) & (u < np.uint32(t2))) \
            | (u >= np.uint32(t3))
        src |= src_bit.astype(np.int64) << level
        dst |= dst_bit.astype(np.int64) << level
    return src, dst


@dataclass(frozen=True)
class EdgeList:
    n: int
    src: np.ndarray  # int64[m]
    dst: np.ndarray  # int64[m]
    m_input: int     # edge count *before* dedup/symmetrize (TEPS denominator)

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    def out_degrees(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n).astype(np.int64)


def rmat_edges(scale: int, edge_factor: int = 16, a: float = 0.57,
               b: float = 0.19, c: float = 0.19, seed: int = 1,
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized R-MAT: returns (src, dst) int64 arrays of 2**scale*ef edges."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edge_factor * n
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab = a + b
    d = 1.0 - a - b - c
    # P(dst_bit=1 | src_bit=0) = b/(a+b);  P(dst_bit=1 | src_bit=1) = d/(c+d)
    p_dst_given0 = b / ab
    p_dst_given1 = d / (c + d)
    for level in range(scale):
        r1 = rng.random(m)
        r2 = rng.random(m)
        src_bit = r1 >= ab
        dst_bit = np.where(src_bit, r2 < p_dst_given1, r2 < p_dst_given0)
        src |= src_bit.astype(np.int64) << level
        dst |= dst_bit.astype(np.int64) << level
    return src, dst


def rmat_edges_counter_jax(scale: int, count: int, start,
                           edge_factor: int = 16, a: float = 0.57,
                           b: float = 0.19, c: float = 0.19, seed: int = 1):
    """jnp twin of ``rmat_edges_counter``: (src, dst) int32 arrays of
    ``count`` edges starting at traced/static ``start``.  Pure uint32
    wrapping arithmetic — bit-identical to the numpy twin — and safe
    under disabled x64 (scale <= 30 fits int32).  This is the per-shard
    generator the distributed build maps over devices."""
    import jax.numpy as jnp
    if scale > 30:
        raise ValueError(f"scale={scale} > 30 overflows int32 vertex ids "
                         f"on x64-disabled devices")
    t1, t2, t3 = rmat_thresholds(a, b, c)
    idx = (jnp.arange(count, dtype=jnp.uint32)
           + jnp.asarray(start, jnp.uint32))
    src = jnp.zeros(count, dtype=jnp.int32)
    dst = jnp.zeros(count, dtype=jnp.int32)
    for level in range(scale):
        x = (idx * jnp.uint32(_GOLDEN)) ^ jnp.uint32(level_salt(seed, level))
        x ^= x >> jnp.uint32(16)
        x = x * jnp.uint32(0x7FEB352D)
        x ^= x >> jnp.uint32(15)
        x = x * jnp.uint32(0x846CA68B)
        x ^= x >> jnp.uint32(16)
        src_bit = x >= jnp.uint32(t2)
        dst_bit = ((x >= jnp.uint32(t1)) & (x < jnp.uint32(t2))) \
            | (x >= jnp.uint32(t3))
        src = src | (src_bit.astype(jnp.int32) << level)
        dst = dst | (dst_bit.astype(jnp.int32) << level)
    return src, dst


def rmat_edges_counter_kernel(scale: int, count: int, start,
                              edge_factor: int = 16, a: float = 0.57,
                              b: float = 0.19, c: float = 0.19,
                              seed: int = 1, tile: int = 4096, *,
                              interpret: bool):
    """Pallas build of the per-shard counter generator: a grid program
    over ``tile``-edge blocks, each an independent VPU-width batch of
    uint32 mixing (no cross-tile state — the whole point of the
    counter RNG).  Bit-identical to the jnp/numpy twins.  ``interpret``
    selects the Pallas interpreter (CPU) or a Mosaic compile (TPU).

    The TPU core PRNG (pltpu.prng_random_bits) is deliberately NOT used:
    its stream depends on how work is split over cores, which would
    break the shard-count-independence contract."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if scale > 30:
        raise ValueError(f"scale={scale} > 30 overflows int32 vertex ids")
    if count % tile:
        tile = count if count < tile else \
            next(t for t in range(tile, 0, -1) if count % t == 0)
    t1, t2, t3 = rmat_thresholds(a, b, c)
    salts = tuple(level_salt(seed, lv) for lv in range(scale))

    def kernel(start_ref, src_ref, dst_ref):
        pid = pl.program_id(0)
        base = start_ref[0] + (pid * tile).astype(jnp.uint32)
        idx = jnp.arange(tile, dtype=jnp.uint32) + base
        s = jnp.zeros(tile, dtype=jnp.int32)
        d = jnp.zeros(tile, dtype=jnp.int32)
        for level in range(scale):
            x = (idx * jnp.uint32(_GOLDEN)) ^ jnp.uint32(salts[level])
            x ^= x >> jnp.uint32(16)
            x = x * jnp.uint32(0x7FEB352D)
            x ^= x >> jnp.uint32(15)
            x = x * jnp.uint32(0x846CA68B)
            x ^= x >> jnp.uint32(16)
            sb = (x >= jnp.uint32(t2)).astype(jnp.int32)
            db = (((x >= jnp.uint32(t1)) & (x < jnp.uint32(t2)))
                  | (x >= jnp.uint32(t3))).astype(jnp.int32)
            s = s | (sb << level)
            d = d | (db << level)
        src_ref[...] = s
        dst_ref[...] = d

    start = jnp.asarray(start, jnp.uint32).reshape(1)
    out = jax.ShapeDtypeStruct((count,), jnp.int32)
    src, dst = pl.pallas_call(
        kernel,
        grid=(count // tile,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],  # start scalar
        out_specs=[pl.BlockSpec((tile,), lambda i: (i,))] * 2,
        out_shape=[out, out],
        interpret=interpret,
    )(start)
    return src, dst


def relabel_edges(edges: EdgeList, relabel: VertexRelabel) -> EdgeList:
    """``edges`` with both endpoints mapped through ``relabel``."""
    if 1 << relabel.scale != edges.n:
        raise ValueError(f"relabel of 2**{relabel.scale} ids does not fit "
                         f"a graph of {edges.n} vertices")
    return EdgeList(n=edges.n, src=relabel.apply(edges.src),
                    dst=relabel.apply(edges.dst), m_input=edges.m_input)


def preprocess(src: np.ndarray, dst: np.ndarray, n: int,
               symmetrize: bool = True) -> EdgeList:
    """Prune self-loops + duplicates; optionally symmetrize (undirected)."""
    m_input = int(src.shape[0])
    keep = src != dst
    src, dst = src[keep], dst[keep]
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    key = src * np.int64(n) + dst
    _, idx = np.unique(key, return_index=True)
    return EdgeList(n=n, src=src[idx], dst=dst[idx], m_input=m_input)


def rmat_graph(scale: int, edge_factor: int = 16, seed: int = 1,
               a: float = 0.57, b: float = 0.19, c: float = 0.19,
               generator: str = "numpy") -> EdgeList:
    """Host-side generate + preprocess.  ``generator="numpy"`` is the
    original sequential-RNG stream (every pinned graph in the repo);
    ``generator="counter"`` draws the stateless counter stream — the
    SAME edges the distributed device build generates, so host-built and
    device-built graphs at one (scale, ef, seed) are comparable
    bit-for-bit."""
    if generator == "numpy":
        src, dst = rmat_edges(scale, edge_factor, a, b, c, seed)
    elif generator == "counter":
        src, dst = rmat_edges_counter(scale, edge_factor, a, b, c, seed)
    else:
        raise ValueError(f"unknown generator {generator!r} "
                         f"(have 'numpy', 'counter')")
    return preprocess(src, dst, 1 << scale)


def scale_free_standin(n: int, m_target: int, seed: int = 7) -> EdgeList:
    """Synthetic scale-free graph used as the Twitter-dataset standin
    (container is offline).  Preferential-attachment-flavored R-MAT with a
    heavier hub parameter, matching Twitter's skew qualitatively."""
    scale = int(np.ceil(np.log2(max(n, 2))))
    ef = max(1, m_target // (1 << scale))
    src, dst = rmat_edges(scale, ef, a=0.65, b=0.15, c=0.15, seed=seed)
    return preprocess(src, dst, 1 << scale)


def random_source(edges: EdgeList, rng: np.random.Generator) -> int:
    """A random root with at least one edge (Graph500 requirement)."""
    deg = edges.out_degrees()
    candidates = np.flatnonzero(deg > 0)
    return int(rng.choice(candidates))
