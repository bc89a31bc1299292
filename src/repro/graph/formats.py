"""Blocked 2D graph storage: CSR/CSC per block + DCSC/DCSR compressions.

The adjacency block at device (i,j) is T[R_i, C_j], T[v,u]=1 iff edge u->v
(pre-transposed, paper §4.1).  Two orientations are stored, as the paper
stores each undirected adjacency twice (§5.1):

  * CSC-by-source-column  -> top-down SpMSV   (frontier u -> children v)
  * CSR-by-dest-row       -> bottom-up scan   (unvisited v -> parents u)

DCSC (doubly compressed sparse columns, Buluc & Gilbert) compresses the
O(n*pr) aggregate col_ptr down to O(nnz-columns); DCSR does the same for
rows.  Both share the index arrays with their uncompressed counterparts,
so a ``storage`` mode only changes which *pointer* arrays are shipped.

All arrays are statically padded to per-block capacity ``cap`` (XLA needs
static shapes); ``nnz[(i,j)]`` masks the tail.  ``edge_src``/``edge_dst``
are explicit per-edge locals for the edge-parallel jnp path (the Pallas
kernels use the pointer arrays instead).

Strip DCSC and the §5.1 storage charge against 1D
-------------------------------------------------
The paper's §5.1 argument for 2D is a *storage* argument against 1D: a
1D row strip T[V_i, :] spans all n source columns, so an uncompressed
CSC col_ptr costs n+1 words on EVERY processor — O(n*p) aggregate, vs
O(n*(pr+pc)) for the 2D blocks — which is why this repo's 1D path was
edge-parallel dense-only at first.  Strip DCSC answers that charge the
same way DCSC answers it for hypersparse 2D blocks: ``Blocked1DGraph``
stores, per strip, only the non-empty *global* source columns ``jc``
with pointers ``cp`` into the CSC-ordered ``row_idx`` — O(nzc) <= O(m/p)
words per strip, independent of n.  Because the ids in ``jc`` are
global, the strip SpMSV (kernels/spmsv/strip.py) tests them directly
against the allgathered frontier bitmap with col_offset = 0; no O(n)
pointer array is ever materialized on the device.  The uncompressed
strip ``col_ptr`` can be built host-side on request
(``with_col_ptr=True``, opt-in) so benchmarks can *measure* the
blow-up Fig. 6-style via ``storage_words("csr")`` vs
``storage_words("dcsc")``.
"""
from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.core.partition import (Partition1D, Partition2D, make_partition,
                                  make_partition_1d)
from repro.graph.rmat import EdgeList, VertexRelabel


def _round_up(x: int, q: int) -> int:
    return ((x + q - 1) // q) * q


@dataclass
class BlockedGraph:
    part: Partition2D
    m_input: int
    m: int
    # --- top-down orientation (CSC by source column u) ---
    col_ptr: np.ndarray   # (pr, pc, nc+1) i32
    row_idx: np.ndarray   # (pr, pc, cap)  i32  local dest v, CSC order
    edge_src: np.ndarray  # (pr, pc, cap)  i32  local src u, CSC order
    # --- bottom-up orientation (CSR by dest row v) ---
    row_ptr: np.ndarray   # (pr, pc, nr+1) i32
    col_idx: np.ndarray   # (pr, pc, cap)  i32  local src u, CSR order
    edge_dst: np.ndarray  # (pr, pc, cap)  i32  local dest v, CSR order
    seg_ptr: np.ndarray   # (pr, pc, pc+1) i32  CSR ptr at chunk-segment bounds
    # --- hypersparse pointer compressions ---
    jc: np.ndarray        # (pr, pc, cap_nzc)   i32 non-empty source cols
    cp: np.ndarray        # (pr, pc, cap_nzc+1) i32 ptrs into row_idx
    jr: np.ndarray        # (pr, pc, cap_nzr)   i32 non-empty dest rows
    rp: np.ndarray        # (pr, pc, cap_nzr+1) i32 ptrs into col_idx
    # --- per-block / per-vertex metadata ---
    nnz: np.ndarray       # (pr, pc) i32
    nzc: np.ndarray       # (pr, pc) i32
    nzr: np.ndarray       # (pr, pc) i32
    deg_A: np.ndarray     # (pr, pc, chunk) i32 out-degree, layout-A chunks
    cap: int
    cap_seg: int
    maxdeg_col: int       # max CSC column-segment length over all blocks
    # the bijection the vertex ids were relabeled by before partitioning
    # (None: stored under the input labels); the search maps through it
    relabel: Optional[VertexRelabel] = None

    # ------------------------------------------------------------------
    def device_arrays(self) -> Dict[str, Any]:
        """The pytree of arrays shipped to devices (everything but
        part/ints).  Fields may be host np.ndarrays (host-built graphs)
        or already-sharded jax.Arrays (born-sharded device builds /
        store loads) — the engine ships the former and passes the
        latter through without a host round-trip."""
        out = {}
        for f in fields(self):
            v = getattr(self, f.name)
            if isinstance(v, (np.ndarray, jax.Array)):
                out[f.name] = v
        return out

    def storage_words(self, mode: str) -> Dict[str, int]:
        """64-bit-word accounting per §5.1 (we store i32 => 0.5 words each,
        reported in raw index units for clarity)."""
        p = self.part.p
        idx = 2 * self.cap * p                       # row_idx + col_idx
        if mode == "csr":
            ptr = (self.part.nc + 1 + self.part.nr + 1) * p
        elif mode == "dcsc":
            ptr = int(2 * (self.nzc.sum() + self.nzr.sum()) + 2 * p)
        else:
            raise ValueError(mode)
        return {"index_i32": idx, "pointer_i32": int(ptr),
                "total_i32": idx + int(ptr)}


@dataclass
class Blocked1DGraph:
    """1D row-strip storage: processor i holds T[V_i, :] (all edges into
    its owned vertices), in both orientations.

    Unlike the 2D format, source-column indices are *global* ids (the
    strip spans every column), so the top-down SpMSV and bottom-up scan
    run with ``col_offset = 0`` against the full allgathered frontier.
    Two top-down pointer compressions are available per strip:

      * strip CSC ``col_ptr`` (n+1 words/processor) — the O(n) aggregate
        blow-up the paper's §5.1 charges against 1D; kept so the charge
        can be *measured* (``storage_words("csr")``)
      * strip DCSC ``(jc, cp)`` over non-empty global source columns —
        O(nzc) words, the compression that makes 1D compressed formats
        viable (``storage_words("dcsc")``, kernels/spmsv/strip.py)

    The dense edge-parallel path (``edge_src``/``row_idx``) needs no
    pointers at all.
    """
    part: Partition1D
    m_input: int
    m: int
    # --- top-down orientation (edges sorted by source col u) ---
    edge_src: np.ndarray  # (p, cap) i32 GLOBAL source u
    row_idx: np.ndarray   # (p, cap) i32 local dest v
    # --- bottom-up orientation (CSR by dest row v) ---
    row_ptr: np.ndarray   # (p, chunk+1) i32
    col_idx: np.ndarray   # (p, cap) i32 GLOBAL source u, CSR order
    edge_dst: np.ndarray  # (p, cap) i32 local dest v, CSR order
    # --- strip DCSC (top-down pointer compression) ---
    jc: np.ndarray        # (p, cap_nzc)   i32 non-empty GLOBAL source cols
    cp: np.ndarray        # (p, cap_nzc+1) i32 ptrs into row_idx
    # --- per-block / per-vertex metadata ---
    nnz: np.ndarray       # (p,) i32
    nzc: np.ndarray       # (p,) i32 non-empty columns per strip
    deg_A: np.ndarray     # (p, chunk) i32 out-degree of owned vertices
    cap: int
    cap_nzc: int
    maxdeg_col: int       # max CSC column-segment length over all strips
    col_ptr: "np.ndarray | None" = None   # (p, n+1) i32, the §5.1 blow-up
    relabel: Optional[VertexRelabel] = None   # as in BlockedGraph

    def device_arrays(self) -> Dict[str, Any]:
        out = {}
        for f in fields(self):
            v = getattr(self, f.name)
            if isinstance(v, (np.ndarray, jax.Array)):
                out[f.name] = v
        return out

    def storage_words(self, mode: str) -> Dict[str, int]:
        """i32 accounting mirroring BlockedGraph.storage_words(mode).
        Index arrays are mode-independent; "csr" charges the (n+1)-per-
        strip top-down col_ptr (the §5.1 1D blow-up) while "dcsc"
        charges 2*nzc+2 per strip — the strip-DCSC pointer savings.
        Both include the (chunk+1)-per-strip bottom-up row_ptr."""
        p = self.part.p
        idx = 2 * self.cap * p
        bu_ptr = (self.part.chunk + 1) * p
        if mode == "csr":
            ptr = (self.part.n + 1) * p + bu_ptr
        elif mode == "dcsc":
            ptr = int(2 * self.nzc.sum()) + 2 * p + bu_ptr
        else:
            raise ValueError(mode)
        return {"index_i32": idx, "pointer_i32": int(ptr),
                "total_i32": idx + int(ptr)}


def input_degrees(graph) -> np.ndarray:
    """(n_orig,) out-degree of every vertex by its input label, on host:
    ``deg_A`` is indexed by the stored ids, relabeled or not."""
    deg = np.asarray(graph.deg_A).reshape(-1)[: graph.part.n_orig]
    if graph.relabel is None:
        return deg
    return deg[graph.relabel.apply(np.arange(graph.part.n_orig))]


def build_blocked_1d(edges: EdgeList, p: int, align: int = 128,
                     cap_pad: int = 128,
                     with_col_ptr: bool = False) -> Blocked1DGraph:
    """Partition edges u->v by owner of the *destination* v into p row
    strips; pad every strip to a common static capacity.

    ``with_col_ptr=True`` additionally materializes the (p, n+1)
    uncompressed strip CSC pointer — O(n*p) host words, wanted ONLY by
    the local_mode="kernel" / storage="csr" comparison cell of Fig. 6
    (measuring that blow-up is its purpose); dense and strip-DCSC runs
    never ship it, so it is opt-in."""
    part = make_partition_1d(edges.n, p, align)
    chunk = part.chunk
    u, v = edges.src.astype(np.int64), edges.dst.astype(np.int64)
    blk = v // chunk
    v_loc = v - blk * chunk

    nnz = np.bincount(blk, minlength=p).astype(np.int64)
    cap = _round_up(max(int(nnz.max()), 1), cap_pad)

    def _orient(primary, secondary):
        """Sort by (block, primary, secondary), return padded per-block
        (primary, secondary) arrays."""
        order = np.lexsort((secondary, primary, blk))
        pb, pp, ps = blk[order], primary[order], secondary[order]
        pri = np.zeros((p, cap), dtype=np.int64)
        sec = np.zeros((p, cap), dtype=np.int64)
        starts = np.concatenate([[0], np.cumsum(nnz)])
        for b in range(p):
            k = int(nnz[b])
            pri[b, :k] = pp[starts[b]:starts[b] + k]
            sec[b, :k] = ps[starts[b]:starts[b] + k]
        return pri, sec

    # top-down orientation: sorted by global source u
    edge_src, row_idx = _orient(u, v_loc)
    # bottom-up orientation: CSR by local dest row v
    edge_dst_, col_idx_ = _orient(v_loc, u)
    row_ptr = np.zeros((p, chunk + 1), dtype=np.int64)
    flat = blk * np.int64(chunk) + v_loc
    cnt = np.bincount(flat, minlength=p * chunk).reshape(p, chunk)
    row_ptr[:, 1:] = np.cumsum(cnt, axis=1)

    # strip DCSC: doubly compressed (jc, cp) over the strip's non-empty
    # GLOBAL source columns.  edge_src rows are sorted by u, so unique's
    # first-occurrence indices are exactly the CSC segment starts.
    nzc = np.zeros(p, dtype=np.int64)
    uniq = []
    maxdeg_col = 0
    for b in range(p):
        k = int(nnz[b])
        uu, first = np.unique(edge_src[b, :k], return_index=True)
        if k == 0:
            uu, first = uu[:0], first[:0]
        uniq.append((uu, first))
        nzc[b] = uu.size
        if uu.size:
            maxdeg_col = max(maxdeg_col,
                             int(np.diff(np.append(first, k)).max()))
    cap_nzc = _round_up(max(int(nzc.max()), 1), 8)
    jc = np.full((p, cap_nzc), part.n, dtype=np.int64)       # sentinel = n
    cp = np.zeros((p, cap_nzc + 1), dtype=np.int64)
    for b in range(p):
        uu, first = uniq[b]
        jc[b, :uu.size] = uu
        cp[b, :uu.size] = first
        cp[b, uu.size:] = int(nnz[b])

    col_ptr = None
    if with_col_ptr:
        # the uncompressed strip CSC pointer — (n+1) words per strip,
        # materialized on request so the §5.1 blow-up is measurable
        cnt = np.bincount(blk * np.int64(part.n) + u,
                          minlength=p * part.n).reshape(p, part.n)
        col_ptr = np.zeros((p, part.n + 1), dtype=np.int64)
        col_ptr[:, 1:] = np.cumsum(cnt, axis=1)

    deg = np.bincount(u, minlength=part.n).astype(np.int64)

    def _i32(x):
        return np.ascontiguousarray(x.astype(np.int32))

    return Blocked1DGraph(
        part=part, m_input=edges.m_input, m=edges.m,
        edge_src=_i32(edge_src), row_idx=_i32(row_idx),
        row_ptr=_i32(row_ptr), col_idx=_i32(col_idx_),
        edge_dst=_i32(edge_dst_),
        jc=_i32(jc), cp=_i32(cp),
        nnz=_i32(nnz), nzc=_i32(nzc), deg_A=_i32(deg.reshape(p, chunk)),
        cap=cap, cap_nzc=cap_nzc, maxdeg_col=maxdeg_col,
        col_ptr=None if col_ptr is None else _i32(col_ptr),
    )


def build_blocked(edges: EdgeList, pr: int, pc: int, align: int = 128,
                  cap_pad: int = 128) -> BlockedGraph:
    part = make_partition(edges.n, pr, pc, align)
    nr, nc, chunk, p = part.nr, part.nc, part.chunk, part.p
    u, v = edges.src.astype(np.int64), edges.dst.astype(np.int64)
    bi = v // nr          # block row   (dest strip)
    bj = u // nc          # block col   (source strip)
    blk = bi * pc + bj
    u_loc = (u - bj * nc).astype(np.int64)
    v_loc = (v - bi * nr).astype(np.int64)

    nnz = np.bincount(blk, minlength=p).astype(np.int64)
    cap = _round_up(max(int(nnz.max()), 1), cap_pad)

    def _orient(primary, secondary, n_primary):
        """Sort edges by (block, primary, secondary); build padded per-block
        primary-ptr, secondary index array, explicit primary array."""
        order = np.lexsort((secondary, primary, blk))
        pb, pp, ps = blk[order], primary[order], secondary[order]
        ptr = np.zeros((p, n_primary + 1), dtype=np.int64)
        # counts of (block, primary)
        flat = pb * np.int64(n_primary) + pp
        cnt = np.bincount(flat, minlength=p * n_primary).reshape(p, n_primary)
        ptr[:, 1:] = np.cumsum(cnt, axis=1)
        sec = np.zeros((p, cap), dtype=np.int64)
        pri = np.zeros((p, cap), dtype=np.int64)
        starts = np.concatenate([[0], np.cumsum(nnz)])
        for b in range(p):
            k = int(nnz[b])
            sec[b, :k] = ps[starts[b]:starts[b] + k]
            pri[b, :k] = pp[starts[b]:starts[b] + k]
        return ptr, sec, pri, cnt

    # CSC: primary = source col u, secondary = dest row v
    col_ptr, row_idx, edge_src, col_cnt = _orient(u_loc, v_loc, nc)
    # CSR: primary = dest row v, secondary = source col u
    row_ptr, col_idx, edge_dst, row_cnt = _orient(v_loc, u_loc, nr)

    # DCSC / DCSR: compress pointer arrays over non-empty primaries
    def _compress(ptr, cnt, n_primary):
        nz_counts = (cnt > 0).sum(axis=1)
        cap_nz = _round_up(max(int(nz_counts.max()), 1), 8)
        jx = np.full((p, cap_nz), n_primary, dtype=np.int64)   # sentinel
        px = np.zeros((p, cap_nz + 1), dtype=np.int64)
        for b in range(p):
            nz = np.flatnonzero(cnt[b])
            jx[b, :nz.size] = nz
            px[b, :nz.size] = ptr[b, nz]
            px[b, nz.size:] = ptr[b, n_primary]
        return jx, px, nz_counts, cap_nz

    jc, cp, nzc, _ = _compress(col_ptr, col_cnt, nc)
    jr, rp, nzr, _ = _compress(row_ptr, row_cnt, nr)

    # CSR ptr at chunk-segment boundaries (bottom-up sub-step windows)
    seg_bounds = np.arange(pc + 1) * chunk
    seg_ptr = row_ptr[:, seg_bounds]
    cap_seg = int(np.diff(seg_ptr, axis=1).max())
    cap_seg = _round_up(max(cap_seg, 1), cap_pad)
    # pad the CSR-orientation index arrays so a cap_seg-wide dynamic slice
    # starting at any segment boundary stays in bounds
    tail = np.zeros((p, cap_seg), dtype=np.int64)
    col_idx = np.concatenate([col_idx, tail], axis=1)
    edge_dst = np.concatenate([edge_dst, tail], axis=1)

    deg = np.bincount(u, minlength=part.n).astype(np.int64)
    deg_A = deg.reshape(pr, pc, chunk)

    def _blk(x):  # (p, ...) -> (pr, pc, ...) int32
        return np.ascontiguousarray(x.reshape(pr, pc, *x.shape[1:]).astype(np.int32))

    return BlockedGraph(
        part=part, m_input=edges.m_input, m=edges.m,
        col_ptr=_blk(col_ptr), row_idx=_blk(row_idx), edge_src=_blk(edge_src),
        row_ptr=_blk(row_ptr), col_idx=_blk(col_idx), edge_dst=_blk(edge_dst),
        seg_ptr=_blk(seg_ptr),
        jc=_blk(jc), cp=_blk(cp), jr=_blk(jr), rp=_blk(rp),
        nnz=_blk(nnz), nzc=_blk(nzc), nzr=_blk(nzr),
        deg_A=deg_A.astype(np.int32),
        cap=cap, cap_seg=cap_seg, maxdeg_col=int(col_cnt.max()),
    )
