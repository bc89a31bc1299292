"""LocalOps: the pluggable local-discovery layer behind both BFS
decompositions.

The paper's §5.1 axis — which *local* data structure (CSR vs DCSC) backs
the per-processor SpMSV — is orthogonal to the decomposition (1D strips
vs 2D blocks), but the drivers used to hard-code it as string checks and
shipping-key tuples spread across core/bfs.py, core/steps.py,
core/steps_1d.py and graph/formats.py (and the 1D path rejected
everything but dense).  This module makes the axis explicit: a
``LocalOps`` entry, registered under ``(decomposition, local_mode,
storage)``, declares

  * ``keys``           — which graph device arrays the driver ships
  * ``topdown``        — the SpMSV closure (frontier -> candidate parents)
  * ``bottomup``       — the unvisited-row scan closure (one sub-step)
  * ``storage_words``  — the §5.1 word-accounting model for the format

The plan layer (``core/engine.py``) looks the entry up once at plan
time and threads it through LevelArgs via the Decomposition entry's
``make_level_args`` (``core/decomp.py``); the step modules just call
the closures.  Registered combos (Fig. 6 grid):

  2d  x {dense, kernel} x {csr, dcsc}  (dense ignores pointer storage)
  1d  x {dense, kernel} x {csr, dcsc}  (kernel/dcsc = the Pallas strip
                                        SpMSV over doubly compressed
                                        global source columns)
  1ds x {dense, kernel} x {csr, dcsc}  (mirrors the 1d entries: the
                                        sparse-exchange decomposition
                                        changes the expand collective,
                                        not local discovery)

Closure signatures (all arrays squeezed to the local block/strip):

  topdown(g, f_words, f_mask, nr, col_offset, args)
      -> (cand (nr,) i32 candidate parents, edges_examined_local f32)
  bottomup(rp_seg, ue_win, f_words, cvec, col_offset, n_edges, ve_win,
           args)
      -> (chunk,) i32 newly discovered parents (INT_INF = none)

``ve_win`` is each window edge's row: the dense steps read it from the
shipped CSR ``edge_dst`` (rebased to the segment in 2d); entries that
ship no ``edge_dst`` get None, and the Pallas scan finds rows itself.
``f_words`` is the packed frontier bitmap over the block's column range
(uint32 words), ``f_mask`` its unpacked bool form; 2D passes the C_j
slice with col_offset = j*nc, 1D passes the full allgathered frontier
with col_offset = 0 (strip ids are global).  ``args`` is the LevelArgs /
LevelArgs1D NamedTuple (cap_f, maxdeg statics, and ``interpret``: the
plan runs the Pallas kernels in the interpreter only on a CPU mesh).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Dict, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class LocalOps:
    decomposition: str            # "1d" | "2d"
    local_mode: str               # "dense" | "kernel"
    storage: str                  # "csr" | "dcsc"
    keys: Tuple[str, ...]         # graph device arrays to ship
    topdown: Callable             # SpMSV closure (see module docstring)
    bottomup: Callable            # bottom-up sub-step closure
    storage_words: Callable       # (graph) -> Dict[str, int], §5.1 words
    # Optional per-chunk SpMSV for the software-pipelined 1d/1ds expand
    # (expand_chunks > 1): consumes ONE raw gathered sub-chunk buffer
    # (owner-major (p * w_sub,) u32 words) without materializing the
    # full-size frontier bitmap.  Signature:
    #   topdown_chunk(g, g_sub, k, n_chunks, nr, col_offset, args)
    #       -> (cand (nr,) i32, edges_examined_local f32)
    # Entries without one fall back to scattering the sub-chunk into a
    # full-size partial bitmap and calling ``topdown`` (exact either
    # way: candidates min-combine across chunks).
    topdown_chunk: Callable = None


_REGISTRY: Dict[Tuple[str, str, str], LocalOps] = {}


def register_local_ops(ops: LocalOps) -> LocalOps:
    key = (ops.decomposition, ops.local_mode, ops.storage)
    if key in _REGISTRY:
        raise ValueError(f"duplicate LocalOps {key}")
    _REGISTRY[key] = ops
    return ops


def get_local_ops(decomposition: str, local_mode: str,
                  storage: str) -> LocalOps:
    key = (decomposition, local_mode, storage)
    if key not in _REGISTRY:
        raise ValueError(
            f"no LocalOps registered for {key}; have "
            f"{sorted(_REGISTRY)}")
    return _REGISTRY[key]


def registered_combos() -> Tuple[Tuple[str, str, str], ...]:
    return tuple(sorted(_REGISTRY))


def unregister_local_ops(decomposition: str, local_mode: str,
                         storage: str) -> None:
    """Remove an entry — for scoped test/fixture registrations only
    (mirrors decomp.unregister_decomposition)."""
    key = (decomposition, local_mode, storage)
    if key not in _REGISTRY:
        raise ValueError(f"no LocalOps registered for {key}")
    del _REGISTRY[key]


# ---------------------------------------------------------------------------
# Top-down SpMSV closures
# ---------------------------------------------------------------------------


def _td_dense(g, f_words, f_mask, nr, col_offset, args):
    """Edge-parallel dense scan over the whole block/strip (oracle path):
    work O(nnz) regardless of frontier size."""
    from repro.kernels.spmsv.ref import spmsv_dense
    cand = spmsv_dense(g["edge_src"], g["row_idx"], g["nnz"], f_mask, nr,
                       col_offset)
    ex = jnp.sum(jnp.arange(g["edge_src"].shape[0]) < g["nnz"],
                 dtype=jnp.float32)
    return cand, ex


def _td_kernel_csr(g, f_words, f_mask, nr, col_offset, args):
    """Pallas ragged gather through the uncompressed col_ptr — O(n)
    pointer words per block column range (strip: per processor).  The
    cap_f=0 fallback covers the whole column range, so in 1D the gather
    scratch is O(n * maxdeg) per strip — the deliberately unscalable
    Fig. 6 comparison cell; pass cap_f (a bound the frontier never
    exceeds: larger frontiers are silently truncated) to shrink it."""
    from repro.kernels.spmsv import ops as spmsv_ops
    cap_f = args.cap_f or f_mask.shape[0]
    cand = spmsv_ops.spmsv_block_csr(g["col_ptr"], g["row_idx"], f_mask, nr,
                                     col_offset, cap_f=cap_f,
                                     maxdeg=args.maxdeg,
                                     interpret=args.interpret)
    ex = jnp.sum(jnp.where(f_mask, g["col_ptr"][1:] - g["col_ptr"][:-1], 0),
                 dtype=jnp.float32)
    return cand, ex


def _dcsc_edges_examined(jc, cp, nzc, f_mask):
    """Sum of frontier-column segment lengths straight off the compressed
    pointers (padded slots have zero-length segments)."""
    nc = f_mask.shape[0]
    slot = jnp.arange(jc.shape[0])
    live = (slot < nzc) & (jc < nc) & f_mask[jnp.minimum(jc, nc - 1)]
    return jnp.sum(jnp.where(live, cp[1:] - cp[:-1], 0), dtype=jnp.float32)


def _td_kernel_dcsc_2d(g, f_words, f_mask, nr, col_offset, args):
    """Pallas gather through (JC, CP) with the per-frontier-vertex binary
    search — the paper's hypersparse indirection cost, Fig. 6."""
    from repro.kernels.spmsv import ops as spmsv_ops
    cap_f = args.cap_f or f_mask.shape[0]
    cand = spmsv_ops.spmsv_block_dcsc(g["jc"], g["cp"], g["nzc"],
                                      g["row_idx"], f_mask, nr, col_offset,
                                      cap_f=cap_f, maxdeg=args.maxdeg,
                                      interpret=args.interpret)
    return cand, _dcsc_edges_examined(g["jc"], g["cp"], g["nzc"], f_mask)


def _td_strip_dcsc(g, f_words, f_mask, nr, col_offset, args):
    """The 1D strip SpMSV: walk the strip's non-empty GLOBAL columns
    against the allgathered frontier bitmap (kernels/spmsv/strip.py) —
    no O(n) pointer array and no per-frontier-vertex search."""
    from repro.kernels.spmsv import ops as spmsv_ops
    cand = spmsv_ops.spmsv_strip_dcsc(g["jc"], g["cp"], g["nzc"],
                                      g["row_idx"], f_words, nr,
                                      maxdeg=args.maxdeg,
                                      interpret=args.interpret)
    return cand, _dcsc_edges_examined(g["jc"], g["cp"], g["nzc"], f_mask)


def _td_strip_dcsc_chunk(g, g_sub, k, n_chunks, nr, col_offset, args):
    """Per-chunk entry of the strip SpMSV for the software-pipelined
    expand: the gather consumes the raw gathered sub-chunk buffer
    directly (kernels/spmsv/strip.py chunk entry point); the caller
    min-combines candidates across chunks.  The edges-examined sum tests
    the same per-chunk column liveness, so the per-chunk sums add up
    exactly to the unchunked ``_dcsc_edges_examined``."""
    from repro.kernels.spmsv import ops as spmsv_ops
    from repro.kernels.spmsv.strip import strip_live_columns_chunk
    part = args.part
    jc, cp = g["jc"], g["cp"]
    cand = spmsv_ops.spmsv_strip_dcsc_chunk(
        jc, cp, g["nzc"], g["row_idx"], g_sub, nr, n=part.n, p=part.p,
        k=k, n_chunks=n_chunks, maxdeg=args.maxdeg,
        interpret=args.interpret)
    live = strip_live_columns_chunk(jc, g["nzc"], g_sub, n=part.n, p=part.p,
                                    k=k, n_chunks=n_chunks)
    ex = jnp.sum(jnp.where(live, cp[1:] - cp[:-1], 0), dtype=jnp.float32)
    return cand, ex


# ---------------------------------------------------------------------------
# Bottom-up sub-step closures
# ---------------------------------------------------------------------------


def _bu_ref(rp_seg, ue_win, f_words, cvec, col_offset, n_edges, ve_win,
            args):
    from repro.kernels.bottomup.ref import bottomup_substep
    return bottomup_substep(rp_seg, ue_win, f_words, cvec, col_offset,
                            n_edges, ve_win=ve_win)


def _bu_kernel(rp_seg, ue_win, f_words, cvec, col_offset, n_edges, ve_win,
               args):
    """Pallas tile-granular early-exit scan; per-edge rows come from the
    CSR pointers inside the kernel, so ve_win is unused."""
    from repro.kernels.bottomup import ops as bu_ops
    chunk = rp_seg.shape[0] - 1
    return bu_ops.bottomup_substep(rp_seg, ue_win, f_words, cvec, col_offset,
                                   n_edges, rt=min(128, chunk),
                                   interpret=args.interpret)


# ---------------------------------------------------------------------------
# Registry entries
# ---------------------------------------------------------------------------

_DENSE_KEYS_2D = ("edge_src", "row_idx", "nnz", "deg_A", "col_idx",
                  "row_ptr", "seg_ptr", "edge_dst")
_KERNEL_CSR_KEYS_2D = ("col_ptr", "row_idx", "nnz", "deg_A", "col_idx",
                       "row_ptr", "seg_ptr")
_KERNEL_DCSC_KEYS_2D = ("jc", "cp", "nzc", "row_idx", "nnz", "deg_A",
                        "col_idx", "row_ptr", "seg_ptr")
_DENSE_KEYS_1D = ("edge_src", "row_idx", "nnz", "deg_A", "col_idx",
                  "row_ptr", "edge_dst")
_KERNEL_CSR_KEYS_1D = ("col_ptr", "row_idx", "nnz", "deg_A", "col_idx",
                       "row_ptr")
_KERNEL_DCSC_KEYS_1D = ("jc", "cp", "nzc", "row_idx", "nnz", "deg_A",
                        "col_idx", "row_ptr")


def _words(mode):
    return lambda graph: graph.storage_words(mode)


for _storage in ("csr", "dcsc"):
    # dense local discovery reads per-edge arrays only — no pointer
    # arrays shipped, but the storage model still reports the mode the
    # caller would pay for on a real deployment
    register_local_ops(LocalOps(
        decomposition="2d", local_mode="dense", storage=_storage,
        keys=_DENSE_KEYS_2D, topdown=_td_dense, bottomup=_bu_ref,
        storage_words=_words(_storage)))
    register_local_ops(LocalOps(
        decomposition="1d", local_mode="dense", storage=_storage,
        keys=_DENSE_KEYS_1D, topdown=_td_dense, bottomup=_bu_ref,
        storage_words=_words(_storage)))

register_local_ops(LocalOps(
    decomposition="2d", local_mode="kernel", storage="csr",
    keys=_KERNEL_CSR_KEYS_2D, topdown=_td_kernel_csr, bottomup=_bu_kernel,
    storage_words=_words("csr")))
register_local_ops(LocalOps(
    decomposition="2d", local_mode="kernel", storage="dcsc",
    keys=_KERNEL_DCSC_KEYS_2D, topdown=_td_kernel_dcsc_2d,
    bottomup=_bu_kernel, storage_words=_words("dcsc")))
register_local_ops(LocalOps(
    decomposition="1d", local_mode="kernel", storage="csr",
    keys=_KERNEL_CSR_KEYS_1D, topdown=_td_kernel_csr, bottomup=_bu_kernel,
    storage_words=_words("csr")))
register_local_ops(LocalOps(
    decomposition="1d", local_mode="kernel", storage="dcsc",
    keys=_KERNEL_DCSC_KEYS_1D, topdown=_td_strip_dcsc, bottomup=_bu_kernel,
    storage_words=_words("dcsc"), topdown_chunk=_td_strip_dcsc_chunk))

# "1ds" (sparse-exchange 1D, core/steps_1d_sparse.py) traverses the same
# row strips with the same local kernels — only the expand collective
# differs — so its LocalOps entries mirror "1d" exactly.
for _combo in [k for k in sorted(_REGISTRY) if k[0] == "1d"]:
    register_local_ops(dataclasses.replace(_REGISTRY[_combo],
                                           decomposition="1ds"))
