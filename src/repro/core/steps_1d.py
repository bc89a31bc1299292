"""Per-level BFS steps for the 1D row decomposition (the paper's Alg. 1/2
distributed baseline, Buluc & Madduri): shard_map bodies over ONE mesh
axis of size p.

Schedule per level:

  expand : pack the owned frontier chunk into a bitmap and allgather it
           along the single axis -> every processor holds the full
           n-vertex frontier.  This replaces BOTH the 2D transpose and
           fold phases (there is no second axis to exchange along), so
           the entire wire volume of a 1D level is the allgather.
  local  : top-down — SpMSV over the strip T[V_i, :] (select-source,
           min semiring) through the LocalOps entry (core/local_ops.py):
           edge-parallel dense, strip-CSR Pallas gather, or the
           strip-DCSC Pallas kernel over non-empty global columns
           (kernels/spmsv/strip.py); bottom-up — in-neighbor scan of
           unvisited owned rows.  Discovered children are *always
           locally owned* (the strip holds every edge into V_i), so the
           parent update is local and fold-free.

Counters share COUNTER_KEYS with the 2D steps (core/steps.py) so the
driver, benchmarks, and Eq. 2 comparisons treat both decompositions
uniformly; 1D leaves wire_transpose / wire_fold / wire_rotate /
wire_updates at zero by construction.  wire_expand per level is
(p-1) * n/64 global 64-bit words (dense bitmap, every chunk replicated
to the other p-1 processors) — the closed form in
``core.comm_model.expand_1d_words``.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import comm_model
from repro.core.frontier import INT_INF, pack_bits, unpack_bits
from repro.core.scopes import DISCOVER, EXPAND, FOLD, UPDATE
from repro.core.steps import zero_counters


class LevelArgs1D(NamedTuple):
    """Static/per-search context threaded into 1D level steps.  Local
    discovery goes through the LocalOps entry (core/local_ops.py) —
    dense edge-parallel, strip-CSR kernel, or the strip-DCSC Pallas
    kernel all plug in behind the same two closures."""
    part: "object"            # Partition1D (static)
    axis: str                 # the single mesh axis name
    local_mode: str = "dense"  # "dense" | "kernel" (Pallas)
    storage: str = "csr"      # "csr" | "dcsc" (strip pointer compression)
    cap_f: int = 0            # kernel csr: frontier capacity (0 = n)
    maxdeg: int = 0           # kernel mode: max column-segment length
    ops: "object" = None      # LocalOps entry (None = look up from strings)
    instrument: bool = True   # False: compile out the counters
    # software-pipelined expand: split the top-down allgather into this
    # many sub-chunk collectives, consuming sub-chunk k while k+1 is in
    # flight (1 = the classic single-gather schedule)
    expand_chunks: int = 1
    interpret: bool = False   # Pallas interpreter (CPU mesh) vs Mosaic


def _resolve_ops(args: "LevelArgs1D"):
    if args.ops is not None:
        return args.ops
    from repro.core.local_ops import get_local_ops
    return get_local_ops("1d", args.local_mode, args.storage)


def expand_frontier_1d(front: jax.Array, axis: str):
    """Allgather the packed frontier chunk along the single axis.

    Returns (f_words uint32[n//32], ctr-updates dict with the global
    wire/use expand words in paper 64-bit units)."""
    words = pack_bits(front)                         # (chunk//32,) u32
    gathered = lax.all_gather(words, axis, tiled=True)
    p = lax.axis_size(axis)
    # shared closed form (word-size conversion lives in comm_model, so
    # the measured counter and the model cannot drift): n = chunk * p
    wire = jnp.float32(comm_model.expand_1d_level_words(words.size * 32 * p, p))
    return gathered, wire


# ---------------------------------------------------------------------------
# Software-pipelined (chunked) expand
# ---------------------------------------------------------------------------
#
# With ``expand_chunks = C > 1`` the top-down expand splits each owner's
# packed strip words into C contiguous sub-chunks and runs C tiled
# allgathers, issuing sub-chunk k+1's gather BEFORE consuming sub-chunk
# k — the gathered sub-chunk feeds local discovery while the next
# collective is in flight (total bytes unchanged:
# ``comm_model.chunked_expand_1d_level_words``).  Exactness: every
# top-down closure resolves candidates by scatter-MIN of global source
# ids ((select-source, min) semiring), so per-sub-chunk partial SpMSV
# passes combine exactly via ``jnp.minimum``.  Bottom-up keeps the ONE
# dense allgather regardless of expand_chunks: its unvisited-row scan
# takes the FIRST frontier in-neighbor (not the min), so partial-bitmap
# passes would not combine exactly, and the heuristic only enters
# bottom-up on large frontiers where the single tiled gather is
# bandwidth- (not latency-) bound anyway.
#
# Gathered sub-chunk layout (both the dense gather and the 1ds sparse
# sub-bucket decode produce it): ``(p * w_sub,)`` u32 words, owner-major
# — owner i's words for LOCAL word range [k*w_sub, (k+1)*w_sub) sit at
# [i*w_sub, (i+1)*w_sub), i.e. sub-chunk k covers owner-local vertices
# [k*sub, (k+1)*sub) with sub = chunk/C.


def _consume_subchunk(g, g_k, k: int, n_chunks: int, args: "LevelArgs1D"):
    """Local discovery over ONE gathered sub-chunk -> (cand_k, ex_k).

    Entries with a chunk-aware kernel closure (``LocalOps.topdown_chunk``,
    e.g. the strip-DCSC Pallas kernel's per-chunk entry point) consume
    the raw owner-major sub-chunk words directly; everything else gets
    the sub-chunk scattered into a full-size partial frontier bitmap and
    goes through the ordinary ``topdown`` closure."""
    part = args.part
    ops = _resolve_ops(args)
    if getattr(ops, "topdown_chunk", None) is not None:
        return ops.topdown_chunk(g, g_k, k, n_chunks, part.chunk,
                                 jnp.int32(0), args)
    p = part.p
    w_sub = g_k.size // p
    fw_k = jnp.zeros((p, n_chunks, w_sub), jnp.uint32).at[:, k, :].set(
        g_k.reshape(p, w_sub)).reshape(-1)
    f_k = unpack_bits(fw_k)
    return ops.topdown(g, fw_k, f_k, part.chunk, jnp.int32(0), args)


def pipelined_expand_consume(g, sub_gather, n_chunks: int,
                             args: "LevelArgs1D"):
    """Run the C-step expand/discover software pipeline.

    ``sub_gather(k)`` issues the collective for sub-chunk k and returns
    the gathered owner-major words.  The gather for sub-chunk k+1 is
    issued before sub-chunk k is consumed, so the collective has no data
    dependency on the SpMSV below it and the two overlap.  Candidate
    parents min-combine across sub-chunks (exact under the
    (select-source, min) semiring); edges-examined sums."""
    cand = jnp.full((args.part.chunk,), INT_INF, jnp.int32)
    ex = jnp.float32(0.0)
    with jax.named_scope(EXPAND):
        nxt = sub_gather(0)
    for k in range(n_chunks):
        cur = nxt
        if k + 1 < n_chunks:
            with jax.named_scope(EXPAND):
                nxt = sub_gather(k + 1)  # in flight during the consume
        with jax.named_scope(DISCOVER):
            c_k, e_k = _consume_subchunk(g, cur, k, n_chunks, args)
            cand = jnp.minimum(cand, c_k)
            ex = ex + e_k
    return cand, ex


def _pipelined_topdown_expand_1d(g, front: jax.Array, args: "LevelArgs1D"):
    """Chunked dense expand: C sub-chunk allgathers overlapped with the
    per-sub-chunk SpMSV.  Returns (cand, ex_local, wire)."""
    part = args.part
    C = args.expand_chunks
    words = pack_bits(front)                         # (chunk//32,) u32
    subs = words.reshape(C, words.size // C)
    cand, ex = pipelined_expand_consume(
        g, lambda k: lax.all_gather(subs[k], args.axis, tiled=True), C, args)
    wire = jnp.float32(
        comm_model.chunked_expand_1d_level_words(part.n, part.p, C))
    return cand, ex, wire


def topdown_level_1d(g: Dict[str, jax.Array], pi: jax.Array,
                     front: jax.Array, args: LevelArgs1D, lv=None
                     ) -> Tuple[jax.Array, jax.Array, Dict]:
    """One 1D top-down level. g holds the strip arrays (squeezed).
    ``lv`` is the fast-path per-level context (unused here); with
    ``args.instrument`` False the level is ONE collective — the bitmap
    allgather — and ``ctr`` comes back empty."""
    part = args.part
    instr = args.instrument
    ctr = zero_counters() if instr else {}

    if args.expand_chunks > 1:
        # Software pipeline: C sub-chunk allgathers, each consumed by a
        # partial SpMSV while the next is in flight (same total bytes).
        cand, ex_local, wire = _pipelined_topdown_expand_1d(g, front, args)
    else:
        # --- Expand: allgather the frontier bitmap along the axis --------
        with jax.named_scope(EXPAND):
            f_words, wire = expand_frontier_1d(front, args.axis)
            f_all = unpack_bits(f_words)             # (n,) bool
        # --- Local discovery: SpMSV over the strip (global source ids, so
        # col_offset = 0; format-specific work lives in the LocalOps
        # entry) --
        with jax.named_scope(DISCOVER):
            cand, ex_local = _resolve_ops(args).topdown(
                g, f_words, f_all, part.chunk, jnp.int32(0), args)
    if instr:
        ctr["wire_expand"] = wire
        n_f = lax.psum(jnp.sum(front, dtype=jnp.float32), args.axis)
        ctr["use_expand"] = n_f * (part.p - 1)       # sparse-id equivalent
        ctr["edges_examined"] = lax.psum(ex_local, args.axis)
        ctr["edges_useful"] = lax.psum(
            jnp.sum(jnp.where(front, g["deg_A"], 0), dtype=jnp.float32),
            args.axis)

    # --- Local update (children are owned; no fold) ----------------------
    with jax.named_scope(FOLD):
        newly = (pi == -1) & (cand != INT_INF)
        pi = jnp.where(newly, cand, pi)
    return pi, newly, ctr


def bottomup_level_1d(g: Dict[str, jax.Array], pi: jax.Array,
                      front: jax.Array, args: LevelArgs1D, lv=None
                      ) -> Tuple[jax.Array, jax.Array, Dict]:
    """One 1D bottom-up level: after the same frontier allgather, each
    processor scans its *unvisited* owned rows for an in-neighbor in the
    frontier — one sub-step, no rotation (the strip already holds every
    potential parent edge)."""
    part = args.part
    instr = args.instrument
    ctr = zero_counters() if instr else {}

    with jax.named_scope(EXPAND):
        f_words, wire = expand_frontier_1d(front, args.axis)
    if instr:
        ctr["wire_expand"] = wire
        ctr["use_expand"] = jnp.float32(
            comm_model.expand_1d_level_words(part.n, part.p))

    with jax.named_scope(DISCOVER):
        cvec = (pi != -1).astype(jnp.int32)
        # dense entries ship each edge's strip-local row; kernel entries
        # ship none and find rows inside the scan
        ve = g.get("edge_dst")
        seg_par = _resolve_ops(args).bottomup(g["row_ptr"], g["col_idx"],
                                              f_words, cvec, jnp.int32(0),
                                              g["nnz"], ve, args)
    with jax.named_scope(UPDATE):
        newly = (pi == -1) & (seg_par != INT_INF)
        pi = jnp.where(newly, seg_par, pi)

    if instr:
        row_lens = (g["row_ptr"][1:] - g["row_ptr"][:-1]).astype(jnp.float32)
        edges_use = lax.psum(
            jnp.sum(jnp.where(cvec == 0, row_lens, 0.0)), args.axis)
        ctr["edges_examined"] = edges_use
        ctr["edges_useful"] = edges_use
        # parent updates are local in 1D: use_updates counts discoveries
        # for Eq. 2 comparability, wire_updates stays 0
        ctr["use_updates"] = 2.0 * lax.psum(
            jnp.sum(newly, dtype=jnp.float32), args.axis)
    return pi, newly, ctr
