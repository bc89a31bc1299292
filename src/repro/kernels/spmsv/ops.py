"""Jitted SpMSV wrappers: CSC and DCSC frontier-driven local discovery.

``spmsv_block_csr`` indexes column segments through the full col_ptr
(fast, O(n*pr) aggregate memory); ``spmsv_block_dcsc`` goes through the
compressed (JC, CP) arrays with a binary search per frontier vertex —
the paper's hypersparse trade-off (§5.1), reproduced faithfully.
``spmsv_strip_dcsc`` is the 1D counterpart: it walks the strip's
non-empty global columns against the allgathered frontier bitmap
(kernels/spmsv/strip.py), so no O(n) pointer array ever exists.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core.frontier import INT_INF
from repro.kernels.spmsv.spmsv import gather_segments
from repro.kernels.spmsv.strip import (gather_strip_segments,
                                       gather_strip_segments_chunk)


def _scatter_min(dst, ids, col_offset, nr):
    """(slots, width) gathered dest rows (-1 = none) + the slots' source
    ids -> (nr,) candidate parents."""
    # the barrier keeps the TPU compiler from fusing the kernel output's
    # relayout into the scatter: fused, a scale-14 search took ~55 s to
    # compile for v5e instead of ~1 s
    dst = lax.optimization_barrier(dst)
    parent = (col_offset + ids).astype(jnp.int32)[:, None]
    valid = dst >= 0
    vals = jnp.where(valid, jnp.broadcast_to(parent, dst.shape), INT_INF)
    flat_dst = jnp.where(valid, dst, 0).reshape(-1)
    return jnp.full((nr,), INT_INF, jnp.int32).at[flat_dst].min(
        vals.reshape(-1))


def frontier_ids(f_cj: jnp.ndarray, cap_f: int, nc: int):
    ids = jnp.where(f_cj, size=cap_f, fill_value=nc)[0].astype(jnp.int32)
    return ids, ids < nc


def spmsv_block_csr(col_ptr, row_idx, f_cj, nr: int, col_offset,
                    *, cap_f: int, maxdeg: int, interpret: bool):
    nc = f_cj.shape[0]
    ids, live = frontier_ids(f_cj, cap_f, nc)
    idc = jnp.minimum(ids, nc - 1)
    starts = col_ptr[idc]
    lens = jnp.where(live, col_ptr[idc + 1] - starts, 0)
    dst = gather_segments(starts, lens, row_idx, cap_f=cap_f,
                          maxdeg=maxdeg, interpret=interpret)
    return _scatter_min(dst, ids, col_offset, nr)


def spmsv_strip_dcsc(jc, cp, nzc, row_idx, f_words, nr: int,
                     *, maxdeg: int, interpret: bool):
    """1D strip SpMSV over doubly compressed global source columns: the
    kernel walks the nzc slots, bitmap-testing each column against the
    allgathered frontier, so there is no per-frontier-vertex lookup and
    no O(n) pointer array.  Column ids are already global (col_offset is
    structurally 0 in the strip layout)."""
    dst = gather_strip_segments(jc, cp, nzc, row_idx, f_words,
                                maxdeg=maxdeg, interpret=interpret)
    # sentinel slots (jc = n) gather nothing, so their parent value is
    # never scattered; col_offset=0 keeps the ids global
    return _scatter_min(dst, jc, jnp.int32(0), nr)


def spmsv_strip_dcsc_chunk(jc, cp, nzc, row_idx, f_sub, nr: int, *, n: int,
                           p: int, k: int, n_chunks: int, maxdeg: int,
                           interpret: bool):
    """Software-pipelined strip SpMSV step: consume ONE gathered
    sub-chunk of the chunked expand (owner-major ``(p * w_sub,)`` u32
    words covering owner-local word range [k*w_sub, (k+1)*w_sub)) with
    no full-size frontier bitmap ever built.  The caller min-combines
    the per-chunk candidates — exact, since the scatter below is a MIN
    over global source ids."""
    dst = gather_strip_segments_chunk(jc, cp, nzc, row_idx, f_sub, n=n, p=p,
                                      k=k, n_chunks=n_chunks, maxdeg=maxdeg,
                                      interpret=interpret)
    return _scatter_min(dst, jc, jnp.int32(0), nr)


def spmsv_block_dcsc(jc, cp, nzc, row_idx, f_cj, nr: int, col_offset,
                     *, cap_f: int, maxdeg: int, interpret: bool):
    nc = f_cj.shape[0]
    ids, live = frontier_ids(f_cj, cap_f, nc)
    # binary search in the compressed column ids (the DCSC indirection)
    pos = jnp.searchsorted(jc, ids).astype(jnp.int32)
    pos = jnp.minimum(pos, jc.shape[0] - 1)
    found = live & (jc[pos] == ids) & (pos < nzc)
    starts = jnp.where(found, cp[pos], 0)
    lens = jnp.where(found, cp[pos + 1] - cp[pos], 0)
    dst = gather_segments(starts, lens, row_idx, cap_f=cap_f,
                          maxdeg=maxdeg, interpret=interpret)
    return _scatter_min(dst, ids, col_offset, nr)
