"""Strip SpMSV gather for the 1D row decomposition.

A 1D strip T[V_i, :] spans *every* global source column, so an
uncompressed CSC col_ptr costs n+1 words per processor — the O(n)
aggregate blow-up the paper's §5.1 charges against 1D compressed
storage, and the reason the 1D path was dense-only until now.  Strip
DCSC stores just the strip's non-empty global columns (``jc``) with
pointers (``cp``) into the CSC-ordered ``row_idx``, O(nzc) words.

The gather walks ``jc`` — NOT the frontier — because nzc <= nnz is the
strip-local quantity while the frontier is global: each non-empty
column slot is tested against the allgathered frontier *bitmap* (packed
uint32 words, the same representation the 1D expand allgathers), and
the live columns' contiguous segments are gathered by the ragged-gather
kernel of the 2D path (spmsv.py).  Dead slots get zero-length segments,
so traffic ~ sum of frontier-column degrees.

The bitmap test is a data-dependent 1-D gather, which the TPU compiler
refuses inside a kernel, so it runs in XLA around the ``pallas_call``
and hands the kernel plain (start, length) segments.  As in the 2D
split, the SPA accumulation (scatter-min of global source ids, the
paper's §5.2 sparse accumulator) stays outside the kernel where XLA
lowers it to a sorted segment reduction.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.frontier import test_bits
from repro.kernels.spmsv.spmsv import gather_segments


def strip_live_columns(jc, nzc, f_words):
    """(cap_nzc,) bool: DCSC slot holds a real column present in the
    frontier bitmap ``f_words`` (sentinel columns are ``jc == n``)."""
    n = f_words.shape[0] * 32
    slot = jnp.arange(jc.shape[0])
    return (slot < nzc) & (jc < n) & test_bits(f_words,
                                               jnp.minimum(jc, n - 1))


def strip_live_columns_chunk(jc, nzc, f_sub, *, n: int, p: int, k: int,
                             n_chunks: int):
    """Per-chunk liveness for the software-pipelined expand: ``f_sub`` is
    the RAW gathered sub-chunk buffer of pipeline step ``k`` — owner-major
    ``(p * w_sub,)`` u32 words covering owner-local word range
    [k*w_sub, (k+1)*w_sub) of each owner's ``wpc``-word strip — consumed
    directly, so no full-size frontier bitmap is ever materialized.  A
    column is live only when it falls inside sub-chunk k.  ``n`` and ``p``
    are explicit: the buffer no longer spans the full vertex range."""
    wpc = (n // p) // 32                  # packed words per owner strip
    w_sub = wpc // n_chunks
    if f_sub.shape[0] != p * w_sub:
        raise ValueError(
            f"sub-chunk buffer has {f_sub.shape[0]} words, expected "
            f"p*w_sub = {p}*{w_sub} for n={n}, n_chunks={n_chunks}")
    slot = jnp.arange(jc.shape[0])
    uc = jnp.minimum(jc, n - 1)
    wi = uc >> 5                          # global packed-word index
    owner = wi // wpc
    lw = wi - owner * wpc                 # word index within the owner's strip
    in_rng = (lw >= k * w_sub) & (lw < (k + 1) * w_sub)
    pos = jnp.where(in_rng, owner * w_sub + (lw - k * w_sub), 0)
    bit = ((f_sub[pos] >> (uc.astype(jnp.uint32) & jnp.uint32(31)))
           & jnp.uint32(1)) == 1
    return (slot < nzc) & (jc < n) & in_rng & bit


def _gather_live(cp, live, row_idx, maxdeg: int, interpret: bool):
    lens = jnp.where(live, cp[1:] - cp[:-1], 0)
    return gather_segments(cp[:-1], lens, row_idx, cap_f=live.shape[0],
                           maxdeg=maxdeg, interpret=interpret)


def gather_strip_segments(jc, cp, nzc, row_idx, f_words, *, maxdeg: int,
                          interpret: bool):
    """(cap_nzc,) DCSC columns -> ``gather_segments`` rows of the
    columns present in the frontier bitmap, -1 elsewhere."""
    return _gather_live(cp, strip_live_columns(jc, nzc, f_words), row_idx,
                        maxdeg, interpret)


def gather_strip_segments_chunk(jc, cp, nzc, row_idx, f_sub, *, n: int,
                                p: int, k: int, n_chunks: int, maxdeg: int,
                                interpret: bool):
    """Chunked variant of ``gather_strip_segments`` over one pipelined
    sub-chunk buffer (see ``strip_live_columns_chunk``); the caller
    min-combines the per-chunk scatter results (exact under the
    (select-source, min) semiring)."""
    live = strip_live_columns_chunk(jc, nzc, f_sub, n=n, p=p, k=k,
                                    n_chunks=n_chunks)
    return _gather_live(cp, live, row_idx, maxdeg, interpret)
