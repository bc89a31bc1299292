"""Pure-jnp oracle for the bottom-up sub-step (Alg. 4, lines 10-16).

Given one rotating segment of ``chunk`` rows (window-rebased CSR pointers
``rp_seg`` and the source-column window ``ue_win``), a packed frontier
bitmap over the block's column range, and the completed mask, produce the
segment's newly-discovered parents (global source ids; INT_INF = none).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.frontier import INT_INF, test_bits
from repro.core.scopes import EDGE_ROWS


def bottomup_substep(rp_seg: jnp.ndarray,   # (chunk+1,) i32, rebased to window
                     ue_win: jnp.ndarray,   # (cap_seg,) i32 local source cols
                     f_words: jnp.ndarray,  # (nc//32,) u32 frontier bitmap
                     cvec: jnp.ndarray,     # (chunk,) i32/bool completed
                     col_offset: jnp.ndarray,  # scalar i32: j*nc
                     n_edges: jnp.ndarray,     # scalar i32: window edge count
                     ve_win=None,           # (cap_seg,) i32 per-edge row - row0
                     ) -> jnp.ndarray:
    """``ve_win`` holds each window edge's row (the shipped CSR
    ``edge_dst``, rebased to the segment): the dense steps always pass it,
    an O(E) read.  Without it the rows come from an O(E log chunk)
    ``searchsorted`` over ``rp_seg``; that branch serves only as the
    oracle of the Pallas scan (kernels/bottomup/ops.py), which takes no
    rows."""
    chunk = rp_seg.shape[0] - 1
    cap = ue_win.shape[0]
    eidx = jnp.arange(cap, dtype=jnp.int32)
    valid = eidx < n_edges
    with jax.named_scope(EDGE_ROWS):
        if ve_win is None:
            # row of each window edge (CSR order => rows nondecreasing)
            erow = jnp.searchsorted(rp_seg, eidx,
                                    side="right").astype(jnp.int32) - 1
            erow = jnp.clip(erow, 0, chunk - 1)
        else:
            erow = jnp.clip(ve_win, 0, chunk - 1)
    notdone = (cvec == 0)[erow]
    in_frontier = test_bits(f_words, ue_win)
    hit = valid & notdone & in_frontier
    vals = jnp.where(hit, col_offset + ue_win, INT_INF).astype(jnp.int32)
    out = jnp.full((chunk,), INT_INF, jnp.int32).at[erow].min(
        jnp.where(hit, vals, INT_INF))
    # completed rows can't be rediscovered
    return jnp.where(cvec != 0, INT_INF, out)
