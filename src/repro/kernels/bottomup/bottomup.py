"""Pallas TPU kernel: bottom-up BFS sub-step (Alg. 4, lines 10-16).

TPU adaptation of the paper's serialized inner loop:

  * The per-vertex "scan neighbors until a parent is found, then stop"
    early exit is hostile to SIMD, so it is restructured at *tile*
    granularity: a row tile (RT rows) scans its contiguous CSR edge
    window in 1024-edge tiles inside a ``lax.while_loop`` whose
    predicate stops as soon as EVERY live row in the tile has found a
    parent (or the window is exhausted).  The work skip the paper gets
    from ``break`` is preserved — whole edge tiles are never touched once
    the row tile completes — while each tile step stays fully vectorized
    on the VPU: rows on sublanes, edges on lanes, one (RT, 1024)
    row-membership mask per tile.
  * Frontier membership is a packed uint32 bitmap (the paper's §4.3
    "dense format compressed by a bitmap").  Testing it is a
    data-dependent gather, which the TPU compiler refuses inside a
    kernel, so XLA runs it around the ``pallas_call``: the kernel reads
    each window edge's candidate parent (``col_offset + u`` when u is in
    the frontier, INT_INF otherwise) from a lane-dense (R, 128) array.
  * ``completed`` rows are masked out up front, so rotated-in work that
    earlier sub-steps finished is skipped, exactly like the paper's c
    bitmap filter.

Edge tiles start at the 128-aligned row below the row tile's first
edge (the compiler refuses unaligned 1-D dynamic slices); the per-row
[start, end) mask drops the edges of neighbouring row tiles.  The edge
window is one whole VMEM block (HBM streaming is future work).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.frontier import test_bits
from repro.kernels import check_fast_memory

INT_INF = 2**31 - 1  # python literal: pallas kernels must not capture arrays
LANES = 128
TILE_ROWS = 8                  # edge tile = (8, 128) = 1024 edges
TILE_EDGES = TILE_ROWS * LANES


def _kernel(bounds_ref, starts_ref, ends_ref, done_ref, val_ref, out_ref):
    r = pl.program_id(0)
    lo = bounds_ref[2 * r]                       # row tile's edge range
    hi = bounds_ref[2 * r + 1]
    starts = starts_ref[0]                       # (rt, 1) row [start, end)
    ends = ends_ref[0]
    done = done_ref[0] != 0                      # (rt, 1) completed rows
    row0 = lo // LANES
    lane = lax.broadcasted_iota(jnp.int32, (1, TILE_EDGES), 1)

    # found = completed or discovered; derived, not carried (the loop
    # carries int32 vectors only)
    def cond(state):
        t, par = state
        open_rows = jnp.where(done | (par != INT_INF), 0, 1)
        return (((row0 + t * TILE_ROWS) * LANES < hi)
                & (jnp.max(open_rows) > 0))

    def body(state):
        t, par = state
        rs = row0 + t * TILE_ROWS
        val = val_ref[pl.ds(rs, TILE_ROWS), :].reshape(1, TILE_EDGES)
        e = rs * LANES + lane
        mine = (e >= starts) & (e < ends) & jnp.logical_not(
            done | (par != INT_INF))
        par = jnp.minimum(par, jnp.min(jnp.where(mine, val, INT_INF),
                                       axis=1, keepdims=True))
        return t + 1, par

    par0 = jnp.full(done.shape, INT_INF, jnp.int32)
    _, par = lax.while_loop(cond, body, (jnp.int32(0), par0))
    out_ref[0] = jnp.where(done, INT_INF, par)


def bottomup_substep_kernel(rp_seg, ue_win, f_words, cvec, col_offset,
                            n_edges, *, rt: int = 128, interpret: bool):
    """(chunk+1,)(cap,)(ncw,)(chunk,) + scalars -> (chunk,) i32 parents."""
    chunk = rp_seg.shape[0] - 1
    rt = min(rt, chunk)
    if chunk % rt:
        raise ValueError(f"row tile {rt} does not divide chunk {chunk}")
    nt = chunk // rt
    cap = ue_win.shape[0]
    # frontier-membership gather in XLA: each edge's candidate parent
    eidx = jnp.arange(cap, dtype=jnp.int32)
    hit = (eidx < n_edges) & test_bits(f_words, ue_win)
    val = jnp.where(hit, jnp.asarray(col_offset, jnp.int32) + ue_win,
                    jnp.int32(INT_INF)).astype(jnp.int32)
    rows = -(-cap // LANES) + TILE_ROWS          # last tile may over-read
    val = jnp.pad(val, (0, rows * LANES - cap),
                  constant_values=INT_INF).reshape(rows, LANES)
    # (rt, 1) column blocks pad to 128 lanes
    check_fast_memory("bottom-up sub-step",
                      vmem=4 * (val.size + 4 * rt * LANES), smem=8 * nt)
    rp = rp_seg.astype(jnp.int32)
    bounds = jnp.stack([rp[:-1:rt], rp[rt::rt]], axis=1).reshape(-1)
    col = lambda x: x.reshape(nt, rt, 1)
    tile = pl.BlockSpec((1, rt, 1), lambda r: (r, 0, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # row-tile edge bounds
            tile,                                    # row starts
            tile,                                    # row ends
            tile,                                    # completed
            pl.BlockSpec(val.shape, lambda r: (0, 0)),  # edge window
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((nt, rt, 1), jnp.int32),
        interpret=interpret,
    )(bounds, col(rp[:-1]), col(rp[1:]), col((cvec != 0).astype(jnp.int32)),
      val)
    return out.reshape(chunk)
