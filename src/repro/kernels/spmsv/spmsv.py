"""Pallas TPU kernel: frontier-driven adjacency gather for top-down BFS.

The paper's SpMSV reads only the adjacency lists of *frontier* vertices
(CSC/DCSC column segments) — work proportional to the frontier, not the
block.  On TPU we split the op:

  kernel : the irregular part — a ragged gather that walks each frontier
           vertex's contiguous CSC segment one 128-lane row at a time
           (grid = one program per frontier slot, a ``fori_loop`` over
           only the rows the segment touches, so total traffic ~ sum of
           frontier degrees).
  XLA    : the SPA accumulation (scatter-min), which XLA lowers to a
           sorted segment reduction — the paper's sparse accumulator
           (§5.2) realized as a dense vector write, its recommended
           choice.

``row_idx`` sits in VMEM as a lane-dense ``(R, 128)`` array.  A segment
[s, s + n) is read as the ALIGNED rows that cover it, with lanes outside
the segment masked to -1: the TPU compiler refuses unaligned 1-D
dynamic slices, and the scatter-min consumer does not care where in its
output row an edge lands.  The output row of slot g therefore holds the
segment's edges at lane offset ``s % 128``, -1 elsewhere.

DCSC indirection (the paper's §5.1 hypersparse format) happens *outside*
the kernel: the column-pointer lookup goes through the (JC, CP) parallel
arrays with a binary search, reproducing DCSC's extra access cost that
Figure 6 measures.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import check_fast_memory

LANES = 128


def _gather_kernel(starts_ref, lens_ref, ridx_ref, out_ref, *, nt: int):
    g = pl.program_id(0)          # frontier slot
    s = starts_ref[g]
    n = lens_ref[g]
    r0 = s // LANES               # first aligned row touching the segment
    rows = jnp.where(n > 0, (s + n + LANES - 1) // LANES - r0, 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    out_ref[...] = jnp.full(out_ref.shape, -1, jnp.int32)

    def body(t, carry):
        e = (r0 + t) * LANES + lane
        v = ridx_ref[pl.ds(r0 + t, 1), :]
        out_ref[0, pl.ds(t, 1), :] = jnp.where((e >= s) & (e < s + n), v,
                                               jnp.int32(-1))
        return carry

    lax.fori_loop(0, jnp.minimum(rows, nt), body, 0)


def gather_segments(starts, lens, row_idx, *, cap_f: int, maxdeg: int,
                    interpret: bool):
    """(cap_f,) segment starts/lens -> (cap_f, nt * 128) gathered dest
    rows, -1 outside each segment (see module docstring for the in-row
    placement).  The aligned rows covering a segment of at most
    ``maxdeg`` edges can start up to 127 lanes early, hence the one row
    of slack in nt."""
    nt = -(-max(maxdeg, 1) // LANES) + 1
    rows = -(-row_idx.shape[0] // LANES)
    ridx = jnp.pad(row_idx.astype(jnp.int32),
                   (0, rows * LANES - row_idx.shape[0])).reshape(rows, LANES)
    check_fast_memory("SpMSV gather", vmem=4 * (ridx.size + nt * LANES),
                      smem=8 * cap_f)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, nt=nt),
        grid=(cap_f,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # starts
            pl.BlockSpec(memory_space=pltpu.SMEM),           # lens
            pl.BlockSpec(ridx.shape, lambda g: (0, 0)),      # edge ids (VMEM)
        ],
        out_specs=pl.BlockSpec((1, nt, LANES), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((cap_f, nt, LANES), jnp.int32),
        interpret=interpret,
    )(starts.astype(jnp.int32), lens.astype(jnp.int32), ridx)
    return out.reshape(cap_f, nt * LANES)
