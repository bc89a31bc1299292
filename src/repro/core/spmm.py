"""2D-partitioned SpMM: the paper's BFS machinery generalized to feature
aggregation (sum semiring, d-wide payloads) — the distributed primitive
behind full-graph GNN training (GIN/GAT/products cells).

Identical schedule to top-down BFS (Alg. 3):
  expand : TransposeVector (collective-permute) + allgather along the
           processor column  -> sender-feature slice X[C_j]  (nc, d)
  local  : edge-parallel gather + segment-sum into the row strip (nr, d)
  fold   : **psum_scatter** along the processor row — a true in-network
           combining reduce-scatter (the sum semiring allows what the
           min semiring of BFS could not), bandwidth-optimal on the ICI
           torus.  This is the beyond-paper optimization the roofline
           rewards: fold wire volume drops from (pc-1)*nr to the
           reduce-scatter optimum with zero extra latency terms.

Out-degree normalization etc. are callers' business (they own vertex-wise
scaling in layout A).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.partition import Partition2D
from repro.graph.formats import BlockedGraph


def _spmm_body(g: Dict[str, jax.Array], x: jax.Array, *, part: Partition2D,
               perm, row_axis: str, col_axis: str):
    pr, pc, chunk, nc, nr = part.pr, part.pc, part.chunk, part.nc, part.nr
    g = {k: v[0, 0] for k, v in g.items()}
    x = x[0, 0]                                   # (chunk, d) layout A
    # expand: A -> B layout, then allgather C_j slice along the column
    xb = lax.ppermute(x, (row_axis, col_axis), perm)
    x_cj = lax.all_gather(xb, row_axis, tiled=True)        # (nc, d)
    # local: edge-parallel segment-sum into the row strip
    e_mask = (jnp.arange(g["edge_src"].shape[0]) < g["nnz"])[:, None]
    contrib = x_cj[g["edge_src"]] * e_mask.astype(x.dtype)
    partial = jax.ops.segment_sum(contrib, g["row_idx"], num_segments=nr)
    # fold: combining reduce-scatter along the row
    out = lax.psum_scatter(partial, col_axis, scatter_dimension=0,
                           tiled=True)                      # (chunk, d)
    return out[None, None]


def make_spmm_fn(mesh, part: Partition2D, row_axis: str = "data",
                 col_axis: str = "model"):
    """jitted fn(graph_blocks, x_blocks (pr,pc,chunk,d)) -> y_blocks."""
    body = functools.partial(_spmm_body, part=part,
                             perm=tuple(part.transpose_perm()),
                             row_axis=row_axis, col_axis=col_axis)
    spec = P(row_axis, col_axis)
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=({k: spec for k in ("edge_src", "row_idx", "nnz")}, spec),
        out_specs=spec, check_vma=False)
    return jax.jit(mapped)


def spmm_2d(graph: BlockedGraph, x: np.ndarray, mesh,
            row_axis: str = "data", col_axis: str = "model") -> np.ndarray:
    """Convenience wrapper: x (n_orig, d) -> sum-aggregated (n_orig, d)."""
    part = graph.part
    fn = make_spmm_fn(mesh, part, row_axis, col_axis)
    sh = NamedSharding(mesh, P(row_axis, col_axis))
    g = {k: jax.device_put(np.asarray(getattr(graph, k)), sh)
         for k in ("edge_src", "row_idx", "nnz")}
    xp = np.zeros((part.n, x.shape[1]), x.dtype)
    xp[: part.n_orig] = x
    xb = jax.device_put(
        xp.reshape(part.pr, part.pc, part.chunk, x.shape[1]), sh)
    y = fn(g, xb)
    return np.asarray(y).reshape(part.n, x.shape[1])[: part.n_orig]
