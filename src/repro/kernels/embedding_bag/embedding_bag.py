"""Pallas TPU kernel: table-batched EmbeddingBag (gather + bag-reduce).

The hot recsys op (FBGEMM TBE): for each bag, gather L rows of the
embedding table and reduce.  Tiled over batch; the row gather is a VMEM
vector gather (interpret-validated; the HBM-streaming variant keeps the
same grid and swaps the table BlockSpec for a scalar-prefetch index map).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(ids_ref, w_ref, table_ref, out_ref, *, bt: int, L: int,
            mean: bool):
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    wsum = jnp.zeros((bt,), jnp.float32)
    for j in range(L):
        ids = ids_ref[:, j]
        valid = ids >= 0
        rows = table_ref[jnp.where(valid, ids, 0), :]
        w = w_ref[:, j] * valid.astype(jnp.float32)
        acc += rows.astype(jnp.float32) * w[:, None]
        wsum += w
    if mean:
        acc = acc / jnp.maximum(wsum, 1e-9)[:, None]
    out_ref[...] = acc.astype(out_ref.dtype)


def embedding_bag_kernel(table, bag_ids, bag_weights=None, mode: str = "sum",
                         bt: int = 128, *, interpret: bool):
    B, L = bag_ids.shape
    V, D = table.shape
    bt = min(bt, B)
    assert B % bt == 0, (B, bt)
    if bag_weights is None:
        bag_weights = jnp.ones((B, L), jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, bt=bt, L=L, mean=(mode == "mean")),
        grid=(B // bt,),
        in_specs=[
            pl.BlockSpec((bt, L), lambda b: (b, 0)),
            pl.BlockSpec((bt, L), lambda b: (b, 0)),
            pl.BlockSpec((V, D), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, D), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), table.dtype),
        interpret=interpret,
    )(bag_ids, bag_weights.astype(jnp.float32), table)
