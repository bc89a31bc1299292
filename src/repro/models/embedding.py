"""Sharded EmbeddingBag — the recsys hot path.

JAX has no native EmbeddingBag or CSR sparse; this builds it from
``jnp.take`` + ``jax.ops.segment_sum`` (single-device path) and a
row-sharded shard_map lookup (distributed path).

All field tables are concatenated into one (total_rows, dim) matrix with
per-field offsets.  Distribution: rows sharded over the "model" axis;
each device gathers the rows it owns (mask-clipped local gather) and a
psum over "model" assembles the result — structurally the paper's *fold*
(owner-computes exchange; see DESIGN.md §Arch-applicability).  The
index-exchange (all_to_all) variant lives in the perf notes as the
beyond-baseline option.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import RecsysConfig
from repro.models.common import ShardCtx


def table_meta(cfg: RecsysConfig) -> Tuple[np.ndarray, int]:
    offsets = np.concatenate([[0], np.cumsum(cfg.vocab_sizes)])
    total = int(offsets[-1])
    total = ((total + 511) // 512) * 512       # row-shardable on any mesh
    return offsets.astype(np.int64), total


def init_table(cfg: RecsysConfig, key) -> jnp.ndarray:
    _, total = table_meta(cfg)
    return (jax.random.normal(key, (total, cfg.embed_dim), jnp.float32)
            * (cfg.embed_dim ** -0.5))


def flat_indices(cfg: RecsysConfig, idx: jnp.ndarray) -> jnp.ndarray:
    """(B, F) per-field indices -> flat row ids into the concat table."""
    offsets, _ = table_meta(cfg)
    return idx + jnp.asarray(offsets[:-1], idx.dtype)[None, :]


def lookup(table: jnp.ndarray, rows: jnp.ndarray, ctx: ShardCtx):
    """rows: (...,) flat row ids -> (..., D) embeddings.

    Distributed: table rows sharded P("model", None); local masked gather
    + psum along "model"."""
    if ctx.mesh is None or ctx.tp_size == 1:
        return jnp.take(table, rows, axis=0)

    def body(tab, r):
        size = tab.shape[0]
        r0 = lax.axis_index("model") * size
        loc = r - r0
        ok = (loc >= 0) & (loc < size)
        vals = jnp.take(tab, jnp.clip(loc, 0, size - 1), axis=0)
        vals = jnp.where(ok[..., None], vals, 0.0)
        return lax.psum(vals, "model")

    dpa = ctx.dp
    flat = rows.reshape(-1)
    dp_total = int(np.prod([ctx.mesh.shape[a] for a in dpa])) if dpa else 1
    rspec = P(dpa) if (dpa and flat.shape[0] % dp_total == 0) else P(None)
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P("model", None), rspec),
        out_specs=P(*rspec, None), check_vma=False,
    )(table, flat).reshape(*rows.shape, table.shape[1])


def embedding_bag(table, bag_ids, bag_weights=None, mode: str = "sum",
                  ctx: ShardCtx = ShardCtx(), use_kernel: bool = False):
    """bag_ids: (B, L) multi-hot rows (-1 = pad) -> (B, D) reduced.

    ``use_kernel`` routes the gather-reduce through the Pallas TBE kernel
    (interpret-validated; single-device only)."""
    if use_kernel and (ctx.mesh is None or ctx.tp_size == 1):
        from repro.kernels.embedding_bag import ops as eb_ops
        return eb_ops.embedding_bag(table, bag_ids, bag_weights, mode=mode,
                                    interpret=jax.default_backend() == "cpu")
    valid = bag_ids >= 0
    safe = jnp.where(valid, bag_ids, 0)
    vals = lookup(table, safe, ctx)
    w = valid.astype(vals.dtype)
    if bag_weights is not None:
        w = w * bag_weights
    out = jnp.sum(vals * w[..., None], axis=-2)
    if mode == "mean":
        out = out / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return out
