"""Jitted wrapper for the EmbeddingBag kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.embedding_bag.embedding_bag import embedding_bag_kernel


@functools.partial(jax.jit, static_argnames=("mode", "bt", "interpret"))
def embedding_bag(table, bag_ids, bag_weights=None, mode: str = "sum",
                  bt: int = 128, *, interpret: bool):
    return embedding_bag_kernel(table, bag_ids, bag_weights, mode=mode,
                                bt=bt, interpret=interpret)
