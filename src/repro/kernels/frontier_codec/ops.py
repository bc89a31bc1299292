"""Jitted wrappers for the frontier codec (Pallas kernels + jnp ref)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.frontier_codec.frontier_codec import (
    decode_buckets_kernel, encode_offsets_kernel)
from repro.kernels.frontier_codec.ref import (
    decode_buckets as decode_buckets_ref,
    encode_offsets as encode_offsets_ref)

# the jnp references ride along as part of the public surface so
# callers can A/B a kernel against its ref without a second import
__all__ = ["encode_offsets", "decode_buckets",
           "encode_offsets_ref", "decode_buckets_ref"]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def encode_offsets(off, count, chunk: int, *, interpret: bool):
    return encode_offsets_kernel(off, count, chunk, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "cap", "n", "p", "interpret"))
def decode_buckets(recv, chunk: int, cap: int, n: int, p: int, *,
                   interpret: bool):
    return decode_buckets_kernel(recv, chunk, cap, n, p,
                                 interpret=interpret)
