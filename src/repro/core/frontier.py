"""Frontier representations + the paper's vector-redistribution steps.

Runs *inside* shard_map.  Bitmaps are uint32 words (the paper packs 64
vertices/word; we use 32-bit lanes — the unit conversion is handled in the
comm counters, which report paper-units: 1 vertex id = 1 word, 1 vertex
bitmap bit = 1/64 word).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

INT_INF = jnp.int32(2**31 - 1)


def pack_bits(mask: jax.Array) -> jax.Array:
    """(X,) bool -> (X//32,) uint32.  X must be a multiple of 32."""
    b = mask.reshape(-1, 32).astype(jnp.uint32)
    return jnp.sum(b << jnp.arange(32, dtype=jnp.uint32), axis=1,
                   dtype=jnp.uint32)


def unpack_bits(words: jax.Array) -> jax.Array:
    """(W,) uint32 -> (W*32,) bool."""
    bits = (words[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    return bits.reshape(-1).astype(bool)


def test_bits(words: jax.Array, idx: jax.Array) -> jax.Array:
    """Membership test idx -> bool against a packed bitmap (gather)."""
    w = words[idx >> 5]
    return ((w >> (idx.astype(jnp.uint32) & jnp.uint32(31))) & 1).astype(bool)


def pack_ids(mask: jax.Array, cap: int, offset, sentinel) -> jax.Array:
    """Sparse frontier compaction: the global ids of the set bits of a
    local (chunk,) bool mask, as a fixed-capacity (cap,) i32 buffer.
    Unused slots (and every slot past ``cap``, if the mask has more than
    ``cap`` bits — callers must detect that overflow themselves) hold
    ``sentinel``; set bits beyond ``cap`` are silently dropped, which is
    why the 1ds exchange guards this with a dense-bitmap fallback."""
    chunk = mask.shape[0]
    off = jnp.where(mask, size=cap, fill_value=chunk)[0]
    return jnp.where(off < chunk, offset + off, sentinel).astype(jnp.int32)


def unpack_ids(ids: jax.Array, n: int) -> jax.Array:
    """Scatter sparse global ids back into a packed n-bit bitmap
    (uint32 words).  Out-of-range ids — the ``pack_ids`` sentinel — are
    dropped."""
    mask = jnp.zeros((n,), bool).at[ids].set(True, mode="drop")
    return pack_bits(mask)


def transpose_vector(x: jax.Array, perm: Sequence[Tuple[int, int]],
                     axes: Tuple[str, str]) -> jax.Array:
    """The paper's TransposeVector: one collective-permute over the 2D grid
    moving each device's whole chunk from layout A to layout B (or back,
    with the inverse perm)."""
    return lax.ppermute(x, axes, perm)


def expand_bitmap(front_chunk: jax.Array, perm, axes) -> Tuple[jax.Array, jax.Array]:
    """Expand (Alg.3 l.5-6 / Alg.4 l.6-7): transpose to layout B, then
    allgather packed words along the processor column (mesh axis axes[0])
    to reconstruct the C_j frontier slice.

    Returns (f_cj_words  uint32[nc//32], wire_words_per_device f32 in
    paper 64-bit-word units for the transpose+gather)."""
    row_axis = axes[0]
    words = pack_bits(front_chunk)
    words_b = transpose_vector(words, perm, axes)
    gathered = lax.all_gather(words_b, row_axis, tiled=True)
    pr = lax.axis_size(row_axis)
    wire = jnp.float32(words.size) * (1.0 / 2.0) * (1 + (pr - 1))
    # 1/2: uint32 word = half a 64-bit paper word. transpose sends 1 copy,
    # allgather sends (pr-1) copies of each word.
    return gathered, wire
