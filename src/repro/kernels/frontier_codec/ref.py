"""Pure-jnp oracle for the "1ds" frontier codec: fixed-width bit-packed
local offsets with a count prefix.

The sparse 1D exchange ships each owner's frontier as a bucket of ids.
Raw buckets spend a whole 32-bit lane per id, but an owner only ever
ships vertices from its OWN chunk (1D discoveries are locally owned), so
the local offset fits in ``bits = ceil(log2(chunk))`` bits — the
receiver re-adds ``k * chunk`` because bucket position k in the tiled
allgather identifies the owner.  The encoding is:

    word 0            uint32 live-id count for this bucket
    words 1..W        the cap_x offsets bit-packed at ``bits`` bits each
                      (W = ceil(cap_x * bits / 32)); slots >= count are
                      packed as 0 and ignored by the decoder

``bits`` is static (chunk is a partition constant), so encode and decode
are pure vectorized reshapes: packed bit b is bit (b % bits) of offset
b // bits, i.e. the (cap, bits) bit matrix of the offsets read in
row-major order — no variable-length scan, unlike a delta-varint stream
whose decode is inherently sequential.  Compression is 32/bits (~3x at
chunk=1024) on the physical buffer and 64/bits on the modeled id words
(``comm_model.compressed_expand_1d_words``).

The count prefix exists for correctness, not just accounting: a
sentinel IN the value domain cannot work, because offset ``chunk``
would decode in bucket k as global id (k+1)*chunk — a valid vertex
owned by the next processor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.comm_model import codec_bits, codec_packed_words


def encode_offsets(off: jax.Array, count: jax.Array, chunk: int
                   ) -> jax.Array:
    """(cap,) i32 sorted local offsets (sentinel-padded past ``count``)
    + scalar live count -> (1 + ceil(cap*bits/32),) uint32 count-prefixed
    bit-packed bucket."""
    cap = off.shape[0]
    bits = codec_bits(chunk)
    w = codec_packed_words(cap, bits)
    count = jnp.minimum(jnp.asarray(count, jnp.uint32), jnp.uint32(cap))
    slot = jnp.arange(cap, dtype=jnp.uint32)
    v = jnp.where(slot < count, off.astype(jnp.uint32), jnp.uint32(0))
    # packed bit b = bit (b % bits) of offset b // bits: the row-major
    # (cap, bits) bit matrix, zero-padded to whole words
    bit = (v[:, None] >> jnp.arange(bits, dtype=jnp.uint32)) & jnp.uint32(1)
    bit = jnp.pad(bit.reshape(-1), (0, w * 32 - cap * bits))
    words = jnp.sum(bit.reshape(w, 32) << jnp.arange(32, dtype=jnp.uint32),
                    axis=1, dtype=jnp.uint32)
    return jnp.concatenate([count.reshape(1), words])


def decode_buckets(recv: jax.Array, chunk: int, cap: int, n: int
                   ) -> jax.Array:
    """(p * (1 + W),) uint32 allgathered buckets -> (p * cap,) i32 global
    ids; slots past each bucket's count decode to the ``unpack_ids``
    drop sentinel ``n``.  Bucket position k identifies the owner, so the
    decoded offset is rebased by k * chunk."""
    bits = codec_bits(chunk)
    w = codec_packed_words(cap, bits)
    bufs = recv.reshape(-1, 1 + w)
    p = bufs.shape[0]
    counts = bufs[:, 0].astype(jnp.int32)                     # (p,)
    packed = bufs[:, 1:]                                      # (p, W)
    slot = jnp.arange(cap, dtype=jnp.uint32)
    t = jnp.arange(bits, dtype=jnp.uint32)
    # the packed stream bit by bit, read back as (cap, bits) rows
    stream = (packed[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)
              ) & jnp.uint32(1)                               # (p, W, 32)
    bit = stream.reshape(p, w * 32)[:, : cap * bits].reshape(p, cap, bits)
    val = jnp.sum(bit << t[None, None, :], axis=-1).astype(jnp.int32)
    k = jnp.arange(p, dtype=jnp.int32)[:, None]
    ids = jnp.where(slot[None, :].astype(jnp.int32) < counts[:, None],
                    k * chunk + val, jnp.int32(n))
    return ids.reshape(-1)
