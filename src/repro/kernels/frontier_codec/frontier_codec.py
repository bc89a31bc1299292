"""Pallas TPU kernels: the "1ds" frontier codec (bit-packed offsets).

Same math as the jnp oracle (ref.py) — count-prefixed fixed-width
bit-packing of sorted local offsets — restructured for the VPU:

  * ``bits`` is static, so the packing is periodic: every
    P = lcm(bits, 32) / bits offsets fill exactly Q = lcm(bits, 32) / 32
    words.  Offsets are laid out as a (G, P) array (one period per row)
    and words as (G, Q); slot k of a period lands at bit k*bits, i.e. in
    word (k*bits) // 32 and, when it straddles a word boundary, the next
    one.  Both directions are therefore a static schedule of column
    slices and constant shifts — no gathers, which the TPU compiler
    refuses inside a kernel, and no sequential carry between periods.
  * Encode runs as ONE program over the bucket (cap_x is small — the
    planned crossover capacity, not the chunk); decode runs a grid
    program per received bucket, rebasing offsets by the bucket's
    owner index k * chunk and emitting the ``unpack_ids`` drop
    sentinel ``n`` for slots past the bucket's count word.  The count
    word rides in SMEM; splitting it off the payload and the (G, Q)
    reshapes happen in XLA around the kernels.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.comm_model import codec_bits, codec_packed_words
from repro.kernels import check_fast_memory


def _period(bits: int):
    """(P offsets, Q words) per period of the packed stream."""
    lcm = bits * 32 // math.gcd(bits, 32)
    return lcm // bits, lcm // 32


def _slot_words(k: int, bits: int):
    """Slot k of a period starts at bit k*bits: (first word, bit offset
    in it, second word or None when the slot fits in one word)."""
    b0 = k * bits
    q0, q1 = b0 // 32, (b0 + bits - 1) // 32
    return q0, b0 - 32 * q0, (q1 if q1 != q0 else None)


def _mask(bits: int):
    return jnp.uint32((1 << bits) - 1)


def _encode_kernel(count_ref, v_ref, out_ref, *, bits: int, p: int,
                   q: int):
    g = v_ref.shape[0]
    slot = (lax.broadcasted_iota(jnp.int32, (g, p), 0) * p
            + lax.broadcasted_iota(jnp.int32, (g, p), 1))
    v = jnp.where(slot < count_ref[0], v_ref[...], jnp.uint32(0)) \
        & _mask(bits)
    words = [jnp.zeros((g, 1), jnp.uint32) for _ in range(q)]
    for k in range(p):
        q0, sh, q1 = _slot_words(k, bits)
        col = v[:, k:k + 1]
        words[q0] = words[q0] | (col << jnp.uint32(sh))
        if q1 is not None:       # high bits spill into the next word
            words[q1] = words[q1] | (col >> jnp.uint32(32 - sh))
    out_ref[...] = jnp.concatenate(words, axis=1)


def encode_offsets_kernel(off, count, chunk: int, *, interpret: bool):
    """(cap,) i32 local offsets + scalar live count -> (1+W,) uint32
    count-prefixed bit-packed bucket (W = ceil(cap*bits/32))."""
    cap = off.shape[0]
    bits = codec_bits(chunk)
    w = codec_packed_words(cap, bits)
    p, q = _period(bits)
    g = -(-cap // p)
    count = jnp.minimum(jnp.asarray(count, jnp.int32), cap).reshape(1)
    v = jnp.pad(off.astype(jnp.uint32), (0, g * p - cap)).reshape(g, p)
    check_fast_memory("codec encode", vmem=4 * g * (p + q))
    words = pl.pallas_call(
        functools.partial(_encode_kernel, bits=bits, p=p, q=q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # count scalar
            pl.BlockSpec((g, p), lambda: (0, 0)),         # offsets (VMEM)
        ],
        out_specs=pl.BlockSpec((g, q), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, q), jnp.uint32),
        interpret=interpret,
    )(count, v)
    return jnp.concatenate([count.astype(jnp.uint32),
                            words.reshape(-1)[:w]])


def _decode_kernel(count_ref, w_ref, out_ref, *, bits: int, p: int,
                   chunk: int, n: int):
    k = pl.program_id(0)
    words = w_ref[0]                                      # (G, Q) u32
    g = words.shape[0]
    cols = []
    for s in range(p):
        q0, sh, q1 = _slot_words(s, bits)
        x = words[:, q0:q0 + 1] >> jnp.uint32(sh)
        if q1 is not None:
            x = x | (words[:, q1:q1 + 1] << jnp.uint32(32 - sh))
        cols.append(x)
    val = jnp.concatenate(cols, axis=1) & _mask(bits)
    slot = (lax.broadcasted_iota(jnp.int32, (g, p), 0) * p
            + lax.broadcasted_iota(jnp.int32, (g, p), 1))
    out_ref[0] = jnp.where(slot < count_ref[k],
                           k * chunk + val.astype(jnp.int32), jnp.int32(n))


def decode_buckets_kernel(recv, chunk: int, cap: int, n: int, p: int, *,
                          interpret: bool):
    """(p*(1+W),) uint32 allgathered buckets -> (p*cap,) i32 global ids
    (drop-sentinel ``n`` past each count), one grid program per bucket."""
    bits = codec_bits(chunk)
    w = codec_packed_words(cap, bits)
    per, q = _period(bits)
    g = -(-cap // per)
    bufs = recv.reshape(p, 1 + w)
    counts = bufs[:, 0].astype(jnp.int32)
    words = jnp.pad(bufs[:, 1:], ((0, 0), (0, g * q - w))).reshape(p, g, q)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bits=bits, p=per, chunk=chunk,
                          n=n),
        grid=(p,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # counts
            pl.BlockSpec((1, g, q), lambda k: (k, 0, 0)),     # payloads
        ],
        out_specs=pl.BlockSpec((1, g, per), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, g, per), jnp.int32),
        interpret=interpret,
    )(counts, words)
    return out.reshape(p, g * per)[:, :cap].reshape(-1)
