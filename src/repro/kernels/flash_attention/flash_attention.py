"""Pallas TPU kernel: blocked online-softmax (Flash) attention.

Grid (BH, Sq/BQ); each step streams KV in BK-wide tiles through a
``fori_loop`` with the running (m, l, acc) online-softmax state.  Causal
and sliding-window skips are *block-level*: tiles wholly outside the mask
are never visited (the loop's upper bound is the causal frontier; the
window lower bound advances with q) — the same tile-granular work
skipping used in the bottom-up BFS kernel, applied to attention.
MXU-aligned tile defaults (BQ=BK=128, dh multiple of 128 preferred).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, sk: int,
            causal: bool, window, q_offset: int, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, dh)
    q0 = q_offset + qi * bq
    qpos = q0 + jnp.arange(bq, dtype=jnp.int32)

    hi = sk if not causal else jnp.minimum(sk, q0 + bq)
    lo = 0 if window is None else jnp.maximum(0, q0 - (window - 1))
    lo_blk = (lo // bk) if window is not None else 0
    hi_blk = (hi + bk - 1) // bk

    def body(j, state):
        m, l, acc = state
        kj = k_ref[0, pl.ds(j * bk, bk), :]
        vj = v_ref[0, pl.ds(j * bk, bk), :]
        s = q @ kj.astype(jnp.float32).T               # (BQ, BK)
        kpos = j * bk + jnp.arange(bk, dtype=jnp.int32)
        mask = kpos[None, :] < sk
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1)
        acc_new = acc * corr[:, None] + p @ vj.astype(jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)
    m, l, acc = lax.fori_loop(lo_blk, hi_blk, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window=None,
                           q_offset: int = 0, bq: int = 128, bk: int = 128,
                           interpret: bool):
    """q: (BH, Sq, dh); k, v: (BH, Sk, dh) -> (BH, Sq, dh)."""
    BH, Sq, dh = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    sq_pad = ((Sq + bq - 1) // bq) * bq
    if sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - Sq), (0, 0)))
    sk_pad = ((Sk + bk - 1) // bk) * bk
    if sk_pad != Sk:
        k = jnp.pad(k, ((0, 0), (0, sk_pad - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad - Sk), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, sk=Sk, causal=causal,
                          window=window, q_offset=q_offset,
                          scale=dh ** -0.5),
        grid=(BH, sq_pad // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk_pad, dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk_pad, dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, sq_pad, dh), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq]
