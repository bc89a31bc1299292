"""Jitted wrapper for the Flash attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal: bool = True, window=None,
                    q_offset: int = 0, bq: int = 128, bk: int = 128, *,
                    interpret: bool):
    return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, bq=bq, bk=bk,
                                  interpret=interpret)
