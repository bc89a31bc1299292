"""Jitted wrapper for the bottom-up sub-step kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.bottomup.bottomup import bottomup_substep_kernel
from repro.kernels.bottomup.ref import bottomup_substep as substep_ref

# the jnp reference rides along as part of the public surface so
# callers can A/B the kernel against its ref without a second import
__all__ = ["bottomup_substep", "substep_ref"]


@functools.partial(jax.jit, static_argnames=("rt", "interpret"))
def bottomup_substep(rp_seg, ue_win, f_words, cvec, col_offset, n_edges,
                     rt: int = 128, *, interpret: bool):
    return bottomup_substep_kernel(rp_seg, ue_win, f_words, cvec, col_offset,
                                   n_edges, rt=rt, interpret=interpret)
