"""Data-parallel train step with compressed gradient all-reduce.

Integrates optim/grad_compress into the DP loop: each replica computes
local grads, compresses (error-feedback top-k or int8), the *compressed
payload* crosses the wire (psum), and replicas apply identical updates.
Residuals stay replica-local.  At 1000+-node scale this converts the
fixed per-step DP all-reduce from O(P) to O(P*ratio) bytes.

The exchanged volume is what shrinks: for top-k the psum runs over the
scattered-dense payload here (XLA has no sparse all-reduce); on the real
fleet the payload is an (indices, values) allgather — volume accounting
in EXPERIMENTS reflects ids+values, and the *math* (what update gets
applied) is identical, which is what the convergence test checks."""
from __future__ import annotations

from typing import Callable

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.optim.grad_compress import (ef_init, int8_dequantize,
                                       int8_quantize, topk_compress,
                                       topk_decompress)


def make_dp_compressed_step(loss_fn: Callable, opt, mesh, dp_axis: str,
                            mode: str = "topk", ratio: float = 0.05):
    """loss_fn(params, batch) -> scalar.  Returns jitted
    step((params, opt_state, ef_state), batch) -> (state, metrics) with
    batch sharded over dp_axis."""

    def body(state, batch):
        params, opt_state, ef = state
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = lax.pmean(loss, dp_axis)
        if mode == "topk":
            vals, idxs, ef = topk_compress(grads, ef, ratio)
            dense = topk_decompress(vals, idxs, grads)
            synced = jax.tree.map(lambda d: lax.pmean(d, dp_axis), dense)
        elif mode == "int8":
            qs, ss = int8_quantize(grads)
            deq = int8_dequantize(qs, ss, grads)
            synced = jax.tree.map(lambda d: lax.pmean(d, dp_axis), deq)
        else:
            synced = jax.tree.map(lambda g: lax.pmean(g, dp_axis), grads)
        new_p, new_o = opt.update(synced, opt_state, params)
        return (new_p, new_o, ef), {"loss": loss}

    def step(state, batch):
        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=((jax.tree.map(lambda _: P(), state[0]),
                       jax.tree.map(lambda _: P(), state[1]),
                       jax.tree.map(lambda _: P(), state[2])),
                      jax.tree.map(lambda _: P(dp_axis), batch)),
            out_specs=((jax.tree.map(lambda _: P(), state[0]),
                        jax.tree.map(lambda _: P(), state[1]),
                        jax.tree.map(lambda _: P(), state[2])),
                       {"loss": P()}),
            check_vma=False)
        return mapped(state, batch)

    return jax.jit(step)


def init_dp_state(params, opt):
    return (params, opt.init(params), ef_init(params))
