"""Per-level BFS steps for the sparse-exchange 1D decomposition ("1ds"):
the paper's Alg. 1/2 baseline with the frontier exchanged as
owner-directed sparse vertex ids instead of a dense n-bit bitmap.

The dense ``"1d"`` expand (core/steps_1d.py) allgathers one n-bit bitmap
per level — (p-1)*n/64 words regardless of frontier size, which is
exactly the O(n*p) scaling the paper's §4/§6 analysis charges against 1D
on small frontiers.  Buluc & Madduri's sparse formulation ships only the
live frontier: each processor owns the newly discovered chunk of the
frontier (1D discoveries are always locally owned), so the owner packs
its frontier ids into a fixed-capacity send bucket and one tiled
allgather delivers it to every peer — n_f*(p-1) words on the wire, a win
while n_f < n/64.  (With the adjacency partitioned by destination, every
strip may hold out-edges of any frontier vertex, so the per-destination
buckets of a true alltoall would all be identical — the allgather is
that exchange without materializing p copies.)

Static shapes force a capacity: the per-destination buckets hold
``cap_x`` ids (``PlanStatics.cap_x``, planned from the graph degree
stats by ``comm_model.plan_cap_x``).  When ANY processor's frontier
overflows its buckets the level falls back to the dense bitmap
allgather — a per-level hybrid mirroring the paper's direction-
optimizing switch, with the same globally-consistent-predicate
``lax.cond`` discipline as the 2D bitmap fold (collectives in both
branches lower as whole-mesh ops).  Bottom-up levels always take the
dense bitmap: the heuristics only enter bottom-up when the frontier is
large, where the bitmap is the cheaper encoding anyway.

``wire_expand`` records the LIVE ids each level shipped — the alltoallv
volume of the sparse formulation, ``comm_model.sparse_expand_1d_words``
— or the fallback bitmap words (``comm_model.expand_1d_level_words``),
giving the closed form ``comm_model.topdown_1d_words`` its first
measured counterpart.  The static-shape allgather physically moves the
full cap_x-slot buckets, sentinels included
(``comm_model.sparse_expand_padded_words``); ids are i32, so at the
planned crossover capacity the padded buckets cost the same bytes as
the n-bit bitmap — the padding is a wash, and the id counter is the
figure the variable-length exchange of the papers would put on the
wire.  Local discovery is unchanged: the sparse exchange reconstructs
the same packed frontier bitmap, so every "1d" LocalOps entry (dense
edge-parallel, strip-CSR, strip-DCSC Pallas) plugs in as-is.

Two pre-wire reductions from the literature sit on top:

  * **Sieve** (arXiv 1208.5542): the owner masks already-visited
    vertices out of its send set BEFORE packing, so a vertex never hits
    the wire twice.  In this loop the frontier is freshly discovered
    (``newly``), so the sieve removes nothing and parents stay
    bit-identical — but the exchange no longer ASSUMES its input is
    fresh: the overflow predicate, the packed count words, and the
    dense-fallback bitmap all see the sieved set, so any future caller
    with a stale or speculative frontier pays for live vertices only.
  * **Codec** (arXiv 1704.00513 flavor): with ``codec="packed"`` the
    bucket carries count-prefixed BIT-PACKED LOCAL OFFSETS instead of
    raw i32 global ids — ``codec_bits(chunk)`` bits per id (~3x fewer
    bucket bytes at chunk=1024), rebased by the receiver from the
    bucket's position in the tiled allgather
    (``kernels/frontier_codec``: Pallas encode/decode with a jnp
    oracle).  ``wire_expand`` switches to the compressed closed form
    ``comm_model.compressed_expand_1d_words`` on sparse levels;
    ``use_expand`` stays in raw-id units so codecs are comparable.
    The cheaper per-id wire also moves the sparse/dense crossover from
    n_f ~ n/64 to n_f ~ n/bits, so ``plan_cap_x(bits=...)`` plans
    LARGER buckets and more levels stay sparse.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import comm_model
from repro.core.frontier import (INT_INF, pack_bits, pack_ids, unpack_bits,
                                 unpack_ids)
from repro.core.scopes import DISCOVER, EXPAND, FOLD
from repro.core.steps import zero_counters
from repro.core.steps_1d import (bottomup_level_1d, _resolve_ops,
                                 pipelined_expand_consume)

CODECS = ("none", "packed")


class LevelArgs1DS(NamedTuple):
    """Static/per-search context for the sparse-exchange 1D steps.  The
    field set is a superset of LevelArgs1D (same names), so the dense
    bottom-up step and the "1d" LocalOps closures run against it
    unchanged; ``cap_x`` and ``codec`` are the only additions."""
    part: "object"            # Partition1D (static)
    axis: str                 # the single mesh axis name
    cap_x: int                # sparse exchange: ids per send bucket
    local_mode: str = "dense"  # "dense" | "kernel" (Pallas)
    storage: str = "csr"      # "csr" | "dcsc" (strip pointer compression)
    cap_f: int = 0            # kernel csr: frontier capacity (0 = n)
    maxdeg: int = 0           # kernel mode: max column-segment length
    ops: "object" = None      # LocalOps entry (None = look up from strings)
    instrument: bool = True   # False: compile out the counters
    codec: str = "none"       # sparse-bucket encoding: "none" | "packed"
    # software-pipelined expand: C sub-range bucket exchanges per level,
    # each consumed while the next is in flight (1 = classic schedule);
    # must divide chunk/32 and cap_x (plan_bfs validates)
    expand_chunks: int = 1
    interpret: bool = False   # Pallas interpreter (CPU mesh) vs Mosaic


def sparse_exchange_1d(front: jax.Array, axis: str, cap_x: int, part,
                       over=None, instrument: bool = True,
                       visited=None, codec: str = "none",
                       use_kernel: bool = False, interpret: bool = False):
    """Owner-directed sparse frontier exchange with dense fallback.

    Each processor compacts its owned frontier chunk into a
    fixed-capacity bucket (``pack_ids``) and broadcasts it with one
    tiled all_gather; receivers scatter the ids back into the full
    n-vertex packed bitmap (``unpack_ids``).  With the adjacency
    partitioned by DESTINATION, every strip can hold edges out of any
    frontier vertex, so a per-destination alltoall would carry p
    identical buckets — the allgather IS that exchange without
    materializing the copies (a genuinely filtered alltoall needs a
    source-partitioned format; see ROADMAP).  If any processor holds
    more than ``cap_x`` SEND vertices the WHOLE level reverts to the
    dense bitmap (the predicate is pmax-synced, so every device takes
    the same branch and the collectives stay aligned — ids are never
    silently truncated).

    ``visited`` (optional bool[chunk]) is the owner-side sieve: vertices
    already discovered are dropped from the send set before packing,
    before the overflow count, and before the fallback bitmap — the
    whole exchange operates on ``front & ~visited``.  Receivers union
    the result into their view as usual, so sieving visited vertices
    never changes discovery.

    ``codec="packed"`` bit-packs the bucket (count word + local offsets
    at ``codec_bits(chunk)`` bits each; ``kernels/frontier_codec``,
    Pallas when ``use_kernel`` else the jnp oracle; ``interpret`` runs
    the Pallas kernels in the interpreter).  Same single
    allgather — the count rides inside the buffer — so the collective
    budget is unchanged; only the bytes shrink.

    ``over`` may be passed in pre-computed: the instrument=False fast
    path folds the per-processor bucket-overflow indicator into the
    PREVIOUS level's fused reduction (``decomp._search_loop``), so the
    level itself spends no collective on the predicate.  When ``over``
    is None it is derived here with a pmax (the instrumented path —
    still globally consistent, the cond branches contain collectives).

    Returns (f_words uint32[n//32], wire, overflowed bool).  ``wire`` is
    the modeled f32 words this level shipped — compressed or raw sparse
    form per ``codec``, bitmap words on the dense path — or **None**
    when ``instrument=False``: an uninstrumented exchange reports no
    number at all rather than a fake 0 that would poison ``wire_expand``
    aggregates mixing instrumented and fast levels."""
    if codec not in CODECS:
        raise ValueError(f"unknown frontier codec {codec!r}; "
                         f"expected one of {CODECS}")
    p = part.p
    i = lax.axis_index(axis)
    send = front if visited is None else front & ~visited
    if over is None:
        n_local = jnp.sum(send, dtype=jnp.int32)
        # global predicate: the cond branches contain collectives
        over = lax.pmax(n_local, axis) > cap_x

    if codec == "packed":
        from repro.kernels.frontier_codec import ops as codec_ops
        from repro.kernels.frontier_codec import ref as codec_ref
        enc = functools.partial(codec_ops.encode_offsets,
                                interpret=interpret) if use_kernel \
            else codec_ref.encode_offsets
        dec = (lambda r: codec_ops.decode_buckets(
                   r, part.chunk, cap_x, part.n, p, interpret=interpret)) \
            if use_kernel \
            else (lambda r: codec_ref.decode_buckets(
                      r, part.chunk, cap_x, part.n))

        def sparse(f):
            off = pack_ids(f, cap_x, 0, part.chunk)      # local offsets
            buf = enc(off, jnp.sum(f, dtype=jnp.int32), part.chunk)
            recv = lax.all_gather(buf, axis, tiled=True)  # (p*(1+W),)
            return unpack_ids(dec(recv), part.n)
    else:
        def sparse(f):
            ids = pack_ids(f, cap_x, i * part.chunk, part.n)
            recv = lax.all_gather(ids, axis, tiled=True)  # (p*cap_x,)
            return unpack_ids(recv, part.n)

    def dense(f):
        return lax.all_gather(pack_bits(f), axis, tiled=True)

    f_words = lax.cond(over, dense, sparse, send)
    wire = None
    if instrument:
        n_f = lax.psum(jnp.sum(send, dtype=jnp.float32), axis)
        sparse_words = comm_model.compressed_expand_1d_words(
            n_f, p, comm_model.codec_bits(part.chunk)) \
            if codec == "packed" \
            else comm_model.sparse_expand_1d_words(n_f, p)
        wire = jnp.where(
            over,
            jnp.float32(comm_model.expand_1d_level_words(part.n, p)),
            jnp.float32(sparse_words))
    return f_words, wire, over


def _pipelined_topdown_1ds(g, send: jax.Array, over, args: "LevelArgs1DS"):
    """Software-pipelined sparse top-down expand+discover
    (``expand_chunks = C > 1``): the owner's chunk splits into C
    contiguous sub-ranges of ``sub = chunk/C`` vertices, each exchanged
    as its own capacity-``cap_x/C`` bucket allgather and consumed by a
    partial SpMSV while the next exchange is in flight
    (``pipelined_expand_consume``).  Candidates min-combine across
    sub-chunks — exact under the (select-source, min) semiring — so
    parents are bit-identical to the unchunked schedule.

    The overflow predicate becomes "ANY processor's send set exceeds
    cap_x/C in ANY sub-range" — still one globally-consistent scalar
    (the fast path folds it into the previous level's fused reduction
    exactly as before), and the whole level falls back to the CHUNKED
    dense expand, keeping both cond branches at C collectives.  A level
    that fits unchunked can overflow chunked (skewed sub-ranges), which
    changes only which levels pay bitmap words — never parents or the
    direction-mode sequence.

    Every sub-exchange decodes into the same owner-major ``(p * w_sub,)``
    sub-chunk word layout the chunked dense gather produces: raw ids
    rebase ``owner*sub + local``; the packed codec decodes with
    ``chunk=sub, n=p*sub`` so its bucket-position rebase lands there
    natively (offsets narrow to ``codec_bits(sub)`` bits, one count word
    per sub-bucket — see ``comm_model.compressed_expand_1d_words``'s
    n_chunks term).

    Returns (cand, ex_local, wire, over); ``wire`` is None
    uninstrumented."""
    part = args.part
    C = args.expand_chunks
    p = part.p
    sub = part.chunk // C
    cap_c = args.cap_x // C
    axis = args.axis
    i = lax.axis_index(axis)
    use_kernel = args.local_mode == "kernel"

    if over is None:
        counts = jnp.sum(send.reshape(C, sub), axis=1, dtype=jnp.int32)
        # global predicate: the cond branches contain collectives
        over = lax.pmax(jnp.max(counts), axis) > cap_c

    if args.codec == "packed":
        from repro.kernels.frontier_codec import ops as codec_ops
        from repro.kernels.frontier_codec import ref as codec_ref
        enc = functools.partial(codec_ops.encode_offsets,
                                interpret=args.interpret) if use_kernel \
            else codec_ref.encode_offsets
        dec = (lambda r: codec_ops.decode_buckets(
                   r, sub, cap_c, p * sub, p, interpret=args.interpret)) \
            if use_kernel \
            else (lambda r: codec_ref.decode_buckets(r, sub, cap_c,
                                                     p * sub))

        def sub_bucket(m_k, k):
            off = pack_ids(m_k, cap_c, 0, sub)       # sub-range offsets
            buf = enc(off, jnp.sum(m_k, dtype=jnp.int32), sub)
            recv = lax.all_gather(buf, axis, tiled=True)
            return unpack_ids(dec(recv), p * sub)
    else:
        def sub_bucket(m_k, k):
            ids = pack_ids(m_k, cap_c, i * part.chunk + k * sub, part.n)
            recv = lax.all_gather(ids, axis, tiled=True)  # (p*cap_c,)
            owner = recv // part.chunk
            pos = owner * sub + (recv - owner * part.chunk - k * sub)
            return unpack_ids(jnp.where(recv < part.n, pos, p * sub),
                              p * sub)

    def sparse(s):
        subs_mask = s.reshape(C, sub)
        return pipelined_expand_consume(
            g, lambda k: sub_bucket(subs_mask[k], k), C, args)

    def dense(s):
        subs = pack_bits(s).reshape(C, sub // 32)
        return pipelined_expand_consume(
            g, lambda k: lax.all_gather(subs[k], axis, tiled=True), C, args)

    cand, ex = lax.cond(over, dense, sparse, send)

    wire = None
    if args.instrument:
        n_f = lax.psum(jnp.sum(send, dtype=jnp.float32), axis)
        sparse_words = comm_model.compressed_expand_1d_words(
            n_f, p, comm_model.codec_bits(sub), C) \
            if args.codec == "packed" \
            else comm_model.sparse_expand_1d_words(n_f, p)
        wire = jnp.where(
            over,
            jnp.float32(comm_model.chunked_expand_1d_level_words(
                part.n, p, C)),
            jnp.float32(sparse_words))
    return cand, ex, wire, over


def topdown_level_1ds(g: Dict[str, jax.Array], pi: jax.Array,
                      front: jax.Array, args: LevelArgs1DS, lv=None
                      ) -> Tuple[jax.Array, jax.Array, Dict]:
    """One sparse-exchange 1D top-down level: identical to the dense 1D
    level except the expand ships frontier ids (with bitmap fallback).
    ``lv`` (fast path only) carries the bucket-overflow predicate from
    the previous level's fused reduction, so the instrument=False level
    spends its collectives on the exchange alone.

    The sieve mask is ``(pi != -1) & ~front``: everything discovered on
    EARLIER levels.  The frontier itself is excluded — its vertices also
    have parents by now — so in-loop the sieve is the identity on
    ``front`` and parents are bit-identical with it on or off; the
    fast path's overflow count over ``front`` (decomp.reduce_state)
    matches the sieved count for the same reason."""
    part = args.part
    instr = args.instrument
    ctr = zero_counters() if instr else {}
    over = lv["over"] if lv is not None else None
    visited = (pi != -1) & ~front

    if args.expand_chunks > 1:
        # Software pipeline: C sub-range bucket exchanges, each consumed
        # by a partial SpMSV while the next is in flight.
        send = front & ~visited
        cand, ex_local, wire, _ = _pipelined_topdown_1ds(g, send, over,
                                                         args)
    else:
        # --- Expand: owner-directed sparse ids, dense bitmap on
        # overflow --
        with jax.named_scope(EXPAND):
            f_words, wire, _ = sparse_exchange_1d(
                front, args.axis, args.cap_x, part, over=over,
                instrument=instr, visited=visited, codec=args.codec,
                use_kernel=(args.local_mode == "kernel"),
                interpret=args.interpret)
            f_all = unpack_bits(f_words)             # (n,) bool
        # --- Local discovery: unchanged from "1d" (same LocalOps
        # entries) --
        with jax.named_scope(DISCOVER):
            cand, ex_local = _resolve_ops(args).topdown(
                g, f_words, f_all, part.chunk, jnp.int32(0), args)
    if instr:
        ctr["wire_expand"] = wire
        n_f = lax.psum(jnp.sum(front, dtype=jnp.float32), args.axis)
        ctr["use_expand"] = jnp.float32(
            comm_model.sparse_expand_1d_words(n_f, part.p))
        ctr["edges_examined"] = lax.psum(ex_local, args.axis)
        ctr["edges_useful"] = lax.psum(
            jnp.sum(jnp.where(front, g["deg_A"], 0), dtype=jnp.float32),
            args.axis)

    # --- Local update (children are owned; no fold) ----------------------
    with jax.named_scope(FOLD):
        newly = (pi == -1) & (cand != INT_INF)
        pi = jnp.where(newly, cand, pi)
    return pi, newly, ctr


def bottomup_level_1ds(g: Dict[str, jax.Array], pi: jax.Array,
                       front: jax.Array, args: LevelArgs1DS, lv=None
                       ) -> Tuple[jax.Array, jax.Array, Dict]:
    """Bottom-up levels always exchange the dense bitmap: the direction
    heuristic only enters bottom-up on large frontiers, where
    n_f*(p-1) id words would exceed the (p-1)*n/64 bitmap — reusing the
    "1d" step verbatim (the LevelArgs field names line up)."""
    return bottomup_level_1d(g, pi, front, args, lv)


__all__ = ["CODECS", "LevelArgs1DS", "sparse_exchange_1d",
           "topdown_level_1ds", "bottomup_level_1ds"]
