"""Distributed device-side graph construction (born-sharded graphs).

The Graph500 discipline (and the paper's §7 setup) is that *generation
and CSR/DCSC construction are themselves distributed* — the host never
materializes the edge list.  This module builds ``Blocked1DGraph`` /
``BlockedGraph`` shards entirely on device:

  1. **generate** — each device draws its slice [k*m/p, (k+1)*m/p) of
     the counter-based R-MAT stream (graph/rmat.py): the stream is a
     pure function of (seed, edge index), so the union of shard slices
     is bit-identical for every device count p.  On more than one
     shard both endpoints are relabeled by the seeded bijection of
     ``build_relabel``, which spreads R-MAT's low-id hubs over the
     shards; the graph carries it, and the search maps roots in and
     parents out through it, so callers see input labels only.
  2. **owner-route** — every edge is emitted in both directions
     (symmetrization before routing) and shipped to the owner of its
     destination vertex with the same capped-bucket tiled
     ``lax.all_to_all`` idiom the level exchanges use: scatter records
     into a static (p_dest, cap_route) bucket buffer, one all_to_all,
     sentinel-fill past each bucket's count.  The 2D build routes in
     two hops — along the "model" axis to the block *column* owner
     (bj = u // nc), then along "data" to the block *row* owner
     (bi = v // nr) — so each hop is a plain single-axis all_to_all.
     Bucket overflow is detected on device and raised loudly on host
     (``route_slack`` inflates the comm_model.plan_cap_route caps).
  3. **dedup shard-locally** — self-loops were dropped pre-routing;
     received records are lexsorted by (source, local dest) and
     first-occurrence-compacted.  Dedup commutes with owner routing
     (ownership is a function of the edge), so the per-shard edge sets
     are bit-identical to host ``preprocess`` + ``build_blocked*``.
  4. **build formats in place** — CSR/CSC/DCSC/strip-DCSC pointer
     arrays per shard, padded to the global static capacities.

Static shapes force a **two-phase** scheme: phase 1 returns the routed
+ deduped edges (static (p*cap_route,) buffers that stay on device) and
per-shard scalar stats (nnz, nzc, max segment sizes, overflow flags) —
the ONLY values pulled to host; phase 2 consumes the host-planned
capacities (cap, cap_seg, cap_nzc — the same rounding rules as the host
builders) and emits format arrays bit-identical to
``build_blocked_1d`` / ``build_blocked`` on the same edge set.

The resulting graph dataclasses carry sharded ``jax.Array`` fields;
``BFSEngine`` ships them without a host round-trip, so scale 18+ builds
+ traverses where the host path would thrash.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import comm_model
from repro.core.partition import make_partition, make_partition_1d
from repro.graph.formats import Blocked1DGraph, BlockedGraph, _round_up
from repro.graph.rmat import (VertexRelabel, rmat_edges_counter,
                              rmat_edges_counter_jax)
from repro.launch.mesh import COL_AXIS, ROW_AXIS
from repro.runtime.retry import CapacityOverflow, RetryAttempt


@dataclass(frozen=True)
class BuildSpec:
    """Everything that determines the generated graph, hashable into the
    checkpoint store's config hash.  The edge stream is the counter
    stream of ``rmat_edges_counter``; graphs are always symmetrized
    (Graph500 undirected discipline)."""
    scale: int
    edge_factor: int = 16
    seed: int = 1
    a: float = 0.57
    b: float = 0.19
    c: float = 0.19

    @property
    def n(self) -> int:
        return 1 << self.scale

    @property
    def m_input(self) -> int:
        return self.edge_factor << self.scale

    def validate(self):
        if self.scale > 30:
            raise ValueError(f"scale={self.scale} > 30 overflows int32 "
                             f"vertex ids on x64-disabled devices")
        if self.m_input >= 1 << 32:
            raise ValueError(f"m_input={self.m_input} exhausts the uint32 "
                             f"counter space")


def build_relabel(spec: BuildSpec, p: int) -> Optional[VertexRelabel]:
    """The vertex relabel a build over ``p`` shards applies: the seeded
    bijection of ``graph/rmat.py`` when p > 1, where it balances the
    shards' edges, and none on one shard, which has nothing to
    balance."""
    return VertexRelabel.from_seed(spec.scale, spec.seed) if p > 1 else None


def route_caps_2d(spec: BuildSpec, pr: int, pc: int,
                  route_slack: float = 1.5) -> Tuple[int, int]:
    """(cap_r1, cap_r2): the bucket capacities of the 2D build's two
    routing hops, per destination and per device."""
    nrec = 2 * -(-spec.m_input // (pr * pc))
    share_c, share_r = (comm_model.relabeled_route_share(
        q, spec.scale, spec.a, spec.b) for q in (pc, pr))
    cap_r1 = comm_model.plan_cap_route(nrec, pc, share_c,
                                       slack=route_slack)
    # hop 2 buckets the whole column's records by block row: the worst
    # row bucket of the worst column takes share(pr)*share(pc) of the
    # 2*m_input records a processor row generated
    cap_r2 = comm_model.plan_cap_route(int(nrec * pc * share_c), pr,
                                       share_r, slack=route_slack)
    # can't exceed what hop 1 delivers
    return cap_r1, min(cap_r2, _round_up(pc * cap_r1, 32))


def _route(ru, rv, ok, dest, p_dest: int, cap_route: int, axis: str,
           sentinel_u: int, sentinel_v: int):
    """One capped-bucket all_to_all routing round (the MoE/fold idiom):
    scatter records into (p_dest, cap_route) per-destination buckets,
    exchange along ``axis``, return flat received records + overflow
    stats.  Records with ok=False are dropped; bucket slots past a
    bucket's count carry (sentinel_u, sentinel_v)."""
    nrec = ru.shape[0]
    dest = jnp.where(ok, dest, p_dest).astype(jnp.int32)
    counts = jnp.bincount(dest, length=p_dest + 1)
    start_b = jnp.concatenate([jnp.zeros(1, counts.dtype),
                               jnp.cumsum(counts)])
    order = jnp.argsort(dest, stable=True)
    du, dv, dd = ru[order], rv[order], dest[order]
    slot = jnp.arange(nrec, dtype=jnp.int32) - start_b[dd].astype(jnp.int32)
    flat = jnp.where((dd < p_dest) & (slot < cap_route),
                     dd * cap_route + slot, p_dest * cap_route)
    su = jnp.full(p_dest * cap_route, sentinel_u, jnp.int32
                  ).at[flat].set(du, mode="drop")
    sv = jnp.full(p_dest * cap_route, sentinel_v, jnp.int32
                  ).at[flat].set(dv, mode="drop")
    send = jnp.stack([su.reshape(p_dest, cap_route),
                      sv.reshape(p_dest, cap_route)], axis=-1)
    recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
    k = lax.axis_index(axis)
    # wire accounting: records actually destined off-device this round
    sent = jnp.sum(counts[:p_dest]) - counts[k]
    over = jnp.maximum(jnp.max(counts[:p_dest]) - cap_route, 0)
    return (recv[..., 0].reshape(-1), recv[..., 1].reshape(-1),
            sent.astype(jnp.int32), over.astype(jnp.int32))


def _dedup_sorted(u, v, sent_u: int, sent_v: int):
    """Lexsort records by (u, v), drop sentinels + duplicates, compact
    unique records to the front (tail re-sentineled).  Returns compacted
    (u, v) and the unique count."""
    r = u.shape[0]
    order = jnp.lexsort((v, u))        # primary u, secondary v
    su, sv = u[order], v[order]
    valid = su < sent_u
    prev_u = jnp.concatenate([jnp.full(1, -1, su.dtype), su[:-1]])
    prev_v = jnp.concatenate([jnp.full(1, -1, sv.dtype), sv[:-1]])
    uniq = valid & ~((su == prev_u) & (sv == prev_v))
    nnz = jnp.sum(uniq).astype(jnp.int32)
    pos = jnp.where(uniq, jnp.cumsum(uniq) - 1, r)
    cu = jnp.full(r, sent_u, jnp.int32).at[pos].set(su, mode="drop")
    cv = jnp.full(r, sent_v, jnp.int32).at[pos].set(sv, mode="drop")
    return cu, cv, nnz


def _first_occurrence(cu, nnz, n_sentinel: int, cap_nz: int):
    """(jc, cp)-style doubly-compressed pointers over a front-compacted
    primary-sorted array: unique primaries (sentinel-padded) + their
    first-occurrence indices (tail = nnz), matching the host builders'
    np.unique(..., return_index=True) layout."""
    r = cu.shape[0]
    valid = jnp.arange(r) < nnz
    prev = jnp.concatenate([jnp.full(1, -1, cu.dtype), cu[:-1]])
    newcol = valid & (cu != prev)
    # drop index must clear BOTH targets: cp is one entry longer than jc
    colpos = jnp.where(newcol, jnp.cumsum(newcol) - 1, cap_nz + 1)
    jc = jnp.full(cap_nz, n_sentinel, jnp.int32
                  ).at[colpos].set(cu, mode="drop")
    cp = jnp.full(cap_nz + 1, nnz, jnp.int32
                  ).at[colpos].set(jnp.arange(r, dtype=jnp.int32),
                                   mode="drop")
    nzc = jnp.sum(newcol).astype(jnp.int32)
    # per-primary segment lengths -> max column degree
    seg = jnp.where(valid, jnp.cumsum(newcol) - 1, r)
    seg_len = jnp.bincount(seg, length=r + 1)[:r]
    maxdeg = jnp.max(seg_len).astype(jnp.int32)
    return jc, cp, nzc, maxdeg


def _aot(fn, *args, spent: List[float]):
    """``fn`` lowered and compiled ahead of time (or loaded from the
    persistent compilation cache), the seconds it took appended to
    ``spent`` — so a build reports its compile apart from its run."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    spent.append(time.perf_counter() - t0)
    return compiled


def _scatter_front(vals, nnz, cap: int, fill: int = 0):
    """First ``nnz`` entries of ``vals`` into a (cap,) zero/fill-padded
    array (the host builders' zero-padded block rows)."""
    r = vals.shape[0]
    idx = jnp.where(jnp.arange(r) < nnz, jnp.arange(r), cap)
    return jnp.full(cap, fill, jnp.int32).at[idx].set(vals, mode="drop")


# ---------------------------------------------------------------------------
# 1D strip build
# ---------------------------------------------------------------------------


def dist_build_1d(spec: BuildSpec, p: int, mesh, *, align: int = 128,
                  cap_pad: int = 128, route_slack: float = 1.5,
                  row_axis: str = ROW_AXIS,
                  compile_spent: Optional[List[float]] = None,
                  ) -> Tuple[Blocked1DGraph, Dict[str, Any]]:
    """Device-side distributed build of the 1D row-strip format.

    Bit-identical to ``build_blocked_1d`` of the counter edge list
    (``rmat_graph(..., generator="counter")``) relabeled by
    ``build_relabel(spec, p)`` — same edge set, same sort orders, same
    capacity rounding — but no edge array ever exists on host: only
    per-shard scalar stats cross the device boundary.

    Each phase program compiles ahead of its run; the seconds go to
    ``compile_spent`` (a fresh list when None), whose sum is
    ``info["compile_s"]``."""
    spec.validate()
    spent = [] if compile_spent is None else compile_spent
    part = make_partition_1d(spec.n, p, align)
    chunk, n_pad = part.chunk, part.n
    relabel = build_relabel(spec, p)
    m_input = spec.m_input
    m_per = -(-m_input // p)                     # static per-device slice
    nrec = 2 * m_per
    cap_route = comm_model.plan_cap_route(
        nrec, p, comm_model.relabeled_route_share(p, spec.scale, spec.a,
                                                  spec.b),
        slack=route_slack)
    r_buf = p * cap_route

    def phase1():
        k = lax.axis_index(row_axis)
        start = jnp.asarray(k, jnp.uint32) * jnp.uint32(m_per)
        u, v = rmat_edges_counter_jax(spec.scale, m_per, start,
                                      spec.edge_factor, spec.a, spec.b,
                                      spec.c, spec.seed)
        if relabel is not None:
            u, v = relabel.apply(u, jnp), relabel.apply(v, jnp)
        in_stream = (jnp.arange(m_per, dtype=jnp.uint32) + start) \
            < jnp.uint32(m_input)
        # symmetrize pre-routing: both directions of every kept edge
        ru = jnp.concatenate([u, v])
        rv = jnp.concatenate([v, u])
        ok = (ru != rv) & jnp.concatenate([in_stream, in_stream])
        dest = rv // chunk
        gu, gv, sent, over = _route(ru, rv, ok, dest, p, cap_route,
                                    row_axis, n_pad, chunk)
        v_loc = jnp.where(gu < n_pad, gv - dest_base(k), chunk)

        cu, cv, nnz = _dedup_sorted(gu, v_loc, n_pad, chunk)
        valid = jnp.arange(r_buf) < nnz
        prev = jnp.concatenate([jnp.full(1, -1, jnp.int32), cu[:-1]])
        newcol = valid & (cu != prev)
        nzc = jnp.sum(newcol).astype(jnp.int32)
        seg = jnp.where(valid, jnp.cumsum(newcol) - 1, r_buf)
        maxdeg = jnp.max(jnp.bincount(seg, length=r_buf + 1)[:r_buf])
        deg = jnp.bincount(jnp.where(valid, cv, chunk),
                           length=chunk + 1)[:chunk].astype(jnp.int32)
        stats = jnp.stack([nnz, nzc, maxdeg.astype(jnp.int32), over, sent])
        return (cu.reshape(1, r_buf), cv.reshape(1, r_buf),
                deg.reshape(1, chunk), stats.reshape(1, -1))

    def dest_base(k):
        return jnp.asarray(k, jnp.int32) * chunk

    p1 = jax.jit(jax.shard_map(phase1, mesh=mesh, in_specs=(),
                               out_specs=(P(row_axis), P(row_axis),
                                          P(row_axis), P(row_axis)),
                               check_vma=False))
    t0 = time.perf_counter()
    cu_all, cv_all, deg_all, stats_all = _aot(p1, spent=spent)()
    stats = np.asarray(stats_all)                # (p, 5) scalars only
    t1 = time.perf_counter()
    if stats[:, 3].max() > 0:
        raise CapacityOverflow(
            f"1D routing bucket overflow by {int(stats[:, 3].max())} "
            f"records (cap_route={cap_route}); rebuild with a larger "
            f"route_slack (> {route_slack})",
            cap_name="route_slack", cap_value=route_slack)
    nnz = stats[:, 0].astype(np.int64)
    cap = _round_up(max(int(nnz.max()), 1), cap_pad)
    cap_nzc = _round_up(max(int(stats[:, 1].max()), 1), 8)
    maxdeg_col = int(stats[:, 2].max())
    m = int(nnz.sum())

    def phase2(cu, cv, deg):
        cu, cv, deg = cu[0], cv[0], deg[0]
        nnz_l = jnp.sum(cu < n_pad).astype(jnp.int32)
        edge_src = _scatter_front(cu, nnz_l, cap)
        row_idx = _scatter_front(cv, nnz_l, cap)
        # bottom-up orientation: CSR by local dest row
        order = jnp.lexsort((cu, cv))
        bu, bv = cu[order], cv[order]
        col_idx = _scatter_front(bu, nnz_l, cap)
        edge_dst = _scatter_front(bv, nnz_l, cap)
        cnt = jnp.bincount(cv, length=chunk + 1)[:chunk]
        row_ptr = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(cnt).astype(jnp.int32)])
        jc, cp, nzc_l, _ = _first_occurrence(cu, nnz_l, n_pad, cap_nzc)
        one = lambda x: x.reshape((1,) + x.shape)
        return (one(edge_src), one(row_idx), one(row_ptr), one(col_idx),
                one(edge_dst), one(jc), one(cp), nnz_l.reshape(1),
                nzc_l.reshape(1), one(deg))

    p2 = jax.jit(jax.shard_map(
        phase2, mesh=mesh,
        in_specs=(P(row_axis), P(row_axis), P(row_axis)),
        out_specs=tuple(P(row_axis) for _ in range(10)),
        check_vma=False))
    (edge_src, row_idx, row_ptr, col_idx, edge_dst, jc, cp,
     nnz_d, nzc_d, deg_A) = _aot(p2, cu_all, cv_all, deg_all,
                                 spent=spent)(cu_all, cv_all, deg_all)
    jax.block_until_ready(edge_src)
    t2 = time.perf_counter()

    graph = Blocked1DGraph(
        part=part, m_input=m_input, m=m,
        edge_src=edge_src, row_idx=row_idx, row_ptr=row_ptr,
        col_idx=col_idx, edge_dst=edge_dst, jc=jc, cp=cp,
        nnz=nnz_d, nzc=nzc_d, deg_A=deg_A,
        cap=cap, cap_nzc=cap_nzc, maxdeg_col=maxdeg_col, col_ptr=None,
        relabel=relabel)
    info = {
        "build_s": t2 - t0, "gen_route_s": t1 - t0, "format_s": t2 - t1,
        "compile_s": sum(spent),
        "cap_route": cap_route, "m": m, "m_input": m_input,
        "build_teps": m_input / max(t2 - t0, 1e-12),
        "route_words_measured": float(stats[:, 4].sum()),
        "route_words_expected": comm_model.build_route_1d_words(m_input, p),
        "route_words_padded": comm_model.build_route_padded_words(
            p, cap_route),
    }
    return graph, info


# ---------------------------------------------------------------------------
# 2D checkerboard build
# ---------------------------------------------------------------------------


def dist_build_2d(spec: BuildSpec, pr: int, pc: int, mesh, *,
                  align: int = 128, cap_pad: int = 128,
                  route_slack: float = 1.5, row_axis: str = ROW_AXIS,
                  col_axis: str = COL_AXIS,
                  compile_spent: Optional[List[float]] = None,
                  ) -> Tuple[BlockedGraph, Dict[str, Any]]:
    """Device-side distributed build of the 2D (pr x pc) checkerboard,
    bit-identical to ``build_blocked`` on the counter edge stream
    relabeled by ``build_relabel(spec, pr * pc)``.

    Owner routing is TWO single-axis hops (column owner along "model",
    then row owner along "data") instead of one p-way exchange — each
    hop is the same capped-bucket all_to_all as the 1D build, and the
    closed form is comm_model.build_route_2d_words.  Compile seconds
    go to ``compile_spent`` as in ``dist_build_1d``."""
    spec.validate()
    spent = [] if compile_spent is None else compile_spent
    part = make_partition(spec.n, pr, pc, align)
    nr, nc, chunk, p = part.nr, part.nc, part.chunk, part.p
    n_pad = part.n
    relabel = build_relabel(spec, p)
    m_input = spec.m_input
    m_per = -(-m_input // p)
    cap_r1, cap_r2 = route_caps_2d(spec, pr, pc, route_slack)
    r_buf = pr * cap_r2

    def phase1():
        i = lax.axis_index(row_axis)
        j = lax.axis_index(col_axis)
        k = i * pc + j
        start = jnp.asarray(k, jnp.uint32) * jnp.uint32(m_per)
        u, v = rmat_edges_counter_jax(spec.scale, m_per, start,
                                      spec.edge_factor, spec.a, spec.b,
                                      spec.c, spec.seed)
        if relabel is not None:
            u, v = relabel.apply(u, jnp), relabel.apply(v, jnp)
        in_stream = (jnp.arange(m_per, dtype=jnp.uint32) + start) \
            < jnp.uint32(m_input)
        ru = jnp.concatenate([u, v])
        rv = jnp.concatenate([v, u])
        ok = (ru != rv) & jnp.concatenate([in_stream, in_stream])
        # hop 1: to block-column owner bj = u // nc along the model axis
        g1u, g1v, sent1, over1 = _route(ru, rv, ok, ru // nc, pc, cap_r1,
                                        col_axis, n_pad, n_pad)
        ok1 = g1u < n_pad
        # hop 2: to block-row owner bi = v // nr along the data axis
        g2u, g2v, sent2, over2 = _route(g1u, g1v, ok1, g1v // nr, pr,
                                        cap_r2, row_axis, n_pad, n_pad)
        ok2 = g2u < n_pad
        u_loc = jnp.where(ok2, g2u - j * nc, nc)
        v_loc = jnp.where(ok2, g2v - i * nr, nr)

        # dedup in CSC order (primary u_loc, secondary v_loc)
        cu, cv, nnz = _dedup_sorted(u_loc, v_loc, nc, nr)
        valid = jnp.arange(r_buf) < nnz
        prev = jnp.concatenate([jnp.full(1, -1, jnp.int32), cu[:-1]])
        newc = valid & (cu != prev)
        nzc = jnp.sum(newc).astype(jnp.int32)
        segc = jnp.where(valid, jnp.cumsum(newc) - 1, r_buf)
        maxdeg = jnp.max(jnp.bincount(segc, length=r_buf + 1)[:r_buf])
        # CSR-side stats: row counts give nzr + the max chunk-segment
        rcnt = jnp.bincount(jnp.where(valid, cv, nr), length=nr + 1)[:nr]
        nzr = jnp.sum(rcnt > 0).astype(jnp.int32)
        max_seg = jnp.max(jnp.sum(rcnt.reshape(pc, chunk), axis=1))
        # degree: strip in-degree (psum over the block row) sliced to
        # this device's layout-A chunk (i*pc+j <-> strip offset j*chunk)
        strip_deg = lax.psum(rcnt, col_axis)
        deg = lax.dynamic_slice(strip_deg, (j * chunk,), (chunk,))
        stats = jnp.stack([nnz, nzc, nzr, maxdeg.astype(jnp.int32),
                           max_seg.astype(jnp.int32), over1 + over2,
                           sent1 + sent2])
        return (cu.reshape(1, 1, r_buf), cv.reshape(1, 1, r_buf),
                deg.reshape(1, 1, chunk).astype(jnp.int32),
                stats.reshape(1, 1, -1))

    axes = (row_axis, col_axis)
    p1 = jax.jit(jax.shard_map(phase1, mesh=mesh, in_specs=(),
                               out_specs=tuple(P(*axes) for _ in range(4)),
                               check_vma=False))
    t0 = time.perf_counter()
    cu_all, cv_all, deg_all, stats_all = _aot(p1, spent=spent)()
    stats = np.asarray(stats_all).reshape(p, -1)
    t1 = time.perf_counter()
    if stats[:, 5].max() > 0:
        raise CapacityOverflow(
            f"2D routing bucket overflow by {int(stats[:, 5].max())} "
            f"records (cap_r1={cap_r1}, cap_r2={cap_r2}); rebuild with "
            f"a larger route_slack (> {route_slack})",
            cap_name="route_slack", cap_value=route_slack)
    nnz = stats[:, 0].astype(np.int64)
    cap = _round_up(max(int(nnz.max()), 1), cap_pad)
    cap_nzc = _round_up(max(int(stats[:, 1].max()), 1), 8)
    cap_nzr = _round_up(max(int(stats[:, 2].max()), 1), 8)
    maxdeg_col = int(stats[:, 3].max())
    cap_seg = _round_up(max(int(stats[:, 4].max()), 1), cap_pad)
    m = int(nnz.sum())

    def phase2(cu, cv, deg):
        cu, cv, deg = cu[0, 0], cv[0, 0], deg[0, 0]
        nnz_l = jnp.sum(cu < nc).astype(jnp.int32)
        # CSC orientation (already sorted by u_loc, v_loc)
        edge_src = _scatter_front(cu, nnz_l, cap)
        row_idx = _scatter_front(cv, nnz_l, cap)
        ccnt = jnp.bincount(cu, length=nc + 1)[:nc]
        col_ptr = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(ccnt).astype(jnp.int32)])
        jc, cp, _, _ = _first_occurrence(cu, nnz_l, nc, cap_nzc)
        # CSR orientation
        order = jnp.lexsort((cu, cv))
        bu, bv = cu[order], cv[order]
        col_idx = _scatter_front(bu, nnz_l, cap + cap_seg)
        edge_dst = _scatter_front(bv, nnz_l, cap + cap_seg)
        rcnt = jnp.bincount(cv, length=nr + 1)[:nr]
        row_ptr = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(rcnt).astype(jnp.int32)])
        jr, rp, _, _ = _first_occurrence(bv, nnz_l, nr, cap_nzr)
        seg_ptr = row_ptr[jnp.arange(pc + 1) * chunk]
        nzc_l = jnp.sum(ccnt > 0).astype(jnp.int32)
        nzr_l = jnp.sum(rcnt > 0).astype(jnp.int32)
        one = lambda x: x.reshape((1, 1) + x.shape)
        return (one(col_ptr), one(row_idx), one(edge_src), one(row_ptr),
                one(col_idx), one(edge_dst), one(seg_ptr), one(jc),
                one(cp), one(jr), one(rp), nnz_l.reshape(1, 1),
                nzc_l.reshape(1, 1), nzr_l.reshape(1, 1), one(deg))

    p2 = jax.jit(jax.shard_map(
        phase2, mesh=mesh, in_specs=tuple(P(*axes) for _ in range(3)),
        out_specs=tuple(P(*axes) for _ in range(15)),
        check_vma=False))
    (col_ptr, row_idx, edge_src, row_ptr, col_idx, edge_dst, seg_ptr,
     jc, cp, jr, rp, nnz_d, nzc_d, nzr_d, deg_A) = _aot(
        p2, cu_all, cv_all, deg_all, spent=spent)(cu_all, cv_all, deg_all)
    jax.block_until_ready(row_idx)
    t2 = time.perf_counter()

    graph = BlockedGraph(
        part=part, m_input=m_input, m=m,
        col_ptr=col_ptr, row_idx=row_idx, edge_src=edge_src,
        row_ptr=row_ptr, col_idx=col_idx, edge_dst=edge_dst,
        seg_ptr=seg_ptr, jc=jc, cp=cp, jr=jr, rp=rp,
        nnz=nnz_d, nzc=nzc_d, nzr=nzr_d, deg_A=deg_A,
        cap=cap, cap_seg=cap_seg, maxdeg_col=maxdeg_col, relabel=relabel)
    info = {
        "build_s": t2 - t0, "gen_route_s": t1 - t0, "format_s": t2 - t1,
        "compile_s": sum(spent),
        "cap_route": (cap_r1, cap_r2), "m": m, "m_input": m_input,
        "build_teps": m_input / max(t2 - t0, 1e-12),
        "route_words_measured": float(stats[:, 6].sum()),
        "route_words_expected": comm_model.build_route_2d_words(
            m_input, pr, pc),
        "route_words_padded": comm_model.build_route_padded_words(
            pc, cap_r1) + comm_model.build_route_padded_words(pr, cap_r2),
    }
    return graph, info


def dist_build(spec: BuildSpec, decomposition: str, mesh, grid,
               max_attempts: int = 3, **kw):
    """Dispatch on decomposition: "1d"/"1ds" build the strip format on
    p = prod(grid) devices, "2d" the checkerboard.  ``grid`` is (pr, pc),
    or an int / 1-tuple p for the 1D formats.

    Routing-bucket overflow self-heals: the single-shot builders
    (``dist_build_1d`` / ``dist_build_2d``) still raise
    ``CapacityOverflow`` loudly, but this dispatcher catches it,
    doubles ``route_slack``, and rebuilds — at most ``max_attempts``
    total attempts, each recorded in ``info["retry_log"]`` (empty when
    the first attempt routes clean).  The rebuilt graph is bit-identical
    to a first-try build with the final slack: the edge stream is a
    pure function of (seed, edge index) and slack only sizes the
    exchange buckets.  Exhaustion re-raises with the full escalation
    history attached.  ``info["compile_s"]`` sums the phase compiles
    (or persistent-cache loads) of every attempt."""
    if isinstance(grid, int):
        grid = (grid, 1)
    elif len(grid) == 1:
        grid = (grid[0], 1)
    pr, pc = grid
    if decomposition in ("1d", "1ds"):
        build = lambda **k: dist_build_1d(spec, pr * pc, mesh, **k)
    elif decomposition == "2d":
        build = lambda **k: dist_build_2d(spec, pr, pc, mesh, **k)
    else:
        raise ValueError(f"unknown decomposition {decomposition!r}")

    slack = float(kw.pop("route_slack", 1.5))
    history = []
    spent = []
    for attempt in range(1, max(1, max_attempts) + 1):
        try:
            graph, info = build(route_slack=slack, compile_spent=spent,
                                **kw)
        except CapacityOverflow as e:
            history.append(RetryAttempt(
                attempt=attempt, cap_name="route_slack", cap_value=slack,
                outcome="overflow", detail={"error": str(e)}))
            if attempt >= max(1, max_attempts):
                raise CapacityOverflow(
                    f"routing overflow persisted through {attempt} build "
                    f"attempts: {e}", cap_name="route_slack",
                    cap_value=slack, history=history) from e
            slack *= 2.0
            continue
        if history:
            history.append(RetryAttempt(
                attempt=attempt, cap_name="route_slack", cap_value=slack,
                outcome="ok", detail={}))
        info["retry_log"] = [a.to_json() for a in history]
        return graph, info


# ---------------------------------------------------------------------------
# Host shard regeneration (GraphStore integrity repair)
# ---------------------------------------------------------------------------
#
# A corrupted or truncated store shard is regenerated from the SAME
# counter stream the device build consumed: ``rmat_edges_counter`` is a
# pure function of (seed, edge index), and shard contents depend only on
# the edge subset owned by that shard — so the host twin below filters
# the full stream (relabeled as the build relabeled it) down to one
# shard's edges and replays phases 1+2 with numpy, producing arrays
# bit-identical to the device build (the store re-checks the stored CRC
# after regeneration to prove it).

_REGEN_STEP = 1 << 22     # stream chunking: bounds peak host memory


def _pad_i32(vals, cap: int, fill: int = 0) -> np.ndarray:
    out = np.full(cap, fill, np.int32)
    out[: len(vals)] = vals
    return out


def _shard_edges(spec: BuildSpec, p: int,
                 keep) -> Tuple[np.ndarray, np.ndarray]:
    """Deduped (u, v) int64 pairs of the symmetrized self-loop-free
    stream of a ``p``-shard build for which ``keep(u, v)`` holds, sorted
    by (u, v) — the CSC dedup order of ``_dedup_sorted``."""
    us, vs = [], []
    relabel = build_relabel(spec, p)
    for s in range(0, spec.m_input, _REGEN_STEP):
        cnt = min(_REGEN_STEP, spec.m_input - s)
        u, v = rmat_edges_counter(spec.scale, spec.edge_factor, spec.a,
                                  spec.b, spec.c, spec.seed, start=s,
                                  count=cnt)
        if relabel is not None:
            u, v = relabel.apply(u), relabel.apply(v)
        for a, b in ((u, v), (v, u)):
            mask = (a != b) & keep(a, b)
            if mask.any():
                us.append(a[mask])
                vs.append(b[mask])
    if not us:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64))
    pairs = np.stack([np.concatenate(us), np.concatenate(vs)], axis=1)
    pairs = np.unique(pairs, axis=0)     # lexsort by (u, v) + dedup
    return pairs[:, 0], pairs[:, 1]


def regen_shard_1d(spec: BuildSpec, part, k: int, *, cap: int,
                   cap_nzc: int) -> Dict[str, np.ndarray]:
    """Strip ``k``'s Blocked1DGraph arrays (shard slice, no leading
    block dim), bit-identical to ``dist_build_1d`` phase 2."""
    chunk, n_pad = part.chunk, part.n
    lo = k * chunk
    gu, gv = _shard_edges(spec, part.p,
                          lambda a, b: (b >= lo) & (b < lo + chunk))
    u = gu.astype(np.int32)
    v = (gv - lo).astype(np.int32)
    nnz = len(u)
    order = np.lexsort((u, v))           # CSR: primary v, secondary u
    cnt = np.bincount(v, minlength=chunk)[:chunk] if nnz \
        else np.zeros(chunk, np.int64)
    uu, fi = (np.unique(u, return_index=True) if nnz
              else (np.zeros(0, np.int32), np.zeros(0, np.int64)))
    cp = np.full(cap_nzc + 1, nnz, np.int32)
    cp[: len(fi)] = fi.astype(np.int32)
    # the optional uncompressed strip CSC pointer (host builds with
    # with_col_ptr=True persist it; regen_shard filters to the stored
    # field set)
    col_ptr = np.zeros(n_pad + 1, np.int64)
    col_ptr[1:] = np.cumsum(np.bincount(u, minlength=n_pad)[:n_pad]) \
        if nnz else 0
    return {
        "col_ptr": col_ptr.astype(np.int32),
        "edge_src": _pad_i32(u, cap),
        "row_idx": _pad_i32(v, cap),
        "row_ptr": np.concatenate(
            [[0], np.cumsum(cnt)]).astype(np.int32),
        "col_idx": _pad_i32(u[order], cap),
        "edge_dst": _pad_i32(v[order], cap),
        "jc": _pad_i32(uu, cap_nzc, fill=n_pad),
        "cp": cp,
        "nnz": np.int32(nnz),
        "nzc": np.int32(len(uu)),
        "deg_A": cnt.astype(np.int32),
    }


def regen_shard_2d(spec: BuildSpec, part, i: int, j: int, *, cap: int,
                   cap_seg: int, cap_nzc: int,
                   cap_nzr: int) -> Dict[str, np.ndarray]:
    """Block ``(i, j)``'s BlockedGraph arrays (shard slice, no leading
    block dims), bit-identical to ``dist_build_2d`` phase 2."""
    nr, nc, chunk, pc = part.nr, part.nc, part.chunk, part.pc
    gu, gv = _shard_edges(
        spec, part.p, lambda a, b: (a // nc == j) & (b // nr == i))
    u = (gu - j * nc).astype(np.int32)
    v = (gv - i * nr).astype(np.int32)
    nnz = len(u)
    ccnt = np.bincount(u, minlength=nc)[:nc] if nnz \
        else np.zeros(nc, np.int64)
    rcnt = np.bincount(v, minlength=nr)[:nr] if nnz \
        else np.zeros(nr, np.int64)
    uu, fiu = (np.unique(u, return_index=True) if nnz
               else (np.zeros(0, np.int32), np.zeros(0, np.int64)))
    cp = np.full(cap_nzc + 1, nnz, np.int32)
    cp[: len(fiu)] = fiu.astype(np.int32)
    order = np.lexsort((u, v))           # CSR: primary v, secondary u
    bv = v[order]
    vv, fiv = (np.unique(bv, return_index=True) if nnz
               else (np.zeros(0, np.int32), np.zeros(0, np.int64)))
    rp = np.full(cap_nzr + 1, nnz, np.int32)
    rp[: len(fiv)] = fiv.astype(np.int32)
    row_ptr = np.concatenate([[0], np.cumsum(rcnt)]).astype(np.int32)
    # deg_A: whole-row strip in-degree sliced to this block's layout-A
    # chunk — needs edges from EVERY column block of row i
    dlo = i * nr + j * chunk
    du, dv = _shard_edges(
        spec, part.p, lambda a, b: (b >= dlo) & (b < dlo + chunk))
    deg = (np.bincount((dv - dlo).astype(np.int64),
                       minlength=chunk)[:chunk] if len(dv)
           else np.zeros(chunk, np.int64))
    return {
        "col_ptr": np.concatenate(
            [[0], np.cumsum(ccnt)]).astype(np.int32),
        "row_idx": _pad_i32(v, cap),
        "edge_src": _pad_i32(u, cap),
        "row_ptr": row_ptr,
        "col_idx": _pad_i32(u[order], cap + cap_seg),
        "edge_dst": _pad_i32(bv, cap + cap_seg),
        "seg_ptr": row_ptr[np.arange(pc + 1) * chunk],
        "jc": _pad_i32(uu, cap_nzc, fill=nc),
        "cp": cp,
        "jr": _pad_i32(vv, cap_nzr, fill=nr),
        "rp": rp,
        "nnz": np.int32(nnz),
        "nzc": np.int32(int(np.sum(ccnt > 0))),
        "nzr": np.int32(int(np.sum(rcnt > 0))),
        "deg_A": deg.astype(np.int32),
    }


def regen_shard(spec: BuildSpec, graph_kind: str, part, shard: int,
                scalars: Dict[str, int],
                fields: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Regenerate one store shard from its BuildSpec + stored geometry.

    ``shard`` is the flat shard index (k for strips, i*pc + j for the
    checkerboard); ``scalars``/``fields`` are the store meta entries
    (fields supply the capacities the scalars don't carry:
    cap_nzc/cap_nzr from the jc/jr shapes).  Returns only the arrays
    named in ``fields``."""
    if graph_kind == "Blocked1DGraph":
        arrs = regen_shard_1d(
            spec, part, shard, cap=int(scalars["cap"]),
            cap_nzc=int(fields["jc"][0][-1]))
    elif graph_kind == "BlockedGraph":
        arrs = regen_shard_2d(
            spec, part, shard // part.pc, shard % part.pc,
            cap=int(scalars["cap"]), cap_seg=int(scalars["cap_seg"]),
            cap_nzc=int(fields["jc"][0][-1]),
            cap_nzr=int(fields["jr"][0][-1]))
    else:
        raise ValueError(f"cannot regenerate shards of {graph_kind!r}")
    return {k: arrs[k] for k in fields}
