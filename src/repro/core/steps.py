"""Per-level BFS steps: parallel 2D top-down (Alg. 3) and bottom-up
(Alg. 4), written for shard_map bodies over mesh axes (row, col) = the
paper's (pr, pc) processor grid.

Conventions (see core/partition.py):
  * block at device (i,j) = T[R_i, C_j], T[v,u]=1 iff edge u->v
  * parents pi / frontier f are layout-A chunks of size ``chunk``
  * expand allgathers the C_j frontier slice along mesh axis ``row``
  * fold exchanges candidate parents along mesh axis ``col``
  * bottom-up rotates the completed bitmap along ``col`` (pc sub-steps)

Counters (dict of f32 scalars, *global* paper-units: 1 id = 1 word,
1 bitmap bit = 1/64 word):
  wire_*   what our static-shape implementation actually moves
  use_*    the paper's sparse-equivalent volume (for Eq.2 validation)
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import comm_model
from repro.core.frontier import (INT_INF, expand_bitmap, pack_bits,
                                 unpack_bits)
from repro.core.scopes import DISCOVER, EDGE_ROWS, EXPAND, FOLD, UPDATE

COUNTER_KEYS = ("wire_transpose", "wire_expand", "wire_fold", "wire_rotate",
                "wire_updates", "use_expand", "use_fold", "use_rotate",
                "use_updates", "edges_examined", "edges_useful")


def zero_counters() -> Dict[str, jax.Array]:
    return {k: jnp.float32(0) for k in COUNTER_KEYS}


class LevelArgs(NamedTuple):
    """Static/per-search context threaded into level steps."""
    part: "object"            # Partition2D (static)
    row_axis: str
    col_axis: str
    fold_mode: str            # "alltoall" | "reduce"
    perm: tuple               # transpose perm A->B
    cap_seg: int = 0          # static bottom-up sub-step edge window
    local_mode: str = "dense"  # "dense" | "kernel" (Pallas)
    storage: str = "csr"      # "csr" | "dcsc" (kernel pointer indirection)
    cap_f: int = 0            # kernel mode: frontier capacity (0 = nc)
    maxdeg: int = 0           # kernel mode: max column-segment length
    cap_w: int = 0            # bitmap fold: winner capacity (0 = chunk//16)
    compact_updates: bool = False  # bottom-up: compact (child,parent) sends
    cap_u: int = 0            # compact updates capacity (0 = chunk//8)
    ops: "object" = None      # LocalOps entry (None = look up from strings)
    instrument: bool = True   # False: compile out the counters
    #                           (the latency-lean fast path; parents
    #                           identical, ctr returned empty)
    # > 1 switches the bottom-up systolic rotation to the software-
    # pipelined R/G split ring (see bottomup_level); the value itself is
    # a toggle for 2D — the chunk count only shapes the 1d/1ds expand
    expand_chunks: int = 1
    interpret: bool = False   # Pallas interpreter (CPU mesh) vs Mosaic


def _resolve_ops(args: "LevelArgs"):
    """The LocalOps entry for this step config (builders pass it
    pre-resolved; direct LevelArgs constructions fall back to the
    registry lookup on the string fields)."""
    if args.ops is not None:
        return args.ops
    from repro.core.local_ops import get_local_ops
    return get_local_ops("2d", args.local_mode, args.storage)


# ---------------------------------------------------------------------------
# Top-down (Algorithm 3)
# ---------------------------------------------------------------------------


def _fold_alltoall(cand: jax.Array, pc: int, chunk: int, col_axis: str):
    """Paper-faithful fold: Alltoall along the processor row + local min."""
    t = cand.reshape(pc, chunk)
    r = lax.all_to_all(t, col_axis, split_axis=0, concat_axis=0, tiled=False)
    return jnp.min(r, axis=0)


def _fold_bitmap(cand: jax.Array, pc: int, chunk: int, col_axis: str,
                 cap_w: int):
    """Beyond-paper fold: exchange *presence bitmaps* instead of dense
    candidate arrays, then fetch only the winners' parent ids
    (Checconi-style single-parent-update, restructured for static shapes).

    Round 1: all_to_all of packed candidate-presence bitmaps
             (nr/64 words vs nr words dense -> 64x smaller).
    Round 2: owners pick the lowest source column with a bit set and
             return per-source winner bitmaps (again nr/64 words).
    Round 3: each source compacts the parent ids it won (static cap
             ``cap_w`` per destination chunk; overflow falls back to the
             dense fold via lax.cond) and two all_to_alls deliver the
             winner values + their local offsets.

    Wire per level (the ``comm_model.fold_bitmap_level_words`` closed
    form): 2 bitmap rounds + 2 id exchanges = 2*nr/64 + 2*pc*cap_w words
    per device, vs nr dense.  With cap_w = chunk/4: ~3.4x less fold
    traffic at pc=16."""
    present = cand != INT_INF                         # (nr,)
    pb = pack_bits(present).reshape(pc, chunk // 32)
    # round 1: per-source presence bitmaps for each destination chunk
    recv = lax.all_to_all(pb, col_axis, split_axis=0, concat_axis=0)
    bits = unpack_bits(recv.reshape(-1)).reshape(pc, chunk)  # src j -> bit
    # owner picks winner source column = lowest j with a bit
    j_idx = jnp.arange(pc)[:, None]
    winner = jnp.min(jnp.where(bits, j_idx, pc), axis=0)     # (chunk,)
    # round 2: tell each source which vertices it won
    win_bits = winner[None, :] == j_idx                      # (pc, chunk)
    wb = pack_bits(win_bits.reshape(-1)).reshape(pc, chunk // 32)
    back = lax.all_to_all(wb, col_axis, split_axis=0, concat_axis=0)
    my_wins = unpack_bits(back.reshape(-1)).reshape(pc, chunk)  # dest q
    # round 3: compact won parent ids per destination chunk.
    # jnp.where(..., size=k) returns win positions in ASCENDING order
    # (fills at the end), so the rank of a win within its destination
    # chunk is its global position minus the win count of all earlier
    # chunks — one cumsum over per-chunk counts, O(nr) on the hot fold
    # path instead of the former argsort+searchsorted O(nr log nr).
    flat_wins = my_wins.reshape(-1)                           # (nr,)
    idx_s = jnp.where(flat_wins, size=pc * cap_w, fill_value=-1)[0]
    counts = jnp.sum(my_wins, axis=1)                         # per-dest wins
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    q_s = jnp.where(idx_s >= 0, idx_s // chunk, pc)
    rank = (jnp.arange(idx_s.size, dtype=jnp.int32)
            - starts[jnp.minimum(q_s, pc - 1)].astype(jnp.int32))
    ok = (idx_s >= 0) & (rank < cap_w)
    vals = jnp.where(ok, cand[jnp.maximum(idx_s, 0)], INT_INF)
    offs = jnp.where(ok, idx_s % chunk, chunk)                # local offset
    send_v = jnp.full((pc, cap_w), INT_INF, jnp.int32).at[
        jnp.where(ok, q_s, pc), jnp.where(ok, rank, 0)].set(vals, mode="drop")
    send_o = jnp.full((pc, cap_w), chunk, jnp.int32).at[
        jnp.where(ok, q_s, pc), jnp.where(ok, rank, 0)].set(
        offs.astype(jnp.int32), mode="drop")
    rv = lax.all_to_all(send_v, col_axis, split_axis=0, concat_axis=0)
    ro = lax.all_to_all(send_o, col_axis, split_axis=0, concat_axis=0)
    t = jnp.full((chunk,), INT_INF, jnp.int32).at[
        ro.reshape(-1)].min(rv.reshape(-1), mode="drop")
    return t, my_wins


def _fold_ring_reduce(cand: jax.Array, pc: int, chunk: int, col_axis: str):
    """Bandwidth-optimal ring reduce-scatter in the (min) semiring: pc-1
    neighbor hops on the torus instead of a full all-to-all (beyond-paper:
    contention-free on ICI, in-network combining of duplicate updates)."""
    if pc == 1:
        return cand.reshape(pc, chunk)[0]
    acc = cand.reshape(pc, chunk)
    j = lax.axis_index(col_axis)
    perm = [(q, (q + 1) % pc) for q in range(pc)]
    for t in range(pc - 1):
        idx_s = (j - t - 1) % pc
        piece = lax.dynamic_slice_in_dim(acc, idx_s, 1, axis=0)
        recv = lax.ppermute(piece, col_axis, perm)
        idx_r = (j - t - 2) % pc
        cur = lax.dynamic_slice_in_dim(acc, idx_r, 1, axis=0)
        acc = lax.dynamic_update_slice_in_dim(
            acc, jnp.minimum(cur, recv), idx_r, axis=0)
    out = lax.dynamic_slice_in_dim(acc, j % pc, 1, axis=0)
    return out[0]


def topdown_level(g: Dict[str, jax.Array], pi: jax.Array, front: jax.Array,
                  args: LevelArgs, lv=None
                  ) -> Tuple[jax.Array, jax.Array, Dict]:
    """One top-down level. g holds the local block arrays (squeezed).
    ``lv`` is the fast-path per-level context from ``_search_loop``
    (unused by the 2D steps); with ``args.instrument`` False every
    counter psum is compiled out and ``ctr`` comes back empty."""
    part = args.part
    pr, pc, chunk, nc, nr = part.pr, part.pc, part.chunk, part.nc, part.nr
    p = float(part.p)
    instr = args.instrument
    ctr = zero_counters() if instr else {}

    # --- Expand: transpose + allgather along processor column ------------
    with jax.named_scope(EXPAND):
        f_words, wire = expand_bitmap(front, args.perm,
                                      (args.row_axis, args.col_axis))
        f_cj = unpack_bits(f_words)                      # (nc,) bool
        if instr:
            n_f = lax.psum(jnp.sum(front, dtype=jnp.float32),
                           (args.row_axis, args.col_axis))
            ctr["wire_transpose"] = jnp.float32(chunk / 64.0) * p
            ctr["wire_expand"] = wire * p - ctr["wire_transpose"]
            ctr["use_expand"] = n_f * (pr - 1)       # sparse ids, replicated

    # --- Local discovery: SpMSV in the (select-source, min) semiring -----
    with jax.named_scope(DISCOVER):
        # format-specific work lives behind the LocalOps entry (CSR/DCSC x
        # dense/kernel); the step only owns the collectives and counters
        j = lax.axis_index(args.col_axis)
        col_offset = (j * nc).astype(jnp.int32)
        cand, ex_local = _resolve_ops(args).topdown(g, f_words, f_cj, nr,
                                                    col_offset, args)
        if instr:
            ctr["edges_examined"] = lax.psum(ex_local,
                                             (args.row_axis, args.col_axis))
            m_f = lax.psum(jnp.sum(jnp.where(front, g["deg_A"], 0),
                                   dtype=jnp.float32),
                           (args.row_axis, args.col_axis))
            ctr["edges_useful"] = m_f

    # --- Fold: exchange candidates along the processor row ---------------
    with jax.named_scope(FOLD):
        if args.fold_mode == "alltoall":
            t = _fold_alltoall(cand, pc, chunk, args.col_axis)
            if instr:
                ctr["wire_fold"] = jnp.float32((pc - 1) * chunk) * p
        elif args.fold_mode in ("bitmap", "bitmap_pure"):
            cap_w = args.cap_w or max(chunk // 16, 32)
            t, my_wins = _fold_bitmap(cand, pc, chunk, args.col_axis, cap_w)
            if args.fold_mode == "bitmap":
                # runtime fallback: a source chunk overflowing cap_w wins
                # re-runs the dense fold (compiled but executed only then).
                # NB: the predicate must be GLOBALLY consistent — the branch
                # contains collectives that lower as whole-mesh ops.
                overflow = lax.pmax(
                    jnp.max(jnp.sum(my_wins, axis=1)),
                    (args.row_axis, args.col_axis)) > cap_w
                t = lax.cond(overflow,
                             lambda c: _fold_alltoall(c, pc, chunk,
                                                      args.col_axis),
                             lambda c: t, cand)
            if instr:
                ctr["wire_fold"] = jnp.float32(
                    comm_model.fold_bitmap_level_words(pc * chunk, pc,
                                                       cap_w)) * p
        else:
            t = _fold_ring_reduce(cand, pc, chunk, args.col_axis)
            if instr:
                ctr["wire_fold"] = jnp.float32((pc - 1) * chunk) * p
        if instr:
            n_cand = lax.psum(jnp.sum(cand != INT_INF, dtype=jnp.float32),
                              (args.row_axis, args.col_axis))
            ctr["use_fold"] = 2.0 * n_cand           # (child, parent) pairs

        # --- Local update -------------------------------------------------
        newly = (pi == -1) & (t != INT_INF)
        pi = jnp.where(newly, t, pi)
    return pi, newly, ctr


# ---------------------------------------------------------------------------
# Bottom-up (Algorithm 4)
# ---------------------------------------------------------------------------


def bottomup_level(g: Dict[str, jax.Array], pi: jax.Array, front: jax.Array,
                   args: LevelArgs, lv=None
                   ) -> Tuple[jax.Array, jax.Array, Dict]:
    """One bottom-up level: pc sub-steps with systolic rotation of the
    completed bitmap along the processor row (Fig. 1).

    The per-sub-step update exchange is BATCHED: sub-step s discovers
    parents for the segment owned (layout A) by device (j-s) mod pc —
    destination-disjoint by construction — so the segments accumulate in
    a per-destination buffer and ONE tiled all_to_all delivers them at
    level end, replacing pc-1 latency-bound ppermutes (plus, in compact
    mode, pc-1 per-sub-step overflow pmaxes collapse to one).  The cseg
    rotation ppermute is hoisted to the TOP of the next sub-step —
    issued before the graph slicing and the Pallas scan — so an async
    permute can overlap the local work; its payload (the previous
    sub-step's completed|found bits) is unchanged.  Updates are applied
    in the same s-order after the exchange; the carried completed bitmap
    marks each vertex at its first discovery, so every vertex is
    discovered by at most one sub-step and parents are bit-identical to
    the per-sub-step exchange.

    With ``args.expand_chunks > 1`` the rotation is fully SOFTWARE-
    PIPELINED (the generalization of the hoist above): the carried
    bitmap splits into two chains so the permute no longer waits on the
    scan.  The **R chain** is a pure rotation of the PRE-LEVEL completed
    bitmap — its payload exists at sub-step start, so the ppermute has
    no data dependency on the local scan and overlaps it.  The **G
    chain** accumulates this level's finds (G after sub-step s =
    G_before | F_s, rotated alongside R) on a second ppermute whose
    result is consumed only AFTER the scan, as the exactness
    post-filter: the scan runs against the stale R-only bitmap
    (re-scanning rows discovered earlier this level), then
    ``found &= ~G`` masks those re-discoveries out.  Per-row scan
    results are independent of other rows' cvec, so the filtered result
    is bit-identical to the exact-bitmap scan; the instrumented edges
    counter is computed from the exact ``R | G`` union so counters
    match the classic schedule too.  Cost: 2(pc-1) ppermutes per level
    instead of pc-1 (``wire_rotate`` doubles; ``use_rotate`` — the
    semantic payload — does not), bought for the scan-latency overlap
    (``comm_model.level_collective_budget``)."""
    part = args.part
    pr, pc, chunk, nc, nr = part.pr, part.pc, part.chunk, part.nc, part.nr
    p = float(part.p)
    axes = (args.row_axis, args.col_axis)
    instr = args.instrument
    ctr = zero_counters() if instr else {}

    # --- Gather frontier (dense bitmap; per level) ------------------------
    with jax.named_scope(EXPAND):
        f_words, wire = expand_bitmap(front, args.perm, axes)
        if instr:
            ctr["wire_transpose"] = jnp.float32(chunk / 64.0) * p
            ctr["wire_expand"] = wire * p - ctr["wire_transpose"]
            ctr["use_expand"] = jnp.float32(chunk / 64.0 * (1 + (pr - 1))) * p

    with jax.named_scope(DISCOVER):
        j = lax.axis_index(args.col_axis)
        cseg = pi != -1                   # completed = has parent (own chunk)

        rot_perm = [(q, (q + 1) % pc) for q in range(pc)]
        edges_use = jnp.float32(0)

        col_offset = (j * nc).astype(jnp.int32)
        pure = args.fold_mode.endswith("_pure")
        compact = args.compact_updates
        cap_u = args.cap_u or max(chunk // 8, 32)
        ops = _resolve_ops(args)

        # per-destination accumulation for the level-end batched exchange
        # (compact mode never holds sub-step 0: the self segment pays no
        # wire and must not be capacity-truncated — it rides the self slot)
        if compact:
            send_i = jnp.full((pc, cap_u), chunk, jnp.int32)
            send_v = jnp.full((pc, cap_u), INT_INF, jnp.int32)
        if (not compact) or (not pure):
            send_d = jnp.full((pc, chunk), INT_INF, jnp.int32)
        self_par = None
        max_found = jnp.int32(0)
        carry = None
        pipelined = args.expand_chunks > 1
        if pipelined:
            # R/G split ring: R (in ``carry``) rotates the pre-level
            # completed bitmap — a payload with no scan dependency — while
            # g_acc carries the accumulated this-level finds for the
            # post-scan filter.
            carry = pack_bits(cseg)
            g_acc = jnp.zeros((chunk // 32,), jnp.uint32)
        g_seen = None

    for s in range(pc):
        if s > 0:
            # the ring rotation of the completed bitmap is exchange, so it
            # runs under expand: discover covers the local scan alone
            with jax.named_scope(EXPAND):
                # hoisted rotation: issued ahead of this sub-step's slicing
                # and local scan so the async permute overlaps them
                if pipelined:
                    # R is known since the PREVIOUS sub-step's start, so
                    # this permute overlaps the previous scan as well; the
                    # G permute's result is not consumed until after THIS
                    # sub-step's scan — neither blocks the Pallas scan
                    carry = lax.ppermute(carry, args.col_axis, rot_perm)
                    g_in = lax.ppermute(g_acc, args.col_axis, rot_perm)
                    cseg = unpack_bits(carry)
                else:
                    cseg = unpack_bits(lax.ppermute(carry, args.col_axis,
                                                    rot_perm))
                if instr:
                    ctr["wire_rotate"] += jnp.float32(
                        (2 if pipelined else 1) * chunk / 64.0) * p
                    ctr["use_rotate"] += jnp.float32(chunk / 64.0) * p
        elif pipelined:
            g_in = g_acc                  # no prior finds at sub-step 0
        with jax.named_scope(DISCOVER):
            seg_id = (j - s) % pc         # segment V_{i, j-s} this sub-step
            e0 = lax.dynamic_index_in_dim(g["seg_ptr"], seg_id, keepdims=False)
            e1 = lax.dynamic_index_in_dim(g["seg_ptr"], seg_id + 1,
                                          keepdims=False)
            rp_seg = (lax.dynamic_slice_in_dim(g["row_ptr"], seg_id * chunk,
                                               chunk + 1)
                      - e0).astype(jnp.int32)
            ue = lax.dynamic_slice_in_dim(g["col_idx"], e0, args.cap_seg)
            n_edges = (e1 - e0).astype(jnp.int32)
            cvec = cseg.astype(jnp.int32)
            ve = None             # kernel entries find rows inside the scan
            if "edge_dst" in g:
                with jax.named_scope(EDGE_ROWS):
                    # each window edge's row, rebased to this segment
                    ve = (lax.dynamic_slice_in_dim(g["edge_dst"], e0,
                                                   args.cap_seg)
                          - seg_id * chunk)
            seg_par = ops.bottomup(rp_seg, ue, f_words, cvec, col_offset,
                                   n_edges, ve, args)
            found = seg_par != INT_INF
            if pipelined:
                # exactness post-filter: the scan above used the stale
                # R-only bitmap, so rows discovered by earlier sub-steps
                # (the G chain, arriving here — after the scan) may have
                # been re-found; mask them out.  Per-row results are
                # independent of other rows' cvec, so the surviving finds
                # are bit-identical to the exact-bitmap scan.
                g_seen = unpack_bits(g_in)
                found = found & ~g_seen
                seg_par = jnp.where(found, seg_par, INT_INF)
            row_lens = (rp_seg[1:] - rp_seg[:-1]).astype(jnp.float32)
            if instr:
                # scanned-row accounting uses the EXACT completed view (R|G
                # when pipelined) so counters match the classic schedule
                unknown = (cvec == 0) if not pipelined else ~(cseg | g_seen)
                edges_use += lax.psum(
                    jnp.sum(jnp.where(unknown, row_lens, 0.0)), axes)

            # Accumulate the update segment for its layout-A owner (the
            # s=0 self segment never enters the buffers: it pays no wire
            # and lands in the self slot after the exchange)
            if s == 0:
                self_par = seg_par
            else:
                if compact:
                    # beyond-paper: ship only discovered (child, parent)
                    # pairs (static capacity; level-end fallback to the
                    # dense segments)
                    cidx = jnp.where(found, size=cap_u,
                                     fill_value=chunk)[0].astype(jnp.int32)
                    cval = seg_par[jnp.minimum(cidx, chunk - 1)]
                    send_i = lax.dynamic_update_slice(send_i, cidx[None],
                                                      (seg_id, jnp.int32(0)))
                    send_v = lax.dynamic_update_slice(send_v, cval[None],
                                                      (seg_id, jnp.int32(0)))
                    if not pure:
                        max_found = jnp.maximum(
                            max_found, jnp.sum(found, dtype=jnp.int32))
                    if instr:
                        ctr["wire_updates"] += jnp.float32(2 * cap_u) * p
                if (not compact) or (not pure):
                    send_d = lax.dynamic_update_slice(send_d, seg_par[None],
                                                      (seg_id, jnp.int32(0)))
                if instr and not compact:
                    ctr["wire_updates"] += jnp.float32(chunk) * p
            if instr:
                n_upd = lax.psum(jnp.sum(found, dtype=jnp.float32), axes)
                ctr["use_updates"] += 2.0 * n_upd

            # Mark discoveries in the carried bitmap; the rotation itself is
            # issued at the top of the next sub-step (hoisted)
            if pipelined:
                g_acc = pack_bits(g_seen | found)   # R rides carry unchanged
            else:
                cseg = cseg | found
                if s != pc - 1:
                    carry = pack_bits(cseg)

    # --- Batched update exchange (one tiled all_to_all) -------------------
    with jax.named_scope(UPDATE):
        def _a2a(x):
            return lax.all_to_all(x, args.col_axis, split_axis=0,
                                  concat_axis=0)

        def _scatter_compact(si, sv):
            # idx+val ride one exchange; sentinel idx == chunk drops
            r = _a2a(jnp.concatenate([si, sv], axis=1))       # (pc, 2*cap_u)
            rows = jnp.arange(pc, dtype=jnp.int32)[:, None]
            return jnp.full((pc, chunk), INT_INF, jnp.int32).at[
                rows, r[:, :cap_u]].min(r[:, cap_u:], mode="drop")

        if compact and pure:
            recv = _scatter_compact(send_i, send_v)
        elif compact:
            # global predicate: any sub-step's discoveries overflowing cap_u
            # re-ships the whole level dense (the branch collectives are
            # whole-mesh ops, so the predicate must be globally consistent)
            over = lax.pmax(max_found, axes) > cap_u
            recv = lax.cond(over,
                            lambda b: _a2a(b[0]),
                            lambda b: _scatter_compact(b[1], b[2]),
                            (send_d, send_i, send_v))
        else:
            recv = _a2a(send_d)
        # the self slot always carries sub-step 0's dense segment
        recv = lax.dynamic_update_slice(recv, self_par[None],
                                        (j, jnp.int32(0)))

        # --- Apply updates in sub-step order (source q ran sub-step (q-j)%pc
        # for this chunk, so s-order application matches the old sequential
        # per-sub-step semantics exactly) -----------------------------------
        new_front = jnp.zeros_like(front)
        new_pi = pi
        for s in range(pc):
            upd = lax.dynamic_slice_in_dim(recv, (j + s) % pc, 1, axis=0)[0]
            newly = (upd != INT_INF) & (new_pi == -1)
            new_pi = jnp.where(newly, upd, new_pi)
            new_front = new_front | newly

    if instr:
        ctr["edges_useful"] = edges_use
        ctr["edges_examined"] = edges_use
    return new_pi, new_front, ctr
