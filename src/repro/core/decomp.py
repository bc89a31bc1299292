"""Decomposition registry: the pluggable graph-partitioning axis of the
traversal engine.

PR 2 made *local* discovery pluggable (core/local_ops.py: CSR vs DCSC x
dense vs Pallas kernels); this module does the same for the
*decomposition* — how the adjacency matrix and the vertex vectors are
split over mesh axes.  A ``Decomposition`` entry, registered under the
``BFSConfig.decomposition`` string, declares everything the session API
(core/engine.py) needs to build a search program:

  * ``partition_cls`` / ``graph_cls`` — which partition and blocked
    graph format the entry operates on (plan validation)
  * ``n_axes`` + ``axis_sizes``      — its mesh-axis layout: how many
    mesh axes the graph spans and what size each must have
  * ``make_level_args``              — the LevelArgs builder (statics
    like cap_seg/maxdeg/cap_f threaded from the plan, not ad-hoc kwargs)
  * ``body``                         — the whole-search shard_map body
  * ``validate``                     — entry-specific plan checks

plus in/out PartitionSpec helpers (``graph_spec`` / ``out_specs`` /
``batch_out_specs``) shared by the single-root and pod-batched
programs.  Registered entries:

  "2d"  — the paper's checkerboard (§4.4): axes (row, col) = (pr, pc),
          expand = transpose + allgather, fold along the processor row,
          systolic bottom-up rotation.
  "1d"  — row strips (Alg. 1/2 baseline): one axis of size p, expand =
          one dense-bitmap allgather, no fold/transpose/rotation.
  "1ds" — row strips with the SPARSE owner-directed frontier exchange
          (Buluc & Madduri's formulation): expand = fixed-capacity id
          buckets (``PlanStatics.cap_x``) broadcast with one tiled
          allgather, falling back to the dense bitmap when a bucket
          overflows (core/steps_1d_sparse.py).  Same partition/graph/
          LocalOps as "1d" — the registry's first entry added without
          engine edits.

A future 1D-column or 1.5D decomposition is a new entry here (its own
steps module + LevelArgs + body), not an edit to the engine — see the
"adding a decomposition" guide in README.md (rewritten against the
actual "1ds" diff).

The decomposition-agnostic pieces also live here: ``_search_loop`` (the
level loop + Beamer direction heuristics + COUNTER_KEYS accounting
shared by every entry) and the two registered bodies.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import BFSConfig
from repro.core.partition import Partition1D, Partition2D
from repro.core.scopes import BOTTOMUP, REDUCE, RELABEL, TOPDOWN
from repro.core.steps import (COUNTER_KEYS, LevelArgs, bottomup_level,
                              topdown_level, zero_counters)
from repro.core.steps_1d import (LevelArgs1D, bottomup_level_1d,
                                 topdown_level_1d)
from repro.core.steps_1d_sparse import (LevelArgs1DS, bottomup_level_1ds,
                                        topdown_level_1ds)
from repro.graph.formats import Blocked1DGraph, BlockedGraph
from repro.graph.rmat import VertexRelabel

MAX_LEVELS = 64


@dataclass(frozen=True)
class PlanStatics:
    """Static (compile-time) scalars a plan resolves once from the graph
    and config instead of threading them as per-call kwargs."""
    cap_seg: int = 0          # 2D bottom-up sub-step edge window
    maxdeg: int = 0           # kernel mode: max column-segment length
    cap_f: int = 0            # kernel mode: frontier capacity (0 = nc)
    cap_x: int = 0            # 1ds sparse exchange: ids per send bucket
    n_real_edges: float = 0.0  # unpadded edge count (TEPS/metadata)
    expand_chunks: int = 1    # software-pipelined expand: 1d/1ds chunk
    #                           their top-down gather into this many
    #                           overlapped steps; 2d pipelines the
    #                           bottom-up ring (core/steps.py R/G split)
    instrument: bool = True   # False: compile the counters OUT
    #                           of the search program (the latency-lean
    #                           fast path; parents identical)
    interpret: bool = False   # Pallas kernels in the interpreter: derived
    #                           from the plan's mesh (True only on CPU)
    relabel: Optional[VertexRelabel] = None  # the graph's vertex relabel:
    #                           the program takes the root and returns
    #                           the parents in input labels (relabeled)


@dataclass(frozen=True)
class Decomposition:
    """One registered decomposition (see module docstring)."""
    name: str                 # registry key, = BFSConfig.decomposition
    partition_cls: type       # Partition1D | Partition2D
    graph_cls: type           # Blocked1DGraph | BlockedGraph
    n_axes: int               # mesh axes the graph blocks shard over
    axis_sizes: Callable      # (part) -> required mesh-axis sizes
    make_level_args: Callable  # (part, cfg, ops, axes, statics) -> LevelArgs*
    body: Callable            # (g, root, *, part, args, cfg, sync_axis)
    validate: Callable        # (part, statics) -> None (raises on bad plan)

    # ---- SPMD collective contract (checked by repro.analysis) -------------
    #
    # ``rendezvous_axes(axes, mesh_axes)`` declares the mesh axes this
    # entry's level schedule rendezvouses on: the axes every cond/while
    # predicate guarding one of its collectives must be provably uniform
    # over before divergent slices are safe.  Strip entries (1d/1ds) are
    # group-local — their all_gathers/all_to_alls lower with
    # replica_groups along the strip axis, so per-pod-divergent td/bu
    # decisions are safe and they declare just ``axes``.  The 2d entry
    # ppermutes (transpose / ring fold / systolic rotation), and XLA
    # lowers collective-permute as a single whole-program rendezvous
    # regardless of source_target_pairs — so it declares the WHOLE mesh
    # (pod axis included): a pod taking the other branch would wait on a
    # permute its peers never issue (the PR 4 deadlock class).  The
    # default (None) is the conservative whole-mesh claim.  The linter
    # does not *trust* this: it recomputes per-op rendezvous from the
    # jaxpr (rule R1) and flags entries whose declaration under-claims
    # what their program actually issues (rule R3).
    rendezvous_axes: Optional[Callable] = None
    # ``schedule_dims`` lists the BFSConfig fields that change this
    # entry's per-level collective schedule; the analyzer's R4 rule (and
    # tests/test_perf_guard.py through it) enumerates their cross
    # product against ``comm_model.level_collective_budget`` instead of
    # keeping a hand-written case table — a new entry registers its dims
    # and is budget-checked automatically.
    schedule_dims: Tuple[str, ...] = ("expand_chunks",)
    # ``level_steps`` = (topdown, bottomup) per-level step functions
    # (signature ``step(g, pi, front, args, lv)``), the same closures
    # ``body`` drives through _search_loop — exposed so the analyzer can
    # lower ONE level body in isolation for the R4 budget check.
    level_steps: Optional[Tuple[Callable, Callable]] = None

    # ---- edge-membership hook (Graph500 parent-tree validator) ------------
    #
    # ``local_edges(g, part, axes) -> (u, v, valid)`` enumerates this
    # shard's edge slots in GLOBAL layout-A vertex ids: ``u[k] -> v[k]``
    # is a directed edge stored locally iff ``valid[k]``; padded
    # capacity slots must still yield in-range (u, v) so downstream
    # gathers stay safe.  ``edge_keys`` names the graph device-array
    # fields the hook reads, so the validator ships only those to the
    # mesh.  Entries without a hook (None) cannot be validated
    # device-side — ``core/validate.py`` raises a clear error for them.
    edge_keys: Tuple[str, ...] = ()
    local_edges: Optional[Callable] = None

    # ---- PartitionSpec layout (shared by single-root + batch programs) ----

    def graph_spec(self, axes: Tuple[str, ...]) -> P:
        return P(*axes)

    def out_specs(self, axes: Tuple[str, ...], instrument: bool = True):
        """(parents, level, counters, level_stats) specs.  The fast path
        carries NO counters at all ({} — matching _search_loop_fast):
        uninstrumented runs must not emit zero-valued counters that read
        as measurements in aggregates mixing modes."""
        ctr = {k: P() for k in COUNTER_KEYS} if instrument else {}
        return (P(*axes), P(), ctr, P())

    def batch_out_specs(self, axes: Tuple[str, ...], pod_axis: str):
        """(parents-per-root, levels, level_stats-per-root) specs for the
        pod-batched program."""
        return (P(*(axes + (pod_axis, None))), P(pod_axis), P(pod_axis))


_REGISTRY: Dict[str, Decomposition] = {}


def register_decomposition(entry: Decomposition) -> Decomposition:
    if entry.name in _REGISTRY:
        raise ValueError(f"duplicate decomposition {entry.name!r}")
    _REGISTRY[entry.name] = entry
    return entry


def get_decomposition(name: str) -> Decomposition:
    if name not in _REGISTRY:
        raise ValueError(f"no decomposition registered for {name!r}; "
                         f"have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def registered_decompositions() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def unregister_decomposition(name: str) -> None:
    """Remove an entry — for scoped test/fixture registrations only
    (repro.analysis.fixtures registers a deliberately-broken entry,
    lints it, and must leave the registry exactly as it found it)."""
    if name not in _REGISTRY:
        raise ValueError(f"no decomposition registered for {name!r}")
    del _REGISTRY[name]


# ---------------------------------------------------------------------------
# Input labels in and out of a relabeled graph
# ---------------------------------------------------------------------------


def relabeled(body, relabel: VertexRelabel, axes: Tuple[str, ...]):
    """``body`` of any entry over a graph stored under the relabeled ids
    ``h(x)``, taking the root and returning the parents in input labels:
    the root goes in through ``h``; each device then all-gathers the
    stored-label parents and, for the input labels x of its own layout-A
    chunk, returns ``h^-1(pi[h(x)])`` (-1 stays -1).  Both maps run under
    the ``bfs.relabel`` scope, outside the level loop."""

    def run(g, root, **kw):
        with jax.named_scope(RELABEL):
            root = relabel.apply(root, jnp)
        pi, level, ctr, stats = body(g, root, **kw)
        with jax.named_scope(RELABEL):
            loc = pi.reshape(-1)
            full, k = loc, 0
            for ax in reversed(axes):
                full = lax.all_gather(full, ax, tiled=True)
            for ax in axes:
                k = k * lax.axis_size(ax) + lax.axis_index(ax)
            x = k * loc.shape[0] + jnp.arange(loc.shape[0], dtype=jnp.int32)
            out = relabel.invert(full[relabel.apply(x, jnp)], jnp)
        return out.reshape(pi.shape), level, ctr, stats

    return run


# ---------------------------------------------------------------------------
# The decomposition-agnostic whole-search level loop
# ---------------------------------------------------------------------------


def _level(mode, td_level, bu_level, *operands):
    """One level in the direction ``mode`` picks (1: bottom-up), the
    branch taken under its top scope (core/scopes.py)."""
    def branch(scope, step):
        def run(ops):
            with jax.named_scope(scope):
                return step(*ops)
        return run

    return lax.cond(mode == 1, branch(BOTTOMUP, bu_level),
                    branch(TOPDOWN, td_level), operands)


def _search_loop(g, gidx, root, *, n_total: float, cfg: BFSConfig, axes,
                 sync, td_level, bu_level, sync_modes: bool = False,
                 over_cap: int = 0, expand_chunks: int = 1):
    """Frontier-size / edge-mass direction heuristics, per-level stats,
    counter accumulation.  ``td_level`` / ``bu_level`` are
    (pi, front, lv=None) -> (pi, front, ctr) step closures over the
    local graph ``g`` (already squeezed); ``lv`` is the fast-path
    per-level context (see ``_search_loop_fast``).

    The loop state carries TWO frontier sizes: the per-slice ``n_f``
    (this search's own frontier — what the direction heuristics and the
    level stats must read) and the cross-slice ``n_sync`` (the pmax over
    the sync axes that keeps pod-batched searches in lockstep — what the
    loop predicate reads).  Conflating them made every batched search
    switch modes on the LARGEST pod's frontier instead of its own.

    ``sync_modes``: a step body whose collectives span the WHOLE mesh
    (2D: the ppermute transpose / ring fold / systolic rotation
    rendezvous with every device) cannot let pod slices take different
    td/bu branches — divergent slices would wait on different collective
    ops forever.  Such entries set sync_modes=True and the *decision* is
    made uniform over ``sync``: any slice wanting bottom-up switches all
    of them, and top-down resumes only when every slice wants it.
    Entries whose collectives are group-local per slice (1d/1ds:
    all_gather / all_to_all along the strip axis only) keep sync_modes
    False and genuinely switch per slice.

    ``over_cap``: the "1ds" sparse-exchange bucket capacity; when > 0
    the fast path carries the per-processor overflow indicator in its
    fused reduction so the exchange step needs no predicate collective.
    With ``expand_chunks`` > 1 the chunked exchange sends per-sub-range
    buckets of capacity over_cap/expand_chunks, so the indicator tests
    the per-sub-range counts instead of the whole-strip count.

    With ``cfg.instrument`` False the loop dispatches to
    ``_search_loop_fast``: one fused vector psum per level (plus one
    fused pmax when pod-batched) instead of the 6–11 scalar all-reduces
    the instrumented program spends on counters and stats."""
    pi0 = jnp.where(gidx == root, root, jnp.int32(-1))
    front0 = gidx == root
    if not cfg.instrument:
        return _search_loop_fast(
            g, pi0, front0, n_total=n_total, cfg=cfg, axes=axes, sync=sync,
            td_level=td_level, bu_level=bu_level, sync_modes=sync_modes,
            over_cap=over_cap, expand_chunks=expand_chunks)
    stats0 = jnp.zeros((MAX_LEVELS, 5), jnp.float32)

    def cond(st):
        pi, front, mode, level, n_f, n_sync, ctr, stats = st
        return (level < MAX_LEVELS) & (n_sync > 0)

    def body(st):
        pi, front, mode, level, n_f, n_sync, ctr, stats = st
        with jax.named_scope(REDUCE):
            m_f = lax.psum(jnp.sum(jnp.where(front, g["deg_A"], 0),
                                   dtype=jnp.float32), axes)
            m_u = lax.psum(jnp.sum(jnp.where(pi == -1, g["deg_A"], 0),
                                   dtype=jnp.float32), axes)
            if cfg.direction_optimizing:
                # per-slice n_f: each batched search switches on its OWN
                # frontier size, never a lockstep partner's
                go_bu = (mode == 0) & (m_f > m_u / cfg.alpha)
                go_td = (mode == 1) & (n_f < n_total / cfg.beta)
                if sync_modes and sync != axes:
                    go_bu = lax.pmax(go_bu.astype(jnp.int32), sync) > 0
                    go_td = lax.pmin(go_td.astype(jnp.int32), sync) > 0
                new_mode = jnp.where(go_bu, 1, jnp.where(go_td, 0, mode))
            else:
                new_mode = mode

        pi2, front2, c2 = _level(new_mode, td_level, bu_level, pi, front)
        ctr = {k: ctr[k] + c2[k] for k in ctr}
        with jax.named_scope(REDUCE):
            # stats row: n_f, m_f, mode, used, measured expand words this
            # level (the dense-vs-sparse crossover is read off column 4)
            stats = stats.at[level].set(
                jnp.stack([n_f, m_f, new_mode.astype(jnp.float32),
                           jnp.float32(1), c2["wire_expand"]]))
            n_f2 = lax.psum(jnp.sum(front2, dtype=jnp.float32), axes)
            # the predicate feeds on the cross-slice max so batched
            # searches stay in lockstep; heuristics keep the per-slice n_f2
            n_sync2 = lax.pmax(n_f2, sync) if sync != axes else n_f2
        return (pi2, front2, new_mode, level + 1, n_f2, n_sync2, ctr, stats)

    st = (pi0, front0, jnp.int32(0), jnp.int32(0), jnp.float32(1.0),
          jnp.float32(1.0), zero_counters(), stats0)
    pi, front, mode, level, n_f, n_sync, ctr, stats = lax.while_loop(
        cond, body, st)
    return pi, level, ctr, stats


def _search_loop_fast(g, pi0, front0, *, n_total: float, cfg: BFSConfig,
                      axes, sync, td_level, bu_level, sync_modes: bool,
                      over_cap: int, expand_chunks: int = 1):
    """The ``instrument=False`` level loop: the whole-search program
    spends exactly ONE fused vector psum per level — frontier size,
    frontier edge mass, unvisited edge mass, and (for the "1ds" hybrid)
    the bucket-overflow indicator, stacked and reduced together — plus
    one fused vector pmax when searches are pod-batched (lockstep
    ``n_sync`` and, for sync_modes entries, the direction decision).

    The direction heuristics read the PREVIOUS level's fused reduction:
    the decision for level L+1 is computed at the tail of level L from
    the post-level (pi, front) — the same values the instrumented loop
    recomputes with separate psums at the top of L+1 — so the mode
    sequence and the parents are bit-identical to the instrumented
    program.  Counters are compiled out; the returned ctr is EMPTY (a
    fast run has no measurements — zeros here would masquerade as
    measured wire volumes downstream).  The level_stats rows carry what
    the loop already reduces — the level's own (per-slice) n_f and m_f,
    its direction and used = 1, bit-identical to the instrumented rows
    — and NaN in column 4, the expand words this program does not
    measure.  The row write adds no collective."""
    deg = g["deg_A"]

    def reduce_state(pi, front):
        """(n_f, m_f, m_u, over) from one stacked psum over the slice."""
        n_loc = jnp.sum(front, dtype=jnp.float32)
        if over_cap and expand_chunks > 1:
            # chunked exchange: each of the expand_chunks contiguous
            # sub-ranges gets its own over_cap/expand_chunks bucket, so
            # ANY sub-range overflowing forces the dense fallback
            cnts = jnp.sum(front.reshape(expand_chunks, -1), axis=1,
                           dtype=jnp.float32)
            over_loc = (jnp.max(cnts)
                        > (over_cap // expand_chunks)).astype(jnp.float32)
        elif over_cap:
            over_loc = (n_loc > over_cap).astype(jnp.float32)
        else:
            over_loc = jnp.float32(0)
        red = lax.psum(jnp.stack([
            n_loc,
            jnp.sum(jnp.where(front, deg, 0), dtype=jnp.float32),
            jnp.sum(jnp.where(pi == -1, deg, 0), dtype=jnp.float32),
            over_loc]), axes)
        return red[0], red[1], red[2], red[3] > 0

    def decide_and_sync(mode, n_f, m_f, m_u):
        """Next level's direction decision + the lockstep pmax, fused:
        pmin(go_td) rides the same pmax as 1 - go_td."""
        go_bu = (mode == 0) & (m_f > m_u / cfg.alpha)
        go_td = (mode == 1) & (n_f < n_total / cfg.beta)
        if sync == axes:
            return n_f, go_bu, go_td
        if sync_modes and cfg.direction_optimizing:
            pm = lax.pmax(jnp.stack([
                n_f, go_bu.astype(jnp.float32),
                1.0 - go_td.astype(jnp.float32)]), sync)
            return pm[0], pm[1] > 0, pm[2] < 1
        return lax.pmax(n_f, sync), go_bu, go_td

    with jax.named_scope(REDUCE):
        n_f0, m_f0, m_u0, ov0 = reduce_state(pi0, front0)
        n_sync0, gb0, gt0 = decide_and_sync(jnp.int32(0), n_f0, m_f0, m_u0)
    # column 4 (measured expand words) is not measured here: NaN, never 0
    stats0 = jnp.zeros((MAX_LEVELS, 5), jnp.float32).at[:, 4].set(jnp.nan)

    def cond(st):
        return (st["level"] < MAX_LEVELS) & (st["n_sync"] > 0)

    def body(st):
        with jax.named_scope(REDUCE):
            if cfg.direction_optimizing:
                new_mode = jnp.where(st["gb"], 1,
                                     jnp.where(st["gt"], 0, st["mode"]))
            else:
                new_mode = st["mode"]
        pi2, front2, _ = _level(new_mode, td_level, bu_level, st["pi"],
                                st["front"], {"over": st["ov"]})
        with jax.named_scope(REDUCE):
            n_f2, m_f2, m_u2, ov2 = reduce_state(pi2, front2)
            n_sync2, gb2, gt2 = decide_and_sync(new_mode, n_f2, m_f2, m_u2)
            stats = st["stats"].at[st["level"]].set(jnp.stack([
                st["n_f"], st["m_f"], new_mode.astype(jnp.float32),
                jnp.float32(1), jnp.float32(jnp.nan)]))
        return dict(pi=pi2, front=front2, mode=new_mode,
                    level=st["level"] + 1, n_f=n_f2, m_f=m_f2,
                    n_sync=n_sync2, gb=gb2, gt=gt2, ov=ov2, stats=stats)

    st = lax.while_loop(cond, body, dict(
        pi=pi0, front=front0, mode=jnp.int32(0), level=jnp.int32(0),
        n_f=n_f0, m_f=m_f0, n_sync=n_sync0, gb=gb0, gt=gt0, ov=ov0,
        stats=stats0))
    return st["pi"], st["level"], {}, st["stats"]


# ---------------------------------------------------------------------------
# 2D checkerboard entry
# ---------------------------------------------------------------------------


def _bfs_body_2d(g, root, *, part: Partition2D, args: LevelArgs,
                 cfg: BFSConfig, sync_axis: Optional[str] = None):
    """sync_axis: when searches run batched across an outer axis (pods),
    the level loop must take the same trip count on every slice — the
    loop continues while ANY slice has a live frontier (idle slices run
    empty levels; collectives stay aligned)."""
    pc, chunk = part.pc, part.chunk
    axes = (args.row_axis, args.col_axis)
    sync = axes + ((sync_axis,) if sync_axis else ())
    i = lax.axis_index(args.row_axis)
    j = lax.axis_index(args.col_axis)
    g = {k: v[0, 0] for k, v in g.items()}

    gidx = ((i * pc + j) * chunk + jnp.arange(chunk)).astype(jnp.int32)
    pi, level, ctr, stats = _search_loop(
        g, gidx, root, n_total=part.n, cfg=cfg, axes=axes, sync=sync,
        td_level=lambda pi, f, lv=None: topdown_level(g, pi, f, args, lv),
        bu_level=lambda pi, f, lv=None: bottomup_level(g, pi, f, args, lv),
        # 2D steps ppermute (transpose / ring fold / rotation): the
        # whole mesh must take one td/bu branch per level
        sync_modes=True)
    return pi[None, None], level, ctr, stats


def _make_args_2d(part, cfg, ops, axes, statics: PlanStatics) -> LevelArgs:
    row_axis, col_axis = axes
    return LevelArgs(part=part, row_axis=row_axis, col_axis=col_axis,
                     fold_mode=cfg.fold_mode,
                     perm=tuple(part.transpose_perm()),
                     cap_seg=statics.cap_seg,
                     local_mode=ops.local_mode, storage=cfg.storage,
                     cap_f=statics.cap_f, maxdeg=statics.maxdeg,
                     compact_updates=cfg.compact_updates, ops=ops,
                     instrument=statics.instrument,
                     expand_chunks=statics.expand_chunks,
                     interpret=statics.interpret)


def _validate_2d(part, statics: PlanStatics) -> None:
    if statics.cap_seg <= 0:
        # the bottom-up branch always compiles (lax.cond), and a zero
        # edge window would silently discover nothing
        raise ValueError("2d decomposition needs cap_seg > 0 "
                         "(pass graph.cap_seg)")


def _local_edges_2d(g, part, axes):
    """(u, v, valid) for one (i, j) block in global layout-A ids: CSC
    ``edge_src`` is the block-local source (column j owns sources
    [j*nc, (j+1)*nc)), ``row_idx`` the block-local dest (row i owns
    dests [i*nr, (i+1)*nr)); padded slots hold 0 so the rebased ids
    stay in range."""
    i = lax.axis_index(axes[0])
    j = lax.axis_index(axes[1])
    u = (j * part.nc + g["edge_src"]).astype(jnp.int32)
    v = (i * part.nr + g["row_idx"]).astype(jnp.int32)
    valid = jnp.arange(u.shape[0], dtype=jnp.int32) < g["nnz"]
    return u, v, valid


def _local_edges_1d(g, part, axes):
    """(u, v, valid) for one strip: CSR ``col_idx`` is already the
    GLOBAL source id, ``edge_dst`` the strip-local dest (strip i owns
    [i*chunk, (i+1)*chunk)); padded slots hold 0."""
    i = lax.axis_index(axes[0])
    u = g["col_idx"].astype(jnp.int32)
    v = (i * part.chunk + g["edge_dst"]).astype(jnp.int32)
    valid = jnp.arange(u.shape[0], dtype=jnp.int32) < g["nnz"]
    return u, v, valid


register_decomposition(Decomposition(
    name="2d", partition_cls=Partition2D, graph_cls=BlockedGraph,
    n_axes=2, axis_sizes=lambda part: (part.pr, part.pc),
    make_level_args=_make_args_2d, body=_bfs_body_2d,
    validate=_validate_2d,
    # ppermutes rendezvous with EVERY device (whole-mesh XLA
    # collective-permute) — hence sync_modes=True above
    rendezvous_axes=lambda axes, mesh_axes: tuple(mesh_axes),
    schedule_dims=("fold_mode", "compact_updates", "expand_chunks"),
    level_steps=(topdown_level, bottomup_level),
    edge_keys=("edge_src", "row_idx", "nnz"),
    local_edges=_local_edges_2d))


# ---------------------------------------------------------------------------
# 1D row-strip entries ("1d" dense expand, "1ds" sparse expand)
# ---------------------------------------------------------------------------


def _make_strip_body(td_step, bu_step):
    """Whole-search body over a single strip axis, shared by every 1D
    entry: squeeze the strip arrays, build global vertex ids, run the
    shared search loop with the given per-level step closures.  A new
    strip-family decomposition supplies its two steps here instead of
    copy-pasting the body (their collectives are group-local along the
    strip axis, so per-slice direction switching is safe —
    sync_modes stays False)."""

    def body(g, root, *, part: Partition1D, args, cfg: BFSConfig,
             sync_axis: Optional[str] = None):
        axes = (args.axis,)
        sync = axes + ((sync_axis,) if sync_axis else ())
        i = lax.axis_index(args.axis)
        g = {k: v[0] for k, v in g.items()}

        gidx = (i * part.chunk + jnp.arange(part.chunk)).astype(jnp.int32)
        pi, level, ctr, stats = _search_loop(
            g, gidx, root, n_total=part.n, cfg=cfg, axes=axes, sync=sync,
            td_level=lambda pi, f, lv=None: td_step(g, pi, f, args, lv),
            bu_level=lambda pi, f, lv=None: bu_step(g, pi, f, args, lv),
            # "1ds": the fast path carries the bucket-overflow indicator
            # in its fused reduction (0 disables it for plain "1d");
            # expand_chunks switches it to per-sub-range bucket counts
            over_cap=getattr(args, "cap_x", 0),
            expand_chunks=getattr(args, "expand_chunks", 1))
        return pi[None], level, ctr, stats

    return body


_bfs_body_1d = _make_strip_body(topdown_level_1d, bottomup_level_1d)


def _make_args_1d(part, cfg, ops, axes, statics: PlanStatics) -> LevelArgs1D:
    return LevelArgs1D(part=part, axis=axes[0],
                       local_mode=ops.local_mode, storage=cfg.storage,
                       cap_f=statics.cap_f, maxdeg=statics.maxdeg, ops=ops,
                       instrument=statics.instrument,
                       expand_chunks=statics.expand_chunks,
                       interpret=statics.interpret)


def _validate_strip_chunks(part, statics: PlanStatics) -> None:
    """Shared 1d/1ds check: the chunked expand splits the owner's packed
    bitmap words (chunk/32 of them) into expand_chunks equal sub-chunks,
    so the word count must divide evenly — a ragged last sub-chunk would
    silently mis-align the owner-major gather layout."""
    c = statics.expand_chunks
    words = part.chunk // 32
    if c > 1 and words % c != 0:
        raise ValueError(
            f"expand_chunks={c} does not divide the per-device strip's "
            f"packed word count ({words} = chunk {part.chunk} / 32); "
            f"pick a divisor of {words}")


def _validate_1d(part, statics: PlanStatics) -> None:
    _validate_strip_chunks(part, statics)


register_decomposition(Decomposition(
    name="1d", partition_cls=Partition1D, graph_cls=Blocked1DGraph,
    n_axes=1, axis_sizes=lambda part: (part.p,),
    make_level_args=_make_args_1d, body=_bfs_body_1d,
    validate=_validate_1d,
    # group-local along the strip axis: per-slice direction switching
    # is safe, so pods never enter the rendezvous
    rendezvous_axes=lambda axes, mesh_axes: tuple(axes),
    schedule_dims=("expand_chunks",),
    level_steps=(topdown_level_1d, bottomup_level_1d),
    edge_keys=("col_idx", "edge_dst", "nnz"),
    local_edges=_local_edges_1d))


# ---------------------------------------------------------------------------
# 1D sparse-exchange entry ("1ds"): same strips, owner-directed expand
# ---------------------------------------------------------------------------

_bfs_body_1ds = _make_strip_body(topdown_level_1ds, bottomup_level_1ds)


def _make_args_1ds(part, cfg, ops, axes,
                   statics: PlanStatics) -> LevelArgs1DS:
    return LevelArgs1DS(part=part, axis=axes[0], cap_x=statics.cap_x,
                        local_mode=ops.local_mode, storage=cfg.storage,
                        cap_f=statics.cap_f, maxdeg=statics.maxdeg, ops=ops,
                        instrument=statics.instrument,
                        codec=cfg.frontier_codec,
                        expand_chunks=statics.expand_chunks,
                        interpret=statics.interpret)


def _validate_1ds(part, statics: PlanStatics) -> None:
    if statics.cap_x <= 0:
        # zero-capacity buckets would force the dense fallback on every
        # level — the caller asked for the sparse exchange and got "1d"
        raise ValueError(
            "1ds decomposition needs cap_x > 0 (plan_bfs derives it from "
            "the graph via comm_model.plan_cap_x; graph-less plans must "
            "pass cap_x explicitly)")
    if statics.cap_x > part.chunk:
        raise ValueError(
            f"cap_x={statics.cap_x} exceeds the owned chunk "
            f"({part.chunk}) — a bucket can never hold more frontier "
            f"ids than a processor owns")
    _validate_strip_chunks(part, statics)
    c = statics.expand_chunks
    if c > 1 and statics.cap_x % c != 0:
        raise ValueError(
            f"expand_chunks={c} does not divide cap_x={statics.cap_x}; "
            f"the chunked sparse exchange splits the send bucket into "
            f"expand_chunks equal sub-buckets")


register_decomposition(Decomposition(
    name="1ds", partition_cls=Partition1D, graph_cls=Blocked1DGraph,
    n_axes=1, axis_sizes=lambda part: (part.p,),
    make_level_args=_make_args_1ds, body=_bfs_body_1ds,
    validate=_validate_1ds,
    rendezvous_axes=lambda axes, mesh_axes: tuple(axes),
    schedule_dims=("frontier_codec", "expand_chunks"),
    level_steps=(topdown_level_1ds, bottomup_level_1ds),
    edge_keys=("col_idx", "edge_dst", "nnz"),
    local_edges=_local_edges_1d))
