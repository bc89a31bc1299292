"""BFSEngine: the plan → compile → run traversal session API.

The Graph500 methodology (paper §7) is "build the distributed graph
once, then run BFS from 16–64 roots" — so the engine splits the old
one-shot ``run_bfs`` into three stages:

  plan    ``plan_bfs(graph, cfg, mesh) -> BFSPlan``
          resolves the Decomposition entry (core/decomp.py) and the
          LocalOps entry (core/local_ops.py), pulls the static scalars
          (cap_seg / maxdeg_col / n_real_edges) from the graph, and
          validates arrays/partition/mesh/config coherence up front —
          every shape error surfaces here, before any device work.

  compile ``BFSPlan.compile() -> BFSEngine``
          ships the graph device arrays ONCE (one device_put per
          shipped key) and AOT-compiles the whole-search program ONCE
          (one jit trace); ``engine.ship_s`` / ``engine.compile_s``
          report the two costs separately.

  run     ``BFSEngine.run(root)`` / ``run_many(roots)`` reuse the
          shipped arrays and compiled executable across roots — per-root
          time is pure traversal, never smeared by recompiles.
          ``run_batch(roots, pod_axis=...)`` compiles the pod-parallel
          multi-source program (roots sharded over the pod axis, graph
          replicated, searches in lockstep) — available in EVERY
          registered decomposition, not just 2D.

``plan_for_part`` is the graph-less variant for abstract/dry-run
callers (launch/cells.py) that lower against ShapeDtypeStructs; it
skips the graph-array checks but performs all partition/mesh/config
validation.  The legacy ``make_*_bfs_fn`` builders and ``run_bfs``
(core/bfs.py) are thin wrappers over these two entry points.
"""
from __future__ import annotations

import functools
import re
import time
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import BFSConfig
from repro.core import comm_model
from repro.core.decomp import (Decomposition, PlanStatics,
                               get_decomposition, relabeled)
from repro.core.local_ops import LocalOps, get_local_ops
from repro.core.scopes import op_scope


@dataclass
class BFSResult:
    parents: np.ndarray          # (n_orig,)
    n_levels: int
    counters: Dict[str, float]   # whole-search totals (paper 64-bit words)
    level_stats: np.ndarray      # (MAX_LEVELS, 5): n_f, m_f, mode, used,
    #                              measured expand words that level (NaN
    #                              when the program is not instrumented)
    validation: Optional[Any] = None  # ValidationReport when run(...,
    #                              validate=True); None otherwise


@dataclass
class BFSBatchResult:
    """Pod-batched multi-source searches (counters are not accumulated
    per root in the batched program; use ``run``/``run_many`` for the
    Eq. 2 accounting).  ``level_stats`` carries each root's OWN per-level
    frontier sizes and direction decisions — batched searches share a
    lockstep trip count, not frontier sizes.  Direction switching is per
    slice for entries with group-local collectives (1d/1ds); the 2d
    entry syncs the decision across pods (see decomp._search_loop)."""
    roots: np.ndarray            # (n_roots,)
    parents: np.ndarray          # (n_roots, n_orig)
    n_levels: np.ndarray         # (n_roots,)
    level_stats: np.ndarray      # (n_roots, MAX_LEVELS, 5), per BFSResult


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BFSPlan:
    """A frozen, validated description of one traversal session: which
    decomposition + local format run on which mesh axes with which
    static capacities.  Build programs with ``build_fn`` /
    ``build_batch_fn`` (abstract callers), or ``compile()`` into a
    BFSEngine when a concrete graph is attached."""
    part: Any                     # Partition1D | Partition2D
    cfg: BFSConfig
    mesh: Any
    entry: Decomposition
    ops: LocalOps
    axes: Tuple[str, ...]         # mesh axes the graph blocks shard over
    statics: PlanStatics
    graph: Any = None             # Blocked*Graph; None for abstract plans

    @property
    def keys(self) -> Tuple[str, ...]:
        """Graph device arrays this plan ships (from the LocalOps entry)."""
        return self.ops.keys

    def level_args(self):
        return self.entry.make_level_args(self.part, self.cfg, self.ops,
                                          self.axes, self.statics)

    def _search_body(self, sync_axis: Optional[str]):
        """The entry's whole-search body, in input labels on a
        relabeled graph."""
        body = functools.partial(self.entry.body, part=self.part,
                                 args=self.level_args(), cfg=self.cfg,
                                 sync_axis=sync_axis)
        relabel = self.statics.relabel
        return body if relabel is None else relabeled(body, relabel,
                                                      self.axes)

    # ---- program builders -------------------------------------------------

    def build_fn(self, sync_axis: Optional[str] = None, trace_hook=None):
        """The jitted single-root whole-search program:
        fn(graph_arrays_dict, root) -> (pi, level, ctr, stats).
        ``trace_hook`` (if given) is called once per jit trace — the
        engine uses it to assert compile-once behavior."""
        body = self._search_body(sync_axis)
        if trace_hook is not None:
            inner = body

            def body(g, root):
                trace_hook()
                return inner(g, root)

        gspec = {k: self.entry.graph_spec(self.axes) for k in self.keys}
        mapped = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(gspec, P()),
            out_specs=self.entry.out_specs(self.axes,
                                           self.cfg.instrument),
            check_vma=False)   # pallas_call outputs carry no vma annotation
        return jax.jit(mapped)

    def build_batch_fn(self, pod_axis: str, trace_hook=None):
        """The jitted pod-batched multi-source program: independent
        whole searches scanned over each pod's local roots (the
        roots-per-pod count is fixed by the shape of the roots array the
        program is compiled against), pods embarrassingly parallel
        (graph replicated across pods, zero inter-pod traffic, level
        loops in lockstep via sync_axis).
        fn(graph_arrays_dict, roots) -> (pis, levels)."""
        if pod_axis not in self.mesh.shape:
            raise ValueError(f"mesh has no {pod_axis!r} axis for batched "
                             f"roots; axes are {tuple(self.mesh.shape)}")
        body1 = self._search_body(pod_axis)
        n_axes = self.entry.n_axes

        def multi_body(g, roots):
            if trace_hook is not None:
                trace_hook()

            # roots: (n_roots_local,) — scan full searches over local roots
            def one(carry, root):
                pi, level, ctr, stats = body1(g, root)
                return carry, (pi.reshape(pi.shape[-1]), level, stats)

            _, (pis, levels, stats) = lax.scan(one, jnp.int32(0),
                                               roots.reshape(-1))
            return pis.reshape((1,) * n_axes + pis.shape), levels, stats

        gspec = {k: self.entry.graph_spec(self.axes) for k in self.keys}
        mapped = jax.shard_map(
            multi_body, mesh=self.mesh,
            in_specs=(gspec, P(pod_axis)),
            out_specs=self.entry.batch_out_specs(self.axes, pod_axis),
            check_vma=False)
        return jax.jit(mapped)

    # ---- static analysis --------------------------------------------------

    def lint(self, pod_axis: Optional[str] = None) -> List[Any]:
        """Run the SPMD collective-schedule linter (repro.analysis,
        rules R1–R3) on this plan's traced program and return the
        findings (empty = clean).  When ``pod_axis`` names an axis of
        the plan's mesh the pod-batched program is linted — that is
        where divergence hazards live (per-pod direction decisions
        around whole-mesh collectives); otherwise the single-root
        program.  Traces only; nothing is lowered, compiled, or run.
        Registry-wide sweeps (including the R4 budget check) live in
        ``python -m repro.analysis.lint``."""
        from repro.analysis.registry import lint_plan
        if pod_axis is None and "pod" in self.mesh.shape:
            pod_axis = "pod"
        return lint_plan(self, pod_axis=pod_axis)

    # ---- session ----------------------------------------------------------

    def compile(self, store=None, exec_key: str = "default") -> "BFSEngine":
        """Ship the graph and compile the search program (both once);
        the returned engine runs any number of roots against them.

        ``store`` (a ckpt.graph_store.GraphStore) short-circuits the XLA
        compile: a serialized executable saved under ``exec_key`` whose
        config hash + mesh shape match this plan is deserialized instead
        (``engine.exec_load_s`` / ``exec_from_store`` report it), and a
        fresh compile is persisted back so the next process loads."""
        return BFSEngine(self, store=store, exec_key=exec_key)


def plan_for_part(part, cfg: BFSConfig, mesh, *,
                  row_axis: str = "data", col_axis: str = "model",
                  local_mode: str = "dense", cap_seg: int = 0,
                  maxdeg: int = 0, cap_f: int = 0, cap_x: int = 0,
                  n_real_edges: float = 0.0, relabel=None) -> BFSPlan:
    """A graph-less plan from an explicit partition + static capacities
    (abstract lowering, compat builders).  Performs every validation
    that does not need concrete arrays.  ``relabel`` is the graph's
    vertex relabel (``graph.relabel``; None for input labels)."""
    entry = get_decomposition(cfg.decomposition)
    if not isinstance(part, entry.partition_cls):
        raise TypeError(
            f"decomposition={cfg.decomposition!r} needs a "
            f"{entry.partition_cls.__name__}, got {type(part).__name__}")
    axes = (row_axis, col_axis)[: entry.n_axes]
    for ax, want in zip(axes, entry.axis_sizes(part)):
        if ax not in mesh.shape:
            raise ValueError(
                f"mesh has no {ax!r} axis needed by decomposition="
                f"{cfg.decomposition!r}; axes are {tuple(mesh.shape)}")
        if mesh.shape[ax] != want:
            raise ValueError(
                f"mesh axis {ax!r} has size {mesh.shape[ax]} but the "
                f"partition needs {want} (grid "
                f"{tuple(entry.axis_sizes(part))})")
    from repro.core.steps_1d_sparse import CODECS
    if cfg.frontier_codec not in CODECS:
        raise ValueError(
            f"cfg.frontier_codec={cfg.frontier_codec!r} is not a "
            f"registered frontier codec; have {CODECS}")
    if cfg.expand_chunks < 1:
        raise ValueError(
            f"cfg.expand_chunks={cfg.expand_chunks} must be >= 1 "
            f"(1 = unpipelined expand)")
    ops = get_local_ops(cfg.decomposition, local_mode, cfg.storage)
    # Pallas kernels run in the interpreter only on a CPU mesh: read off
    # the mesh the plan compiles for, never the process default backend,
    # so a plan for a described TPU topology compiles them with Mosaic
    interpret = mesh.devices.flat[0].platform == "cpu"
    statics = PlanStatics(cap_seg=cap_seg, maxdeg=maxdeg, cap_f=cap_f,
                          cap_x=cap_x, n_real_edges=n_real_edges,
                          instrument=cfg.instrument,
                          expand_chunks=cfg.expand_chunks,
                          interpret=interpret, relabel=relabel)
    entry.validate(part, statics)
    return BFSPlan(part=part, cfg=cfg, mesh=mesh, entry=entry, ops=ops,
                   axes=axes, statics=statics)


def plan_bfs(graph, cfg: BFSConfig, mesh, *,
             row_axis: str = "data", col_axis: str = "model",
             local_mode: str = "dense", cap_f: int = 0,
             cap_x: int = 0) -> BFSPlan:
    """Plan a traversal session over a concrete blocked graph.

    Resolves the decomposition + LocalOps entries, pulls the static
    scalars (cap_seg, maxdeg_col, n_real_edges) from the graph, and
    validates graph/partition/mesh/config coherence — including that
    the graph actually carries every array the chosen local format
    ships.  ``cap_x`` (the "1ds" sparse-exchange bucket capacity) is
    planned from the graph degree stats when not given —
    ``comm_model.plan_cap_x`` caps the buckets at the dense/sparse
    crossover so overflowing levels fall back to the bitmap."""
    entry = get_decomposition(cfg.decomposition)
    if not isinstance(graph, entry.graph_cls):
        raise TypeError(
            f"cfg.decomposition={cfg.decomposition!r} does not match "
            f"graph type {type(graph).__name__}")
    part = graph.part
    if cap_x <= 0:
        # bits-aware: the packed codec cheapens each shipped id, moving
        # the sparse/dense crossover out and admitting larger buckets
        bits = comm_model.codec_bits(part.chunk) \
            if cfg.frontier_codec == "packed" else 64
        cap_x = comm_model.plan_cap_x(part.n, part.p, int(graph.m),
                                      bits=bits)
    plan = plan_for_part(
        graph.part, cfg, mesh, row_axis=row_axis, col_axis=col_axis,
        local_mode=local_mode, cap_f=cap_f, cap_x=cap_x,
        cap_seg=getattr(graph, "cap_seg", 0), maxdeg=graph.maxdeg_col,
        n_real_edges=float(graph.m), relabel=graph.relabel)
    arrays = graph.device_arrays()
    missing = [k for k in plan.keys if k not in arrays]
    if missing:
        raise ValueError(
            f"graph lacks arrays {missing} needed by local_mode="
            f"{local_mode!r}/storage={cfg.storage!r} (1d csr kernels need "
            f"build_blocked_1d(..., with_col_ptr=True))")
    return replace(plan, graph=graph)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

# one collective instruction, in compiled HLO (`%x = <shape> op(...)`,
# async collectives as op-start/op-done pairs — count the starts) or in
# lowered StableHLO (`stablehlo.op"?(`).  The HLO arm must not cross a
# quote while scanning from `=` to the op name: instruction lines carry
# metadata={op_name="..."} strings that can embed collective names
# followed by `(`, and matching inside them double-counts the op the
# string merely describes (tests/test_hlo_counts.py pins this).
_COLLECTIVE_OP_RE = re.compile(
    r"(?:=\s*[^=\n\"]*?\b(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute)(?:-start)?\()"
    r"|(?:stablehlo\.(all_reduce|all_gather|all_to_all|reduce_scatter|"
    r"collective_permute)\b)")


def hlo_collective_counts(hlo: str) -> Dict[str, int]:
    """Collective-op instruction counts per kind (hyphenated HLO names)
    in an HLO or StableHLO text dump, plus a ``total``.  Used by the
    perf-guard test and the bench trajectory to pin the collective
    schedule of a program (counts are static program size, NOT dynamic
    executions — while-loop bodies appear once, and both branches of a
    conditional count even though one executes)."""
    counts: Dict[str, int] = {}
    for m in _COLLECTIVE_OP_RE.finditer(hlo):
        kind = (m.group(1) or m.group(2)).replace("_", "-")
        counts[kind] = counts.get(kind, 0) + 1
    counts["total"] = sum(counts.values())
    return counts


# one HLO instruction and its op_name metadata, compiled
# (`  [ROOT ]%fusion.3 = s32[8]{0} fusion(...), ..., metadata={op_name="..."`)
# or lowered with debug info (the same without the `%`)
_INSTR_OP_NAME_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?\bmetadata=\{[^\n]*?"
    r"\bop_name=\"([^\"]*)\"", re.MULTILINE)


def hlo_op_scopes(hlo: str) -> Dict[str, str]:
    """{instruction name: scope path} for every instruction of an HLO
    text (compiled, or lowered with ``debug_info=True``) whose op_name
    lies under a level-program scope (core/scopes.py); instructions
    outside every scope are left out."""
    out = {}
    for m in _INSTR_OP_NAME_RE.finditer(hlo):
        scope = op_scope(m.group(2))
        if scope is not None:
            out[m.group(1)] = scope
    return out


class BFSEngine:
    """A compiled traversal session: graph shipped once, program
    compiled once, traversed from many roots.

    Attributes:
      ship_s          seconds to device_put the graph arrays (once)
      compile_s       seconds to trace + XLA-compile the single-root
                      search (once, eagerly at compile())
      batch_compile_s cumulative seconds compiling pod-batched programs
                      (one per distinct roots-per-pod shape, lazily at
                      first run_batch)
      trace_count     jit traces taken so far (1 after compile;
                      run/run_many never add more — asserted by tests)
    """

    def __init__(self, plan: BFSPlan, store=None, exec_key: str = "default"):
        if plan.graph is None:
            raise ValueError("plan has no graph attached; build it with "
                             "plan_bfs(graph, cfg, mesh)")
        self.plan = plan
        self.trace_count = 0
        sh = NamedSharding(plan.mesh, P(*plan.axes))
        arrays = plan.graph.device_arrays()
        t0 = time.perf_counter()
        # born-sharded jax.Arrays (device builds, store loads) pass
        # through without a host round-trip — device_put on a correctly
        # sharded array is a no-op, on a mis-sharded one a reshard
        self._gdev = {k: jax.device_put(
            arrays[k] if isinstance(arrays[k], jax.Array)
            else np.asarray(arrays[k]), sh) for k in plan.keys}
        for v in self._gdev.values():
            v.block_until_ready()
        t1 = time.perf_counter()
        self.ship_s = t1 - t0
        self.exec_load_s = 0.0
        self.exec_from_store = False
        if store is not None:
            self._exec = store.load_executable(plan, exec_key)
            if self._exec is not None:
                self.exec_from_store = True
                self.exec_load_s = time.perf_counter() - t1
                self.compile_s = 0.0
                self.batch_compile_s = 0.0
                self._batch_cache: Dict[Tuple[str, int], Any] = {}
                return
        fn = plan.build_fn(trace_hook=self._count_trace)
        # AOT lower+compile: the trace happens here exactly once, and
        # run() calls the compiled executable directly — per-root time
        # can never include compilation.
        self._exec = fn.lower(self._gdev, jnp.int32(0)).compile()
        self.compile_s = time.perf_counter() - t1
        self.batch_compile_s = 0.0
        self._batch_cache: Dict[Tuple[str, int], Any] = {}
        if store is not None:
            store.save_executable(self, exec_key)

    def _count_trace(self):
        self.trace_count += 1

    @property
    def instrument(self) -> bool:
        """Whether the compiled search program carries the counters
        and measured expand words (plan-level; see BFSConfig.instrument).
        False = the latency-lean fast path: one fused scalar reduction
        per level, no counters in the results."""
        return self.plan.statics.instrument

    def collective_counts(self) -> Dict[str, int]:
        """Collective-op counts of the compiled single-root search (the
        static schedule the fast path exists to shrink)."""
        return hlo_collective_counts(self._exec.as_text())

    def op_scopes(self) -> Dict[str, str]:
        """{HLO instruction name: scope path} of the compiled
        single-root search, read from each instruction's op_name: what
        names a device op of a profiler trace by its phase of the level
        program ("bfs.bottomup/discover/edge_rows", ...)."""
        return hlo_op_scopes(self._exec.as_text())

    def _check_root(self, root) -> int:
        """Graphs are padded up to p*chunk vertices; a root in the padded
        ghost range has no edges, so the device program would silently
        return an all-empty parents array.  Validate at the engine
        boundary instead."""
        part = self.plan.part
        root = int(root)
        if not 0 <= root < part.n_orig:
            raise ValueError(
                f"root {root} out of range [0, {part.n_orig}): the graph "
                f"has {part.n_orig} vertices (padded to {part.n} — "
                f"traversing from a padded ghost vertex would return an "
                f"empty tree)")
        return root

    # ---- single-root ------------------------------------------------------

    def search(self, root: int):
        """Device-level search: (pi, level, ctr, stats) as device arrays,
        no host transfer.  Benchmark loops time this (+ a block on pi)
        so per-root numbers measure traversal, not result conversion.
        The root and the parents are input labels: on a relabeled graph
        the program itself maps them (``core/decomp.py::relabeled``)."""
        return self._exec(self._gdev, jnp.int32(self._check_root(root)))

    def to_result(self, out) -> BFSResult:
        """Convert a ``search`` output to the layout-independent
        BFSResult (parents indexed by input vertex label, counters in the
        shared COUNTER_KEYS units) so 1D and 2D runs diff directly."""
        part = self.plan.part
        pi, level, ctr, stats = out
        pi = np.asarray(pi).reshape(part.n)[: part.n_orig]
        return BFSResult(
            parents=pi.astype(np.int64),
            n_levels=int(level),
            counters={k: float(v) for k, v in ctr.items()},
            level_stats=np.asarray(stats),
        )

    def run(self, root: int, validate: bool = False) -> BFSResult:
        """One whole search against the shipped graph, results on host.

        ``validate=True`` runs the sharded Graph500 parent-tree
        validator (core/validate.py) on the DEVICE parent array before
        it ever crosses to host: the report is attached as
        ``result.validation`` and a failing tree raises
        ``ValidationError`` (the result is recoverable from the
        exception's report plus ``validate_parents`` for forensics).
        The validator program is built and compiled lazily on the first
        validated run and reused after that.
        """
        out = self.search(root)
        res = self.to_result(out)
        if validate:
            from repro.core import validate as _validate
            rep = _validate.validate_device(self, self._check_root(root),
                                            out[0])
            res.validation = rep
            if not rep.ok:
                raise _validate.ValidationError(rep)
        return res

    def run_many(self, roots: Sequence[int], validate: bool = False,
                 monitor=None) -> List[BFSResult]:
        """The Graph500 loop: sequential searches from many roots, all
        against the one shipped graph + compiled program.

        ``monitor`` accepts a ``runtime.straggler.StragglerMonitor``:
        each root's wall time (search + host conversion + optional
        validation) is fed through ``monitor.observe(step, dt)`` so
        anomalously slow roots are recorded as events — reported by the
        caller's timing summary, never raised here.
        """
        results = []
        for step, r in enumerate(roots):
            t0 = time.perf_counter()
            results.append(self.run(int(r), validate=validate))
            if monitor is not None:
                monitor.observe(step, time.perf_counter() - t0)
        return results

    # ---- pod-batched multi-source -----------------------------------------

    def run_batch(self, roots: Sequence[int],
                  pod_axis: str = "pod") -> BFSBatchResult:
        """Multi-source BFS with roots sharded over ``pod_axis``: each
        pod scans its len(roots)/pods searches while the level loops
        stay in lockstep.  Works in every registered decomposition (the
        batched program is built from the same Decomposition entry as
        the single-root one).  The batched executable is compiled once
        per (pod_axis, roots-per-pod) shape and cached."""
        mesh = self.plan.mesh
        if pod_axis not in mesh.shape:
            raise ValueError(f"mesh has no {pod_axis!r} axis for batched "
                             f"roots; axes are {tuple(mesh.shape)}")
        pods = mesh.shape[pod_axis]
        roots = np.asarray(roots, dtype=np.int32).reshape(-1)
        if roots.size == 0 or roots.size % pods:
            raise ValueError(f"{roots.size} roots do not split evenly over "
                             f"{pods} pods")
        for r in roots:
            self._check_root(r)
        rdev = jax.device_put(roots, NamedSharding(mesh, P(pod_axis)))
        key = (pod_axis, roots.size // pods)
        if key not in self._batch_cache:
            fn = self.plan.build_batch_fn(pod_axis,
                                          trace_hook=self._count_trace)
            t0 = time.perf_counter()
            self._batch_cache[key] = fn.lower(self._gdev, rdev).compile()
            self.batch_compile_s += time.perf_counter() - t0
        pis, levels, stats = self._batch_cache[key](self._gdev, rdev)
        part, n_axes = self.plan.part, self.plan.entry.n_axes
        # (*block_dims, n_roots, chunk) -> (n_roots, n) in layout A
        pis = np.moveaxis(np.asarray(pis), n_axes, 0)
        pis = pis.reshape(roots.size, part.n)[:, : part.n_orig]
        return BFSBatchResult(
            roots=roots.astype(np.int64),
            parents=pis.astype(np.int64),
            n_levels=np.asarray(levels).astype(np.int64),
            level_stats=np.asarray(stats),
        )


# ---------------------------------------------------------------------------
# Self-healing session: bounded cap_x replan-retry
# ---------------------------------------------------------------------------


@dataclass
class HealedRun:
    """Result of ``run_bfs_healed``: the final (healthy) session plus
    the structured escalation log — one entry per plan attempt, empty
    detail when the first plan was already overflow-free."""
    result: BFSResult
    engine: BFSEngine
    plan: BFSPlan
    retry_log: List[Dict[str, Any]]


def _overflow_levels_1ds(plan: BFSPlan, stats) -> List[int]:
    """Levels whose sparse exchange fell back to the dense bitmap.

    The 1ds exchange NEVER raises on bucket overflow — it reverts the
    level to the dense bitmap (parents stay exact, wire cost jumps to
    the (p-1)*n/64 dense words).  The instrumented run records the
    measured wire per level (stats col 4), so a fallback is detectable
    host-side: a used top-down level whose wire matches the dense
    formula instead of the sparse/compressed words its frontier size
    (stats col 0) predicts.  The double check (== dense AND != sparse)
    keeps frontier sizes sitting exactly at the crossover — where both
    formulas agree and there is nothing to heal — out of the list."""
    part, cfg = plan.part, plan.cfg
    C = plan.statics.expand_chunks
    p = part.p
    stats = np.asarray(stats, dtype=np.float64)
    n_f = stats[:, 0]
    if cfg.frontier_codec == "packed":
        sub = part.chunk // C
        bits = comm_model.codec_bits(sub)
        exp = np.array([comm_model.compressed_expand_1d_words(
            f, p, bits, C) for f in n_f])
    else:
        exp = np.array([comm_model.sparse_expand_1d_words(f, p)
                        for f in n_f])
    dense = comm_model.chunked_expand_1d_level_words(part.n, p, C) \
        if C > 1 else comm_model.expand_1d_level_words(part.n, p)
    exp32 = np.float32(exp).astype(np.float64)
    dense32 = float(np.float32(dense))
    wire = stats[:, 4]
    over = ((stats[:, 3] > 0) & (stats[:, 2] == 0)
            & np.isclose(wire, dense32, rtol=1e-4)
            & ~np.isclose(wire, exp32, rtol=1e-4))
    return [int(i) for i in np.nonzero(over)[0]]


def run_bfs_healed(graph, cfg: BFSConfig, mesh, root: int, *,
                   max_attempts: int = 3, store=None,
                   exec_key: str = "healed", validate: bool = False,
                   **plan_kw) -> HealedRun:
    """Plan + compile + run with bounded ``cap_x`` replan-retry.

    For the "1ds" decomposition an undersized sparse-exchange bucket
    capacity does not corrupt anything — overflowing levels silently
    revert to the dense bitmap — but it forfeits exactly the wire
    savings the sparse exchange exists for.  This driver detects the
    fallback from an instrumented probe run, escalates ``cap_x``
    geometrically (x2 per attempt, clamped to the chunk size where
    overflow is impossible), replans + recompiles, and retries, at most
    ``max_attempts`` plan attempts.  Parents are bit-identical across
    every attempt (fallback levels are exact); the escalation history
    lands in ``HealedRun.retry_log``.  Exhausting the attempts raises
    ``CapacityOverflow`` carrying the full history.

    Non-1ds decompositions have no cap_x knob: single attempt, empty
    retry log.
    """
    from repro.runtime.retry import CapacityOverflow, RetryAttempt

    if cfg.decomposition != "1ds":
        plan = plan_bfs(graph, cfg, mesh, **plan_kw)
        engine = plan.compile(store=store, exec_key=exec_key)
        return HealedRun(result=engine.run(root, validate=validate),
                         engine=engine, plan=plan, retry_log=[])

    probe_cfg = cfg if cfg.instrument else replace(cfg, instrument=True)
    history: List[RetryAttempt] = []
    cap_x = int(plan_kw.pop("cap_x", 0))
    part = graph.part
    for attempt in range(1, max_attempts + 1):
        plan = plan_bfs(graph, probe_cfg, mesh, cap_x=cap_x, **plan_kw)
        cap_now = plan.statics.cap_x
        engine = plan.compile(store=store,
                              exec_key=f"{exec_key}-x{cap_now}")
        res = engine.run(root, validate=validate)
        levels = _overflow_levels_1ds(plan, res.level_stats)
        if not levels:
            history.append(RetryAttempt(
                attempt=attempt, cap_name="cap_x", cap_value=cap_now,
                outcome="ok", detail={}))
            if probe_cfg is not cfg:
                # caller wanted the fast program: rebuild it at the
                # healthy cap (parents bit-identical by construction)
                plan = plan_bfs(graph, cfg, mesh, cap_x=cap_now,
                                **plan_kw)
                engine = plan.compile(store=store,
                                      exec_key=f"{exec_key}-x{cap_now}")
                res = engine.run(root, validate=validate)
            log = [a.to_json() for a in history]
            # drop the no-op log when the FIRST plan was already clean
            if len(log) == 1 and log[0]["outcome"] == "ok":
                log = []
            return HealedRun(result=res, engine=engine, plan=plan,
                             retry_log=log)
        history.append(RetryAttempt(
            attempt=attempt, cap_name="cap_x", cap_value=cap_now,
            outcome="overflow", detail={"levels": levels}))
        nxt = min(cap_now * 2, part.chunk)
        if nxt <= cap_now:
            break
        cap_x = nxt
    raise CapacityOverflow(
        f"cap_x escalation exhausted after {len(history)} attempts "
        f"(levels still falling back to the dense bitmap)",
        cap_name="cap_x", cap_value=cap_now, history=history)
