"""Registry-wide enumeration for the collective-schedule linter.

Two enumerations, both driven by the Decomposition registry so a new
entry is covered the day it registers:

  * ``lint_combos()`` — every decomposition × (local_mode, storage)
    LocalOps combo × instrument on/off × expand_chunks {1, 2} × the
    entry's other ``schedule_dims`` values (codec for 1ds, fold/compact
    for 2d).  ``lint_registry()`` traces each combo's pod-batched
    program (pods = 2 — the mesh shape where divergence hazards live)
    plus one single-mesh program per entry, and runs rules R1–R3 on
    the closed jaxpr.

  * ``budget_cases()`` — the cross product of each entry's
    ``schedule_dims`` domains, each case carrying its
    ``comm_model.level_budgets_for`` budgets.  ``collect_counts()``
    lowers every case's td/bu level bodies and whole-search program
    (instrument on and off, lowering only — no XLA compile) and is the
    ONE source of truth behind both the R4 rule and
    tests/test_perf_guard.py (which keeps the previously pinned values
    as explicit regression assertions on top).

Everything here lowers against ShapeDtypeStructs on forced host
devices; nothing executes.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from repro.core import comm_model

# value domains of the BFSConfig fields entries may list in
# schedule_dims (first value = the canonical default for that sweep)
SCHEDULE_DOMAINS: Dict[str, Tuple] = {
    "fold_mode": ("alltoall", "reduce", "bitmap"),
    "compact_updates": (False, True),
    "frontier_codec": ("packed", "none"),
    "expand_chunks": (1, 2),
}

# the graph/mesh family every enumeration lowers against: the scale-9
# R-MAT from the original perf guard, on p=8 strips / a 2x4 grid
# (pods = 2 for the batched lint programs -> 16 forced host devices)
SCALE, EDGE_FACTOR, SEED = 9, 8, 3
GRID_PR, GRID_PC, STRIP_P, PODS = 2, 4, 8, 2


def _short(dim: str, val) -> str:
    if dim == "fold_mode":
        return f"fold={val}"
    if dim == "compact_updates":
        return f"compact={int(val)}"
    if dim == "frontier_codec":
        return f"codec={val}"
    if dim == "expand_chunks":
        return f"c={val}"
    return f"{dim}={val}"


def case_name(decomposition: str, overrides: Dict[str, Any]) -> str:
    """Canonical name of one schedule case, e.g.
    ``2d[fold=alltoall,compact=0,c=1]`` — dims in the entry's declared
    order, every dim spelled even at its default so names are stable."""
    from repro.core.decomp import get_decomposition
    entry = get_decomposition(decomposition)
    toks = []
    for dim in entry.schedule_dims:
        val = overrides.get(dim, SCHEDULE_DOMAINS[dim][0])
        toks.append(_short(dim, val))
    return f"{decomposition}[{','.join(toks)}]" if toks else decomposition


@dataclass(frozen=True)
class BudgetCase:
    """One schedule point of one entry, with its comm-model budgets."""
    name: str
    decomposition: str
    overrides: Dict[str, Any] = field(hash=False)

    def budgets(self, pc: int, p: int) -> Dict[str, int]:
        return comm_model.level_budgets_for(
            self.decomposition, pc=pc, p=p, **self.overrides)


def budget_cases() -> Tuple[BudgetCase, ...]:
    """Cross product of every registered entry's schedule_dims — the
    R4 enumeration.  No hand-written case table: registering an entry
    (with its dims) is what adds its budget coverage."""
    from repro.core.decomp import (get_decomposition,
                                   registered_decompositions)
    cases = []
    for name in registered_decompositions():
        entry = get_decomposition(name)
        dims = entry.schedule_dims
        for vals in itertools.product(*(SCHEDULE_DOMAINS[d] for d in dims)):
            ov = dict(zip(dims, vals))
            cases.append(BudgetCase(case_name(name, ov), name, ov))
    return tuple(cases)


# ---------------------------------------------------------------------------
# Lowering helpers (shared with tests/_perf_guard_main.py)
# ---------------------------------------------------------------------------


def _sds(a):
    import jax
    import numpy as np
    a = np.asarray(a)
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def _graph_sds(plan):
    return {k: _sds(v) for k, v in plan.graph.device_arrays().items()
            if k in plan.keys}


def search_counts(plan) -> Dict[str, int]:
    """Collective counts of the lowered whole-search program."""
    import jax.numpy as jnp
    from repro.core.engine import hlo_collective_counts
    txt = plan.build_fn().lower(_graph_sds(plan), jnp.int32(0)).as_text()
    return hlo_collective_counts(txt)


def level_counts(plan, which: str) -> Dict[str, int]:
    """Collective counts of ONE lowered level step body (td or bu) —
    the per-level schedule minus the loop's fused reduction.  The
    fast-path ``lv`` context is threaded as a replicated input; the
    instrumented step gets lv=None, exactly as _search_loop calls it.
    The steps come from the entry's ``level_steps`` declaration."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import steps
    from repro.core.engine import hlo_collective_counts

    if plan.entry.level_steps is None:
        raise ValueError(
            f"decomposition {plan.entry.name!r} declares no level_steps; "
            f"the R4 budget lowering needs them")
    args = plan.level_args()
    nax = plan.entry.n_axes
    td, bu = plan.entry.level_steps
    step = td if which == "td" else bu
    sq = (0,) * nax

    ctr_keys = steps.COUNTER_KEYS if args.instrument else ()

    def fn(garr, pi, front, over):
        gl = {k: v[sq] for k, v in garr.items()}
        lv = None if args.instrument else {"over": over}
        pi2, f2, ctr = step(gl, pi[sq], front[sq], args, lv)
        # ctr must stay a live output or the counter psums get DCE'd —
        # the whole point is counting what the instrumented level pays
        return pi2.reshape((1,) * nax + pi2.shape), dict(ctr)

    spec = P(*plan.axes)
    gspec = {k: spec for k in plan.keys}
    mapped = jax.shard_map(fn, mesh=plan.mesh,
                           in_specs=(gspec, spec, spec, P()),
                           out_specs=(spec, {k: P() for k in ctr_keys}),
                           check_vma=False)
    arrs = _graph_sds(plan)
    pi = jax.ShapeDtypeStruct(arrs["deg_A"].shape, np.int32)
    fr = jax.ShapeDtypeStruct(arrs["deg_A"].shape, np.bool_)
    txt = jax.jit(mapped).lower(arrs, pi, fr,
                                jnp.zeros((), bool)).as_text()
    return hlo_collective_counts(txt)


def _inputs(family: str, batched: bool):
    """The shared scale-9 graph + mesh for one decomposition family.
    Graphs and meshes are cached; the pod meshes (16 devices) are only
    created when a batched program asks for them, so the budget-only
    sweep runs on 8 forced host devices."""
    if "graphs" not in _CACHE:
        from repro.graph.formats import build_blocked, build_blocked_1d
        from repro.graph.rmat import rmat_graph
        e = rmat_graph(SCALE, edge_factor=EDGE_FACTOR, seed=SEED)
        _CACHE["graphs"] = {
            "2d": build_blocked(e, GRID_PR, GRID_PC, align=32, cap_pad=32),
            # with_col_ptr: the kernel/csr combos ship the uncompressed
            # column pointers — the sweep covers every LocalOps combo
            "1d": build_blocked_1d(e, STRIP_P, align=32, cap_pad=32,
                                   with_col_ptr=True),
        }
    key = (family, batched)
    if key not in _CACHE:
        from repro.launch.mesh import make_local_mesh, make_local_mesh_1d
        pods = PODS if batched else 0
        _CACHE[key] = (make_local_mesh(GRID_PR, GRID_PC, pods=pods)
                       if family == "2d"
                       else make_local_mesh_1d(STRIP_P, pods=pods))
    return _CACHE["graphs"][family], _CACHE[key]


_CACHE: Dict = {}


def _family(decomposition: str) -> str:
    from repro.core.decomp import get_decomposition
    from repro.core.partition import Partition2D
    entry = get_decomposition(decomposition)
    return "2d" if entry.partition_cls is Partition2D else "1d"


def plan_case(decomposition: str, overrides: Dict[str, Any], *,
              instrument: bool, local_mode: str = "dense",
              storage: str = "csr", batched: bool = False):
    """A concrete plan for one enumerated case on the shared inputs."""
    from repro.configs.base import BFSConfig
    from repro.core.engine import plan_bfs
    graph, mesh = _inputs(_family(decomposition), batched)
    cfg = BFSConfig(decomposition=decomposition, instrument=instrument,
                    storage=storage, **overrides)
    return plan_bfs(graph, cfg, mesh, local_mode=local_mode)


def validator_counts(decomposition: str) -> Dict[str, int]:
    """Collective counts of the lowered Graph500 parent-tree validator
    for one registered decomposition (lowering only).  The validator is
    schedule-dim-independent — one program per decomposition — and its
    footprint is pinned against ``comm_model.validate_collective_budget``
    in tests/test_perf_guard.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import hlo_collective_counts
    from repro.core.validate import build_validate_fn

    plan = plan_case(decomposition, {}, instrument=False)
    fn = build_validate_fn(plan)
    arrays = plan.graph.device_arrays()
    gsds = {k: _sds(arrays[k]) for k in plan.entry.edge_keys}
    pi = jax.ShapeDtypeStruct(np.asarray(arrays["deg_A"]).shape,
                              np.int32)
    txt = fn.lower(gsds, pi, jnp.int32(0)).as_text()
    return hlo_collective_counts(txt)


def collect_counts() -> Dict[str, Any]:
    """The perf-guard payload: lowered collective counts of every
    ``budget_cases()`` case (td/bu level bodies + whole search,
    instrument on and off), keyed by canonical case name — plus the
    parent-tree validators under ``"validators"``."""
    from repro.core.decomp import registered_decompositions

    out: Dict[str, Any] = {"pc": GRID_PC, "p": STRIP_P}
    for case in budget_cases():
        row = {}
        for label, instr in (("fast", False), ("instrumented", True)):
            plan = plan_case(case.decomposition, case.overrides,
                             instrument=instr)
            row[label] = {
                "search": search_counts(plan),
                "td": level_counts(plan, "td"),
                "bu": level_counts(plan, "bu"),
            }
        out[case.name] = row
    out["validators"] = {name: validator_counts(name)
                         for name in registered_decompositions()}
    return out


def budget_findings(counts: Optional[Dict[str, Any]] = None) -> List:
    """R4 over the full enumeration: every case's instrument-off level
    bodies vs its comm-model budgets."""
    from repro.analysis.rules import check_budget
    counts = counts if counts is not None else collect_counts()
    pc, p = counts["pc"], counts["p"]
    findings = []
    for case in budget_cases():
        budgets = case.budgets(pc, p)
        fast = counts[case.name]["fast"]
        for mode in ("td", "bu"):
            findings.extend(check_budget(
                fast[mode], budgets[mode], combo=case.name, mode=mode))
    return findings


# ---------------------------------------------------------------------------
# Jaxpr lint (rules R1-R3) over plans and the registry
# ---------------------------------------------------------------------------


def lint_plan(plan, *, pod_axis: Optional[str] = None,
              combo: Optional[str] = None) -> List:
    """Run rules R1–R3 on one plan's traced program (the pod-batched
    one when ``pod_axis`` names an axis of the plan's mesh — that is
    where divergence hazards live).  Needs a concrete graph attached
    (shapes for the trace); nothing is lowered or compiled."""
    import jax
    import numpy as np

    from repro.analysis.rules import (check_axis_layout,
                                      check_branch_schedules,
                                      check_divergent_collectives)
    from repro.analysis.uniformity import analyze_jaxpr

    if plan.graph is None:
        raise ValueError("lint needs a plan with a graph attached "
                         "(plan_bfs, not plan_for_part)")
    combo = combo or f"{plan.entry.name}/{plan.ops.local_mode}/" \
                     f"{plan.cfg.storage}"
    arrs = _graph_sds(plan)
    mesh_axes = tuple(plan.mesh.shape)
    if pod_axis is not None:
        pods = plan.mesh.shape[pod_axis]
        roots = jax.ShapeDtypeStruct((pods,), np.int32)
        cj = jax.make_jaxpr(plan.build_batch_fn(pod_axis))(arrs, roots)
        sync = (pod_axis,)
    else:
        root = jax.ShapeDtypeStruct((), np.int32)
        cj = jax.make_jaxpr(plan.build_fn())(arrs, root)
        sync = ()
    an = analyze_jaxpr(cj, mesh_axes)
    entry = plan.entry
    declared = (tuple(entry.rendezvous_axes(plan.axes, mesh_axes))
                if entry.rendezvous_axes is not None else tuple(mesh_axes))
    findings = check_divergent_collectives(an, combo)
    findings += check_branch_schedules(an, combo)
    findings += check_axis_layout(
        an, combo, entry_name=entry.name, graph_axes=plan.axes,
        sync_axes=sync, declared_rendezvous=declared)
    return findings


@dataclass(frozen=True)
class LintCombo:
    decomposition: str
    local_mode: str
    storage: str
    instrument: bool
    overrides: Dict[str, Any] = field(hash=False)

    @property
    def name(self) -> str:
        instr = "instr" if self.instrument else "fast"
        return (f"{case_name(self.decomposition, self.overrides)}/"
                f"{self.local_mode}/{self.storage}/{instr}")


def lint_combos(quick: bool = False) -> Tuple[LintCombo, ...]:
    """The registry-wide R1–R3 sweep:

    * every (local_mode, storage) LocalOps combo of every entry ×
      instrument on/off × expand_chunks {1, 2} × codec (entries that
      declare it), at the entry's other schedule defaults;
    * plus the full schedule_dims cross product × instrument at
      dense/csr (fold modes and compact updates change the 2d branch
      bodies, so they get their own jaxprs).

    ``quick`` keeps one representative per entry (dense/csr, both
    instrument modes, chunks 1) for fast tests."""
    from repro.core import local_ops
    from repro.core.decomp import (get_decomposition,
                                   registered_decompositions)
    combos: List[LintCombo] = []
    seen = set()

    def add(decomp, lm, st, instr, ov):
        key = (decomp, lm, st, instr, tuple(sorted(ov.items())))
        if key not in seen:
            seen.add(key)
            combos.append(LintCombo(decomp, lm, st, instr, dict(ov)))

    for decomp in registered_decompositions():
        entry = get_decomposition(decomp)
        lm_st = [(lm, st) for d, lm, st in local_ops.registered_combos()
                 if d == decomp] or [("dense", "csr")]
        codecs = (SCHEDULE_DOMAINS["frontier_codec"]
                  if "frontier_codec" in entry.schedule_dims else (None,))
        if quick:
            for instr in (False, True):
                add(decomp, "dense", "csr", instr, {})
            continue
        for (lm, st), instr, chunks, codec in itertools.product(
                lm_st, (False, True), SCHEDULE_DOMAINS["expand_chunks"],
                codecs):
            ov = {"expand_chunks": chunks}
            if codec is not None:
                ov["frontier_codec"] = codec
            add(decomp, lm, st, instr, ov)
        # the full schedule sweep at the default local format
        for vals in itertools.product(
                *(SCHEDULE_DOMAINS[d] for d in entry.schedule_dims)):
            ov = dict(zip(entry.schedule_dims, vals))
            for instr in (False, True):
                add(decomp, "dense", "csr", instr, ov)
    return tuple(combos)


def lint_registry(quick: bool = False,
                  with_budgets: bool = True) -> Dict[str, Any]:
    """The full registry lint: R1–R3 on every combo's pod-batched
    program (plus one single-mesh program per entry), R4 over the
    budget enumeration.  Returns the JSON-ready report."""
    report: Dict[str, Any] = {"combos": [], "findings": []}
    for combo in lint_combos(quick=quick):
        plan = plan_case(combo.decomposition, combo.overrides,
                         instrument=combo.instrument,
                         local_mode=combo.local_mode,
                         storage=combo.storage, batched=True)
        fs = lint_plan(plan, pod_axis="pod", combo=combo.name)
        report["combos"].append({"name": combo.name,
                                 "findings": len(fs)})
        report["findings"].extend(f.to_json() for f in fs)
    # one single-mesh program per entry (no pod axis: trivially uniform
    # predicates — a cheap sanity pass over the non-batched trace path)
    from repro.core.decomp import registered_decompositions
    for decomp in registered_decompositions():
        plan = plan_case(decomp, {}, instrument=True)
        fs = lint_plan(plan, combo=f"{decomp}/single")
        report["combos"].append({"name": f"{decomp}/single",
                                 "findings": len(fs)})
        report["findings"].extend(f.to_json() for f in fs)
    if with_budgets:
        counts = collect_counts()
        fs = budget_findings(counts)
        report["budget_cases"] = [c.name for c in budget_cases()]
        report["findings"].extend(f.to_json() for f in fs)
    report["n_findings"] = len(report["findings"])
    report["clean"] = not report["findings"]
    return report
