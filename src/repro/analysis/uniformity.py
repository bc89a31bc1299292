"""Mesh-uniformity lattice over jaxpr values.

The abstract value of every jaxpr variable is the set of mesh axes the
value is *provably uniform over*: every pair of devices differing only
along those axes holds bit-identical contents.  The lattice is the
powerset of mesh axes ordered by inclusion; meet is intersection;
constants/literals sit at top (uniform over everything), shard-resident
data at whatever its sharding leaves.

Transfer functions (the SPMD facts the linter rests on):

  * shard_map input sharded over axes S  ->  uniform over mesh - S
    (a replicated input — empty spec — is uniform everywhere)
  * ``axis_index(a)``                    ->  uniform over mesh - {a}
  * ``psum/pmax/pmin`` over axes S (no axis_index_groups): the result
    is bit-identical on every member of the reduction group ->
    in ∪ S.  Grouped reductions only unify within each group, which
    the axes no longer describe -> conservatively ``in``.
  * ``all_gather`` over S: every member receives the same concatenated
    buffer -> in ∪ S
  * ``all_to_all`` over S: each member keeps a different slice ->
    in - S
  * ``ppermute``: a permutation moves values between devices but a
    value uniform over an axis set stays uniform (all sources agree)
    -> in
  * pure eqns: meet of the inputs
  * ``cond``: branch bodies evaluate under the predicate's uniformity;
    outputs are the meet over branches, met with the predicate (a
    divergent predicate makes every output divergent)
  * ``while``/``scan`` carries: fixpoint iteration — carry(k+1) =
    init ∩ body_out(carry(k)); the lattice is finite and the
    transfer monotone, so this terminates

Alongside the abstract values the walker records every *collective
site* (kind, axes, the stack of enclosing predicates, a path) and
every *cond record* (predicate + per-branch ordered collective
sequences) — the raw material for rules R1–R3 in
``repro.analysis.rules``.  Each abstract value also carries a short
provenance string (``desc``) naming the binding constraint — the
collective or sharded input its uniformity came from — so findings can
name the non-uniform predicate in source terms.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

# collectives that rendezvous between devices (jaxpr primitive names)
REDUCTIONS = ("psum", "pmax", "pmin")
DATA_COLLECTIVES = ("all_gather", "all_to_all", "ppermute",
                    "psum_scatter", "all_to_all_invariant", "pbroadcast")
COLLECTIVES = REDUCTIONS + DATA_COLLECTIVES

# a fixpoint that hasn't stabilized after this many sweeps is a walker
# bug, not a real program (the lattice height bounds it far lower)
_MAX_FIXPOINT_SWEEPS = 64

# R2 marker: a nested cond whose branches already disagree
MISMATCH = ("<branch-mismatch>", ())


@dataclass(frozen=True)
class AbstractVal:
    """One lattice point: the axes a value is uniform over, plus the
    provenance of the *binding* constraint (smallest contributor)."""
    unif: frozenset
    desc: str

    def meet(self, other: "AbstractVal") -> "AbstractVal":
        u = self.unif & other.unif
        # keep the description of whichever input constrains the result
        desc = self.desc if len(self.unif) <= len(other.unif) else other.desc
        return AbstractVal(u, desc)


@dataclass(frozen=True)
class Pred:
    """One enclosing control-flow predicate."""
    kind: str          # "cond" | "while"
    unif: frozenset    # axes the predicate is provably uniform over
    desc: str          # provenance, e.g. "psum over ('data', 'model')"
    path: str


@dataclass(frozen=True)
class CollectiveSite:
    """One collective eqn and the control context it executes under."""
    kind: str                   # primitive name, e.g. "ppermute"
    axes: Tuple[str, ...]       # the op's named mesh axes
    preds: Tuple[Pred, ...]     # enclosing predicates, outermost first
    path: str

    def rendezvous(self, mesh_axes: Sequence[str]) -> Tuple[str, ...]:
        """Axes whose devices this op rendezvouses with.  XLA lowers
        collective-permute as one whole-program instruction regardless
        of source_target_pairs — every device participates — while
        all-reduce/-gather/-to-all carry replica_groups and stay local
        to the named axes."""
        if self.kind == "ppermute":
            return tuple(mesh_axes)
        return self.axes


@dataclass(frozen=True)
class CondRecord:
    """One lax.cond: predicate + each branch's collective sequence.
    A sequence element is (kind, axes); nested conds whose branches
    agree contribute their merged sequence, disagreeing ones a
    MISMATCH marker (which R2 always treats as a difference)."""
    pred: Pred
    path: str
    branch_seqs: Tuple[Tuple[Tuple[str, Tuple[str, ...]], ...], ...]


@dataclass
class Analysis:
    """Walker output for one closed jaxpr."""
    mesh_axes: Tuple[str, ...]
    sites: List[CollectiveSite]
    conds: List[CondRecord]
    out_vals: List[AbstractVal]   # top-level jaxpr outputs


def _norm_axes(ax) -> Tuple[str, ...]:
    if isinstance(ax, (tuple, list)):
        return tuple(ax)
    return (ax,)


def _sub_jaxprs(params) -> List[ClosedJaxpr]:
    """Every jaxpr-valued param of an eqn (pjit, custom_jvp, remat...)."""
    found = []
    for v in params.values():
        if isinstance(v, (ClosedJaxpr, Jaxpr)):
            found.append(v)
    return found


def _as_closed(j) -> ClosedJaxpr:
    return j if isinstance(j, ClosedJaxpr) else ClosedJaxpr(j, ())


class _Walker:
    def __init__(self, mesh_axes: Sequence[str]):
        self.mesh_axes = tuple(mesh_axes)
        self.full = frozenset(mesh_axes)
        self.sites: List[CollectiveSite] = []
        self.conds: List[CondRecord] = []

    # -- environment helpers ------------------------------------------------

    def _read(self, env: Dict, atom) -> AbstractVal:
        if isinstance(atom, Literal):
            return AbstractVal(self.full, "constant")
        return env[atom]

    def _meet_inputs(self, env, eqn) -> AbstractVal:
        vals = [self._read(env, a) for a in eqn.invars]
        if not vals:
            return AbstractVal(self.full, "constant")
        out = vals[0]
        for v in vals[1:]:
            out = out.meet(v)
        return out

    # -- jaxpr evaluation ---------------------------------------------------

    def eval_closed(self, cj, in_vals: Sequence[AbstractVal],
                    preds: Tuple[Pred, ...], path: str, record: bool):
        """Returns (out_vals, collective_seq)."""
        cj = _as_closed(cj)
        jaxpr = cj.jaxpr
        env: Dict = {}
        for v in jaxpr.constvars:
            env[v] = AbstractVal(self.full, "constant")
        assert len(jaxpr.invars) == len(in_vals), (
            f"jaxpr arity mismatch at {path}: "
            f"{len(jaxpr.invars)} vars, {len(in_vals)} values")
        for v, val in zip(jaxpr.invars, in_vals):
            env[v] = val
        seq: List[Tuple[str, Tuple[str, ...]]] = []
        for i, eqn in enumerate(jaxpr.eqns):
            self._eval_eqn(env, eqn, preds, f"{path}/{i}:{eqn.primitive.name}",
                           record, seq)
        return [self._read(env, v) for v in jaxpr.outvars], seq

    def _bind(self, env, eqn, vals: Sequence[AbstractVal]):
        assert len(eqn.outvars) == len(vals)
        for v, val in zip(eqn.outvars, vals):
            env[v] = val

    def _eval_eqn(self, env, eqn, preds, path, record, seq):
        name = eqn.primitive.name
        params = eqn.params

        if name in COLLECTIVES:
            self._eval_collective(env, eqn, preds, path, record, seq)
        elif name == "axis_index":
            ax = params["axis_name"]
            self._bind(env, eqn, [AbstractVal(self.full - {ax},
                                              f"axis_index({ax!r})")])
        elif name == "shard_map":
            self._eval_shard_map(env, eqn, preds, path, record, seq)
        elif name == "cond":
            self._eval_cond(env, eqn, preds, path, record, seq)
        elif name == "while":
            self._eval_while(env, eqn, preds, path, record, seq)
        elif name == "scan":
            self._eval_scan(env, eqn, preds, path, record, seq)
        elif name == "pallas_call":
            # opaque pure kernel: no collectives inside, outputs inherit
            # the meet of the inputs
            val = self._meet_inputs(env, eqn)
            self._bind(env, eqn, [val] * len(eqn.outvars))
        elif _sub_jaxprs(params):
            # transparent call-like primitives: pjit, closed_call,
            # custom_jvp/vjp_call, remat... — recurse into the (single)
            # sub-jaxpr with the eqn inputs
            subs = _sub_jaxprs(params)
            sub = _as_closed(subs[0])
            n = len(sub.jaxpr.invars)
            in_vals = [self._read(env, a) for a in eqn.invars]
            if len(in_vals) >= n:
                # call-like prims may append/prepend tangent args; keep
                # the trailing n (pjit passes exactly n)
                in_vals = in_vals[len(in_vals) - n:]
                out_vals, sub_seq = self.eval_closed(
                    sub, in_vals, preds, path, record)
                seq.extend(sub_seq)
                self._bind(env, eqn, out_vals[: len(eqn.outvars)])
            else:  # arity surprise: fall back to conservative meet
                val = self._meet_inputs(env, eqn)
                self._bind(env, eqn, [val] * len(eqn.outvars))
        else:
            # pure eqn: meet of the inputs
            val = self._meet_inputs(env, eqn)
            self._bind(env, eqn, [val] * len(eqn.outvars))

    # -- collectives --------------------------------------------------------

    def _eval_collective(self, env, eqn, preds, path, record, seq):
        name = eqn.primitive.name
        params = eqn.params
        axes = _norm_axes(params.get("axes", params.get("axis_name", ())))
        grouped = params.get("axis_index_groups") is not None
        in_val = self._meet_inputs(env, eqn)
        desc = f"{name} over {axes!r}"
        if name in REDUCTIONS and not grouped:
            out = AbstractVal(in_val.unif | set(axes), desc)
        elif name == "all_gather" and not grouped:
            out = AbstractVal(in_val.unif | set(axes), desc)
        elif name in ("all_to_all", "all_to_all_invariant", "psum_scatter"):
            out = AbstractVal(in_val.unif - set(axes), in_val.desc)
        else:  # ppermute / grouped / pbroadcast: preserve the input
            out = AbstractVal(in_val.unif, in_val.desc)
        self._bind(env, eqn, [out] * len(eqn.outvars))
        if record:
            self.sites.append(CollectiveSite(name, axes, preds, path))
        seq.append((name, axes))

    # -- structured control flow --------------------------------------------

    def _eval_shard_map(self, env, eqn, preds, path, record, seq):
        params = eqn.params
        inner = _as_closed(params["jaxpr"])
        manual = frozenset(params["manual_axes"])
        in_vals = []
        for spec in params["in_specs"]:
            # axes named by the input's PartitionSpec that the body sees
            # as manual: the per-device blocks differ along exactly these
            used = set()
            for entry in spec:
                if entry is not None:
                    used.update(_norm_axes(entry))
            used &= manual
            if used:
                in_vals.append(AbstractVal(
                    self.full - used,
                    f"shard_map input sharded over {tuple(sorted(used))}"))
            else:
                in_vals.append(AbstractVal(self.full, "replicated input"))
        out_vals, sub_seq = self.eval_closed(inner, in_vals, preds,
                                             f"{path}/shard_map", record)
        seq.extend(sub_seq)
        self._bind(env, eqn, out_vals)

    def _eval_cond(self, env, eqn, preds, path, record, seq):
        branches = eqn.params["branches"]
        idx_val = self._read(env, eqn.invars[0])
        op_vals = [self._read(env, a) for a in eqn.invars[1:]]
        pred = Pred("cond", idx_val.unif, idx_val.desc, path)
        sub_preds = preds + (pred,)
        branch_outs, branch_seqs = [], []
        for b, bj in enumerate(branches):
            outs, bseq = self.eval_closed(bj, op_vals, sub_preds,
                                          f"{path}[branch {b}]", record)
            branch_outs.append(outs)
            branch_seqs.append(tuple(bseq))
        out_vals = []
        for outs in zip(*branch_outs):
            val = outs[0]
            for o in outs[1:]:
                val = val.meet(o)
            out_vals.append(AbstractVal(val.unif & pred.unif, val.desc))
        self._bind(env, eqn, out_vals)
        if record:
            self.conds.append(CondRecord(pred, path, tuple(branch_seqs)))
        # R2 sequence merging: agreeing branches contribute their shared
        # sequence upward; disagreeing ones poison the parent with a
        # mismatch marker
        if len(set(branch_seqs)) == 1:
            seq.extend(branch_seqs[0])
        else:
            seq.append(MISMATCH)

    def _eval_while(self, env, eqn, preds, path, record, seq):
        params = eqn.params
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        invals = [self._read(env, a) for a in eqn.invars]
        cconsts, bconsts = invals[:cn], invals[cn:cn + bn]
        init = invals[cn + bn:]
        carry = list(init)
        pred = None
        for _ in range(_MAX_FIXPOINT_SWEEPS):
            pred_outs, _ = self.eval_closed(
                params["cond_jaxpr"], cconsts + carry, preds,
                f"{path}/while.cond", record=False)
            pred = Pred("while", pred_outs[0].unif, pred_outs[0].desc,
                        f"{path}/while.cond")
            body_outs, _ = self.eval_closed(
                params["body_jaxpr"], bconsts + carry, preds + (pred,),
                f"{path}/while.body", record=False)
            new = [i.meet(b) for i, b in zip(init, body_outs)]
            if [v.unif for v in new] == [v.unif for v in carry]:
                carry = new
                break
            carry = new
        else:  # pragma: no cover - lattice is finite, cannot happen
            raise RuntimeError(f"uniformity fixpoint diverged at {path}")
        # stable: one recording pass through cond + body
        pred_outs, _ = self.eval_closed(
            params["cond_jaxpr"], cconsts + carry, preds,
            f"{path}/while.cond", record)
        pred = Pred("while", pred_outs[0].unif, pred_outs[0].desc,
                    f"{path}/while.cond")
        body_outs, body_seq = self.eval_closed(
            params["body_jaxpr"], bconsts + carry, preds + (pred,),
            f"{path}/while.body", record)
        seq.extend(body_seq)
        outs = [AbstractVal(i.meet(b).unif & pred.unif, i.meet(b).desc)
                for i, b in zip(init, body_outs)]
        self._bind(env, eqn, outs)

    def _eval_scan(self, env, eqn, preds, path, record, seq):
        params = eqn.params
        nc, ncar = params["num_consts"], params["num_carry"]
        invals = [self._read(env, a) for a in eqn.invars]
        consts, init, xs = invals[:nc], invals[nc:nc + ncar], invals[nc + ncar:]
        carry = list(init)
        for _ in range(_MAX_FIXPOINT_SWEEPS):
            outs, _ = self.eval_closed(
                params["jaxpr"], consts + carry + xs, preds,
                f"{path}/scan.body", record=False)
            new = [i.meet(b) for i, b in zip(init, outs[:ncar])]
            if [v.unif for v in new] == [v.unif for v in carry]:
                carry = new
                break
            carry = new
        else:  # pragma: no cover
            raise RuntimeError(f"uniformity fixpoint diverged at {path}")
        outs, body_seq = self.eval_closed(
            params["jaxpr"], consts + carry + xs, preds,
            f"{path}/scan.body", record)
        seq.extend(body_seq)
        self._bind(env, eqn, list(outs[:ncar]) + list(outs[ncar:]))


def analyze_jaxpr(closed_jaxpr, mesh_axes: Sequence[str],
                  in_vals: Optional[Sequence[AbstractVal]] = None
                  ) -> Analysis:
    """Walk a closed jaxpr and return the collective sites, cond
    records, and output lattice values.

    ``mesh_axes`` is the full mesh the program runs on (pod axis
    included for batched programs).  Top-level inputs default to
    uniform-everywhere, which matches host-level values entering a
    jitted program before any shard_map (the shard_map eqn re-seeds
    its body's inputs from ``in_specs``); pass explicit ``in_vals``
    when analyzing a bare shard_map *body* jaxpr directly."""
    w = _Walker(mesh_axes)
    cj = _as_closed(closed_jaxpr)
    if in_vals is None:
        in_vals = [AbstractVal(w.full, "program input")
                   for _ in cj.jaxpr.invars]
    out_vals, _ = w.eval_closed(cj, list(in_vals), (), "", record=True)
    return Analysis(mesh_axes=tuple(mesh_axes), sites=w.sites,
                    conds=w.conds, out_vals=out_vals)
