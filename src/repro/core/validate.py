"""Sharded Graph500 parent-tree validation.

The Graph500 spec requires every timed BFS to be *validated*: the
returned parent array must (1) self-parent the root, (2) use only real
graph edges as tree edges, (3) place each child exactly one level below
its parent, and (4) mark a vertex reachable iff it is in the tree.
Direction-optimizing traversals (arXiv 1208.5542) make this an
end-to-end safety net, not a formality — a bottom-up level that
mis-anchors parents produces a plausible-looking tree only a validator
catches.

This module runs those checks *where the graph lives*: one shard_map
program per plan, reusing the engine's resident device shards (only the
``Decomposition.edge_keys`` fields), with a single (6,) int32 verdict
vector crossing back to host.  No edge list, parent array, or depth
array is ever materialized host-side.

Per-device work (same for all registered decompositions):

- replicate the candidate parent array to the full ``(n,)`` layout-A
  global order (``all_gather(tiled)`` per mesh axis — 1 gather for the
  strip entries, 2 for 2d);
- resolve every vertex's tree depth by pointer doubling over the parent
  array (7 rounds: 2^7 > MAX_LEVELS + 1), saturating at
  ``CAP = MAX_LEVELS + 1`` so cycles, chains through out-of-tree
  vertices, and out-of-range parents all read as "unanchored";
- check tree-edge existence against the LOCAL edge shard via the
  entry's ``local_edges`` hook: a scatter-max marks every vertex whose
  (parent -> vertex) edge is stored here, then one psum ORs the marks
  across the mesh (an edge exists iff SOME shard stores it);
- count violation sites per check over owned vertices / local edge
  slots, and psum the six counters.

Violation counters (``CHECKS`` order):

- ``root_self_parent``: root's stored parent != root.
- ``tree_edge_missing``: an in-tree non-root vertex whose claimed
  parent edge exists in no shard (covers phantom/bit-flipped parents).
- ``parent_chain_broken``: an in-tree vertex whose parent chain never
  reaches the root (cycle, chain through a -1 vertex, parent >= n).
- ``level_span``: a graph edge whose endpoints' tree depths differ by
  more than one — in a genuine BFS tree, depth equals BFS distance and
  every edge spans <= 1 level, so any skew here means some parent is
  not one level above its child.
- ``reach_mismatch``: a graph edge with exactly one endpoint in the
  tree — reachability must saturate, so a reachable out-of-tree vertex
  (or an in-tree vertex with an out-of-tree neighbor) trips this.

Edge-level counts are violation *sites* (each stored orientation of an
undirected edge counts once per shard that stores it); the report is
pass/fail plus per-check tallies, not a deduplicated edge list.

Padded ghost vertices (ids in [n_orig, n)) have no edges and parent
-1 in any legal run, so they can never contribute a violation.

On a graph stored under relabeled ids (``graph.relabel``), the root and
the parent array arrive in input labels, as the search returns them;
the program maps both to the stored ids before the checks.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.decomp import MAX_LEVELS

CHECKS = ("root_self_parent", "tree_edge_missing", "parent_chain_broken",
          "level_span", "reach_mismatch")

# depth saturation: anything that fails to anchor at the root within
# MAX_LEVELS hops reads as CAP; 2**DOUBLING_ROUNDS must exceed CAP.
CAP = MAX_LEVELS + 1
DOUBLING_ROUNDS = 7


@dataclass(frozen=True)
class ValidationReport:
    """Host-side verdict for one (root, parents) pair."""
    root: int
    ok: bool
    violations: Dict[str, int]   # CHECKS -> violation-site count
    n_tree: int                  # vertices with parent >= 0

    def summary(self) -> str:
        if self.ok:
            return (f"valid parent tree: root={self.root}, "
                    f"{self.n_tree} vertices in tree")
        bad = ", ".join(f"{k}={v}" for k, v in self.violations.items()
                        if v)
        return (f"INVALID parent tree: root={self.root}, "
                f"{self.n_tree} vertices in tree; {bad}")

    def to_json(self) -> Dict:
        return {"root": self.root, "ok": self.ok,
                "violations": dict(self.violations),
                "n_tree": self.n_tree}


class ValidationError(RuntimeError):
    """Raised by ``BFSEngine.run(..., validate=True)`` on a bad tree."""

    def __init__(self, report: ValidationReport):
        super().__init__(report.summary())
        self.report = report


def report_from_counts(root: int, counts) -> ValidationReport:
    c = [int(x) for x in np.asarray(counts).reshape(-1)]
    viol = dict(zip(CHECKS, c[: len(CHECKS)]))
    return ValidationReport(root=int(root), ok=not any(viol.values()),
                            violations=viol, n_tree=c[len(CHECKS)])


def build_validate_fn(plan):
    """jit'd ``fn(gdev, parents_dev, root) -> (6,) int32`` for a plan.

    ``gdev`` maps the entry's ``edge_keys`` to mesh-sharded device
    arrays (block layout, P(*axes)); ``parents_dev`` is the
    block-sharded parent array exactly as ``BFSEngine.search`` returns
    it; ``root`` is a replicated int32 scalar.  Collective footprint is
    pinned by ``comm_model.validate_collective_budget`` and checked in
    ``tests/test_perf_guard.py``.
    """
    entry, part, axes = plan.entry, plan.part, plan.axes
    if entry.local_edges is None:
        raise ValueError(
            f"decomposition {entry.name!r} registers no local_edges hook; "
            "the device-side Graph500 validator requires one")
    n = part.n
    chunk = part.chunk
    n_axes = entry.n_axes
    squeeze = (0,) * n_axes
    relabel = plan.statics.relabel

    def body(g, pi, root):
        g = {k: v[squeeze] for k, v in g.items()}
        pi_loc = pi[squeeze].astype(jnp.int32)
        root = root.astype(jnp.int32)

        # parents replicated to (n,) global layout-A order: innermost
        # axis first so each row-gather concatenates contiguous chunks
        pi_all = pi_loc
        for ax in reversed(axes):
            pi_all = lax.all_gather(pi_all, ax, tiled=True)

        idx = [lax.axis_index(ax) for ax in axes]
        blk = idx[0] if n_axes == 1 else idx[0] * part.pc + idx[1]
        base = (blk * chunk).astype(jnp.int32)
        gidx = base + jnp.arange(chunk, dtype=jnp.int32)

        vid = jnp.arange(n, dtype=jnp.int32)
        if relabel is not None:
            # input labels -> the stored ids: stored vertex v is input
            # h^-1(v), and its parent h(pi[h^-1(v)])
            root = relabel.apply(root, jnp)
            pi_all = relabel.apply(pi_all[relabel.invert(vid, jnp)], jnp)
            pi_loc = lax.dynamic_slice(pi_all, (base,), (chunk,))
        in_tree = pi_all >= 0
        ok_ref = in_tree & (pi_all < n)      # parent is a usable index
        is_root = vid == root
        # pointer doubling: hop[v] saturates at CAP unless v's chain
        # reaches the root through in-tree, in-range parents
        anc = jnp.where(ok_ref & ~is_root, pi_all, vid)
        hop = jnp.where(is_root, 0,
                        jnp.where(ok_ref, 1, CAP)).astype(jnp.int32)
        for _ in range(DOUBLING_ROUNDS):
            hop = jnp.minimum(hop + hop[anc], CAP)
            anc = anc[anc]
        depth = hop

        # local tree-edge existence: mark v if (parent[v] -> v) is a
        # stored edge slot here, then OR marks across every shard
        u, v, valid = entry.local_edges(g, part, axes)
        want = jnp.where(ok_ref, pi_all, n)  # n matches no stored u
        hit = valid & (u == want[v])
        found = jnp.zeros(n, jnp.int32).at[v].max(
            hit.astype(jnp.int32), mode="drop")
        found = lax.psum(found, axes)

        # edge-slot checks (local counts; summed at the end)
        du, dv = depth[u], depth[v]
        tu, tv = in_tree[u], in_tree[v]
        v_span = jnp.sum(valid & tu & tv & (jnp.abs(du - dv) > 1),
                         dtype=jnp.int32)
        v_reach = jnp.sum(valid & (tu != tv), dtype=jnp.int32)

        # owned-vertex checks on this block's chunk
        own_in = pi_loc >= 0
        not_root = gidx != root
        v_root = jnp.sum((gidx == root) & (pi_loc != root),
                         dtype=jnp.int32)
        depth_own = lax.dynamic_slice(depth, (base,), (chunk,))
        v_chain = jnp.sum(own_in & not_root & (depth_own >= CAP),
                          dtype=jnp.int32)
        found_own = lax.dynamic_slice(found, (base,), (chunk,)) > 0
        v_edge = jnp.sum(own_in & not_root & ~found_own,
                         dtype=jnp.int32)
        n_tree = jnp.sum(own_in, dtype=jnp.int32)

        counts = jnp.stack([v_root, v_edge, v_chain, v_span, v_reach,
                            n_tree])
        return lax.psum(counts, axes)

    gspec = {k: P(*axes) for k in entry.edge_keys}
    mapped = jax.shard_map(body, mesh=plan.mesh,
                           in_specs=(gspec, P(*axes), P()),
                           out_specs=P(), check_vma=False)
    return jax.jit(mapped)


def _edge_arrays(engine):
    """The entry's edge_keys shards on device, reusing the engine's
    resident graph arrays where the keys overlap ``plan.keys``."""
    plan = engine.plan
    if getattr(engine, "_vdev", None) is None:
        arrays = plan.graph.device_arrays()
        sh = NamedSharding(plan.mesh, P(*plan.axes))
        vdev = {}
        for k in plan.entry.edge_keys:
            if k in engine._gdev:
                vdev[k] = engine._gdev[k]
            else:
                a = arrays[k]
                vdev[k] = a if isinstance(a, jax.Array) \
                    else jax.device_put(np.asarray(a), sh)
        engine._vdev = vdev
    return engine._vdev


def _validate_fn(engine):
    if getattr(engine, "_vfn", None) is None:
        engine._vfn = build_validate_fn(engine.plan)
    return engine._vfn


def validate_device(engine, root: int, pi_dev) -> ValidationReport:
    """Validate a block-sharded device parent array in place."""
    fn = _validate_fn(engine)
    counts = fn(_edge_arrays(engine), pi_dev, jnp.int32(root))
    return report_from_counts(root, np.asarray(counts))


def validate_parents(engine, root: int, parents) -> ValidationReport:
    """Validate a HOST parent array (``(n_orig,)`` or ``(n,)`` flat, or
    already block-shaped) against the engine's graph shards.

    This is the entry point for post-hoc validation — results restored
    from disk, batch outputs, fault-injection probes.  The array is
    padded with -1 ghosts to ``n``, reshaped to the plan's block
    layout, and shipped sharded; only the (6,) verdict returns.
    """
    plan = engine.plan
    part = plan.part
    root = engine._check_root(root)
    flat = np.asarray(parents).reshape(-1).astype(np.int64)
    if flat.shape[0] == part.n_orig:
        full = np.full(part.n, -1, np.int64)
        full[: part.n_orig] = flat
    elif flat.shape[0] == part.n:
        full = flat
    else:
        raise ValueError(
            f"parents has {flat.shape[0]} entries; expected n_orig="
            f"{part.n_orig} or padded n={part.n}")
    # device parents are int32; clamp so host int64 garbage (e.g. a
    # bit flip above bit 31) still reads as an out-of-range parent
    # instead of wrapping back into range
    full = np.clip(full, -1, np.iinfo(np.int32).max).astype(np.int32)
    if plan.entry.n_axes == 1:
        blocks = full.reshape(part.p, part.chunk)
    else:
        blocks = full.reshape(part.pr, part.pc, part.chunk)
    pi_dev = jax.device_put(
        blocks, NamedSharding(plan.mesh, P(*plan.axes)))
    return validate_device(engine, root, pi_dev)
