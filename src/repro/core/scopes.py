"""The named scopes of the level program: one vocabulary, so that a
profiler trace of any decomposition is read by the same names.

Every level runs under one top scope, its phases nested below it:

  bfs.topdown    expand, discover, fold
  bfs.bottomup   expand (the frontier gather and, in 2d, the ring
                 rotation of the completed bitmap), discover (the local
                 scan, with edge_rows nested around its per-edge row
                 read), update
  bfs.reduce     the loop's per-level reduction, the direction decision
                 and the level_stats row

and, outside the level loop, on a graph stored under relabeled vertex
ids (more than one shard, ``graph/dist_build.py``):

  bfs.relabel    the root mapped in, and the parents gathered and
                 mapped back to input labels

The scopes are ``jax.named_scope`` metadata: each compiled instruction's
``op_name`` carries the path (through JAX's own ``while/body``,
``cond/branch_*`` components), and ``op_scope`` reads it back.  They add
no instruction and no collective to the program, and every collective
of the timed program lies outside ``discover``: discovery is local work.
"""
from __future__ import annotations

from typing import Optional

TOPDOWN = "bfs.topdown"
BOTTOMUP = "bfs.bottomup"
REDUCE = "bfs.reduce"
RELABEL = "bfs.relabel"
EXPAND = "expand"
DISCOVER = "discover"
FOLD = "fold"
UPDATE = "update"
EDGE_ROWS = "edge_rows"

TOP_SCOPES = (TOPDOWN, BOTTOMUP, REDUCE, RELABEL)
PHASES = (EXPAND, DISCOVER, FOLD, UPDATE, EDGE_ROWS)


def op_scope(op_name: str) -> Optional[str]:
    """The scope path of an instruction's ``op_name``
    ("jit(f)/while/body/cond/branch_1_fun/bfs.bottomup/discover/
    edge_rows/searchsorted/..." -> "bfs.bottomup/discover/edge_rows"):
    the innermost top scope and the phases named under it, JAX's own
    path components left out.  None outside every top scope."""
    parts = op_name.split("/")
    tops = [k for k, part in enumerate(parts) if part in TOP_SCOPES]
    if not tops:
        return None
    k = tops[-1]
    return "/".join([parts[k]] + [p for p in parts[k + 1:] if p in PHASES])
