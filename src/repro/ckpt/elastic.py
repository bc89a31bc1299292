"""Elastic re-scaling: reshard a checkpointed state onto a different mesh
(grown/shrunk cluster), and re-partition a BlockedGraph onto a different
(pr, pc) processor grid.

Training state is mesh-agnostic on disk (full logical arrays), so elastic
scaling is device_put with the new mesh's shardings — plus validation
that every spec still divides evenly.  Graphs must be structurally
re-blocked (the paper's data layout is grid-dependent)."""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.graph.formats import BlockedGraph, build_blocked
from repro.graph.rmat import EdgeList


def reshard_state(state: Any, specs: Any, new_mesh) -> Any:
    """Place a (host) state pytree onto new_mesh with the given specs."""
    def put(x, spec):
        sh = NamedSharding(new_mesh, spec if spec is not None else P())
        return jax.device_put(np.asarray(x), sh)
    return jax.tree.map(put, state, specs,
                        is_leaf=lambda x: isinstance(x, (np.ndarray,)) or
                        hasattr(x, "shape"))


def repartition_graph(edges: "EdgeList | None" = None, pr: int = 1,
                      pc: int = 1, align: int = 128, cap_pad: int = 128,
                      *, spec=None, mesh=None, decomposition: str = "2d",
                      **build_kw) -> BlockedGraph:
    """Re-block a graph for a new (pr, pc) grid — used when a pod joins or
    leaves mid-campaign (BFS state is cheap to rebuild: one search).

    Two sources:

    * **host EdgeList** (legacy): re-run ``build_blocked`` on the host
      edge array.
    * **BuildSpec** (born-sharded, PR 8): pass ``spec=`` (a
      ``dist_build.BuildSpec``) and ``mesh=`` sized for the NEW grid —
      the graph is rebuilt device-side by ``dist_build`` straight onto
      the new (pr, pc) blocking from the counter stream; no host edge
      list ever exists.  ``decomposition`` picks the target format
      ("2d" checkerboard, "1d"/"1ds" strips on pr*pc devices), and
      extra ``build_kw`` (route_slack, max_attempts, ...) flow through
      to ``dist_build``.  Bit-identical to a host re-block of the same
      stream at matching align/cap_pad, relabeled on more than one
      shard as ``dist_build.build_relabel`` says (test_faultinject pins
      p=1 parity, test_dist_build p=4).
    """
    if spec is not None:
        if mesh is None:
            raise ValueError(
                "repartition_graph(spec=...) needs mesh= sized for the "
                "new grid (BuildSpec repartitioning is device-side)")
        from repro.graph.dist_build import dist_build
        graph, _ = dist_build(spec, decomposition, mesh, (pr, pc),
                              align=align, cap_pad=cap_pad, **build_kw)
        return graph
    if edges is None:
        raise ValueError("repartition_graph needs an EdgeList or a "
                         "BuildSpec (spec=...)")
    return build_blocked(edges, pr, pc, align=align, cap_pad=cap_pad)
