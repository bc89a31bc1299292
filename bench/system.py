"""The system under test, as the benchmark drives it: the born-sharded
device build (``graph/dist_build.py``) on the configuration's mesh
(``launch/mesh.py``), and a compiled traversal session
(``core/engine.py``) that searches from one root per call.  This is the
only file of the benchmark that imports the program.
"""
from __future__ import annotations

import numpy as np


def build(config: dict):
    """(graph, mesh): the configuration's graph, sharded over its mesh."""
    from repro.graph.dist_build import BuildSpec, dist_build
    from repro.launch.mesh import make_local_mesh

    gen = config["generator"]
    spec = BuildSpec(scale=config["scale"], edge_factor=gen["edge_factor"],
                     seed=gen["seed"], a=gen["a"], b=gen["b"], c=gen["c"])
    pr, pc = config["mesh"]
    mesh = make_local_mesh(pr, pc)
    graph, _ = dist_build(spec, config["decomposition"], mesh, (pr, pc))
    return graph, mesh


def session(graph, mesh, config: dict):
    """The compiled single-root search session on the shipped graph."""
    from repro.configs.base import BFSConfig
    from repro.core.engine import plan_bfs

    cfg = BFSConfig(decomposition=config["decomposition"],
                    **config["bfs_config"])
    return plan_bfs(graph, cfg, mesh,
                    local_mode=config["local_mode"]).compile()


def search(engine, root: int):
    """Start one search; returns the device outputs."""
    return engine.search(root)


def ready(out) -> None:
    """Wait until a search's parents are on the device."""
    out[0].block_until_ready()


def levels(out) -> int:
    return int(out[1])


def parents(engine, out) -> np.ndarray:
    """A search's parent array, by global vertex id (-1 unreached)."""
    return engine.to_result(out).parents
