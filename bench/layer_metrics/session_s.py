"""session_s: host-clock seconds of ``plan_bfs(...).compile()``
(``core/engine.py``): planning, shipping the graph and compiling (or
loading from the persistent cache) the single-root search."""


def read(run, trace):
    return run["session_s"]
