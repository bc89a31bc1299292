"""edge_level_ns: device nanoseconds per stored edge per level.

Device busy time inside the ``bench.search`` spans, averaged over the
cell's chips, over (stored edges x levels summed over the window's
searches).  A dense level scans every stored edge, so this is the cost
of one edge's visit; on several chips the edges are shared among them,
and the number falls with the chips as the scan is split.
"""


def read(run, trace):
    edge_levels = run["graph_m"] * sum(run["levels"])
    if trace is None or edge_levels == 0:
        return None
    busy = trace.mean("search_busy_s")
    return busy * 1e9 / edge_levels if busy > 0 else None
