"""search_roofline: the share of the least time in which a search could
run, if it read each stored edge once, of its measured device time.

Least time: the stored edge list as two int32 per edge
(8 B x stored edges), read at the chips' summed HBM bandwidth
(``bench/peaks.json``).  Measured: device busy time inside the
``bench.search`` spans, averaged over the chips, per search.  Every
search reads the graph at least once, so the share cannot pass 100%.
"""

EDGE_BYTES = 8


def read(run, trace):
    if trace is None or not run["search_s"]:
        return None
    per_search = trace.mean("search_busy_s") / len(run["search_s"])
    if per_search <= 0:
        return None
    least = EDGE_BYTES * run["graph_m"] / (
        run["chips"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / per_search
