"""collective_pct: the share of the chips' device time in search spent in
the exchange operations (all-gather, all-reduce, all-to-all,
collective-permute, reduce-scatter, with their -start/-done halves).

Self time of the operations that ``bench/trace_reduce.py``'s
``COLLECTIVE`` names, over device busy time inside the ``bench.search``
spans, each averaged over the cell's chips.  A chip's collective time
includes its wait for the slowest chip to reach the exchange, so load
imbalance between the blocks reads here as exchange time.
"""


def read(run, trace):
    if trace is None:
        return None
    busy = trace.mean("search_busy_s")
    if busy <= 0:
        return None
    return 100.0 * trace.mean("collective_s") / busy
