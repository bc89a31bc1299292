"""idle_pct: the share of the traced window in which no operation ran
on the device, averaged over the cell's chips."""


def read(run, trace):
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.mean("busy_s") / trace.window_s)
