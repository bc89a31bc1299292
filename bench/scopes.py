"""Where a search's device time goes, by the level program's named
scopes (``core/scopes.py``): reductions of a profiler trace and of the
program's ``level_stats``.

  scope_summary  device self time per scope inside the bench.search
                 spans, mean over chips; ops outside every scope under
                 "(none)"
  directions     a search's direction sequence, from level_stats
  level_split    ms per bottom-up and per top-down level, discover_pct
                 and none_pct (the share of search busy time no scope
                 names: the completeness of the attribution)

An op event is named by scope through ``BFSEngine.op_scopes()``, since
the TPU trace names an op by its HLO text without its ``op_name``.  The
harness does not call these yet: it keeps neither the op map nor the
searches' ``level_stats`` (PERF.md, Open questions).
"""
from __future__ import annotations

import numpy as np

import trace_reduce

NONE = "(none)"


def instr_name(event_name: str) -> str:
    """The HLO instruction an op event names: the TPU trace names it by
    its HLO text ("%fusion.32 = s32[...] fusion(...)"), the CPU trace by
    the bare name ("fusion.32")."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def scope_summary(profile, op_scopes: dict) -> dict:
    """Device self time per scope inside the ``bench.search`` spans of a
    ``jax.profiler.ProfileData``, mean over devices, beside the search
    busy time and the ops with the most self time (each as the start of
    its HLO text, its scope and its seconds).  ``op_scopes`` maps an
    instruction name to its scope (``BFSEngine.op_scopes``)."""
    planes = list(profile.planes)
    spans = trace_reduce._host_spans(planes)
    search = trace_reduce.union(np.asarray(
        [(s, e) for n, s, e in spans if n == trace_reduce.SEARCH_SPAN],
        dtype=np.float64).reshape(-1, 2))
    busy, scopes, ops = [], {}, {}
    devices = [p for p in planes if trace_reduce.DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    for plane in devices:
        events = []
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                for s, e in search:
                    lo, hi = max(ev.start_ns, s), min(ev.end_ns, e)
                    if hi > lo:
                        events.append((ev.name, lo, hi))
        merged = trace_reduce.union(np.asarray(
            [(s, e) for _, s, e in events], dtype=np.float64).reshape(-1, 2))
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9)
        for name, t in trace_reduce.self_times(events).items():
            scope = op_scopes.get(instr_name(name), NONE)
            t = t * 1e-9 / len(devices)
            scopes[scope] = scopes.get(scope, 0.0) + t
            key = (name[:trace_reduce.LABEL], scope)
            ops[key] = ops.get(key, 0.0) + t
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:trace_reduce.TOP]
    return {"search_busy_s": float(np.mean(busy)),
            "scopes": dict(sorted(scopes.items(), key=lambda kv: -kv[1])),
            "top_ops": [[n, s, t] for (n, s), t in top]}


def directions(level_stats) -> str:
    """A search's direction sequence from its level_stats: one letter a
    used level, "T" top-down or "B" bottom-up (column 2)."""
    stats = np.asarray(level_stats)
    return "".join("TB"[int(m)] for m in stats[stats[:, 3] > 0, 2])


def level_split(summary: dict, seqs) -> dict:
    """Per-level times and shares from a ``scope_summary`` and the
    window's direction sequences."""
    sc, busy = summary["scopes"], summary["search_busy_s"]

    def under(top):
        return sum(v for k, v in sc.items() if k.split("/")[0] == top)

    n_bu = sum(s.count("B") for s in seqs)
    n_td = sum(s.count("T") for s in seqs)
    bu, td, red = under("bfs.bottomup"), under("bfs.topdown"), \
        under("bfs.reduce")
    none = sc.get(NONE, 0.0)
    disc = sum(v for k, v in sc.items() if k.split("/")[1:2] == ["discover"])
    return {
        "bottomup_levels": n_bu, "topdown_levels": n_td,
        "bottomup_level_ms": 1e3 * bu / n_bu if n_bu else None,
        "topdown_level_ms": 1e3 * td / n_td if n_td else None,
        "reduce_s": red, "none_s": none,
        "discover_pct": 100.0 * disc / busy if busy else None,
        "none_pct": 100.0 * none / busy if busy else None,
    }
