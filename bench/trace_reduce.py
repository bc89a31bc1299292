"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes,
read with ``jax.profiler.ProfileData``.  Each device is a plane named
``/device:TPU:<i>`` whose ``XLA Ops`` line holds one event per operation
run; the host plane holds the harness's ``bench.*`` spans
(``jax.profiler.TraceAnnotation``) on the same clock.  Everything is
clipped to the ``bench.window`` span, the measured window.

Per device: busy time is the union of the operation intervals; time in
search is the part of it inside ``bench.search`` spans; collective time
is the self time of the exchange operations.  Idle gaps are the holes
between busy intervals, each named by the innermost ``bench.*`` span the
host was in at the gap's middle.
"""
from __future__ import annotations

import pathlib
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# an op event is named by its HLO text, "%all-reduce.3 = f32[] all-reduce(
# ...": the opcode stands before its operand list
COLLECTIVE = re.compile(r"(^|\s)(all-gather|all-reduce|collective-permute|"
                        r"all-to-all|reduce-scatter)(-start|-done)?\(")
LABEL = 160        # characters of an op's HLO text kept in the breakdown
WINDOW_SPAN = "bench.window"
SEARCH_SPAN = "bench.search"
TOP = 10


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge (start, end) rows into disjoint, sorted intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def overlap(merged: np.ndarray, spans: np.ndarray) -> float:
    """Length of the intersection of two sets of disjoint intervals."""
    total = 0.0
    for s, e in union(spans):
        lo = np.clip(merged[:, 0], s, e)
        hi = np.clip(merged[:, 1], s, e)
        total += float(np.sum(hi - lo))
    return total


def self_times(events: list[tuple[str, float, float]]) -> dict[str, float]:
    """Each name's total time not covered by a nested event of the same
    line (events nest or are disjoint within a line)."""
    out: dict[str, float] = {}
    stack: list[list] = []     # [name, end, child time, duration]
    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out[top[0]] = out.get(top[0], 0.0) + top[3] - top[2]
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, e - s])
    for top in stack:
        out[top[0]] = out.get(top[0], 0.0) + top[3] - top[2]
    return out


@dataclass
class DeviceSummary:
    name: str
    busy_s: float
    search_busy_s: float
    collective_s: float
    op_self_s: dict[str, float]
    busy: np.ndarray = field(repr=False)     # merged intervals, ns


@dataclass
class TraceSummary:
    window_s: float
    searches: int
    devices: list[DeviceSummary]
    idle_gaps: list[tuple[str, float]]

    def mean(self, attr: str) -> float:
        return float(np.mean([getattr(d, attr) for d in self.devices]))

    def device_ops(self) -> list[tuple[str, float]]:
        """The operations with the most self time, mean over devices,
        each labelled by the start of its HLO text."""
        total: dict[str, float] = {}
        for d in self.devices:
            for k, v in d.op_self_s.items():
                total[k] = total.get(k, 0.0) + v / len(self.devices)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:TOP]
        return [(k[:LABEL], v) for k, v in top]


def _host_spans(planes) -> list[tuple[str, float, float]]:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    return spans


def summarize(profile) -> TraceSummary:
    """The summary of a ``jax.profiler.ProfileData``."""
    planes = list(profile.planes)
    spans = _host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    search = np.asarray([(s, e) for n, s, e in spans if n == SEARCH_SPAN],
                        dtype=np.float64).reshape(-1, 2)
    devices = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    events.append((ev.name, s, e))
        busy = union(np.asarray([(s, e) for _, s, e in events],
                                dtype=np.float64).reshape(-1, 2))
        selfs = self_times(events)
        devices.append(DeviceSummary(
            name=plane.name,
            busy_s=float(np.sum(busy[:, 1] - busy[:, 0])) * 1e-9,
            search_busy_s=overlap(busy, search) * 1e-9,
            collective_s=sum(v for k, v in selfs.items()
                             if COLLECTIVE.search(k)) * 1e-9,
            op_self_s={k: v * 1e-9 for k, v in selfs.items()},
            busy=busy))
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    return TraceSummary(window_s=(w1 - w0) * 1e-9, searches=len(search),
                        devices=devices,
                        idle_gaps=_idle_gaps(devices[0].busy, spans, w0, w1))


def _idle_gaps(busy, spans, w0, w1) -> list[tuple[str, float]]:
    edges = np.concatenate([[w0], busy.reshape(-1), [w1]]).reshape(-1, 2)
    gaps = [(s, e) for s, e in edges if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:TOP]:
        mid = 0.5 * (s + e)
        inside = [(sp_e - sp_s, n) for n, sp_s, sp_e in spans
                  if n != WINDOW_SPAN and sp_s <= mid <= sp_e]
        out.append((min(inside)[1] if inside else "host", (e - s) * 1e-9))
    return out


def read_dir(log_dir) -> TraceSummary:
    """The summary of the one trace under a ``start_trace`` directory."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, found "
                         f"{len(files)}")
    return summarize(ProfileData.from_file(str(files[0])))
