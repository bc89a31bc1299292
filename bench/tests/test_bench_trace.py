"""Trace reduction on a synthetic trace whose intervals are known."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import trace_reduce  # noqa: E402

US = 1_000_000      # picoseconds per microsecond


def _plane(pid, name, lines):
    """lines: {line name: [(event name, start_us, duration_us)]}."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = "\n".join(
        f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
        f'name: "{n}" }} }}' for i, n in enumerate(names))
    body = ""
    for lid, (line, events) in enumerate(lines.items()):
        evs = "\n".join(
            f"    events {{ metadata_id: {names.index(n) + 1} "
            f"offset_ps: {s * US} duration_ps: {d * US} }}"
            for n, s, d in events)
        body += (f'  lines {{\n    id: {lid + 1}\n    name: "{line}"\n'
                 f'    timestamp_ns: 0\n{evs}\n  }}\n')
    return f'planes {{\n  id: {pid}\n  name: "{name}"\n{body}{meta}\n}}\n'


def synthetic_trace():
    """Window 0-100 us; searches 10-50 and 60-90.  Device 0: a fusion
    10-30 with a nested op 12-14, an all-reduce 30-35, a fusion 60-80,
    and an op at 95-120 that the window clips to 95-100.  Device 1: one
    op 20-40 and a collective-permute 60-70."""
    host = _plane(1, "/host:CPU", {"python": [
        ("bench.window", 0, 100), ("bench.search", 10, 40),
        ("bench.search", 60, 30), ("bench.check", 100, 50)]})
    d0 = _plane(2, "/device:TPU:0", {"XLA Ops": [
        ("fusion.1", 10, 20), ("copy.2", 12, 2),
        ("%all-reduce.3 = s32[4]{0} all-reduce(s32[4]{0} %fusion.1)", 30, 5),
        ("fusion.1", 60, 20), ("while.4", 95, 25)]})
    d1 = _plane(3, "/device:TPU:1", {
        "XLA Ops": [("%fusion.7 = s32[8]{0} fusion(s32[8]{0} %p)", 20, 20),
                    ("%collective-permute-done.2 = u32[8]{0} "
                     "collective-permute-done(u32[8]{0} %collective-"
                     "permute-start.2)", 60, 10)],
        "Steps": [("step", 0, 100)]})
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(host + d0 + d1)


def test_interval_helpers():
    import numpy as np
    merged = trace_reduce.union(np.array([[5, 7], [0, 2], [1, 3], [7, 8]]))
    assert merged.tolist() == [[0, 3], [5, 8]]
    assert trace_reduce.overlap(merged, np.array([[2, 6]])) == 2.0
    selfs = trace_reduce.self_times([("a", 0, 10), ("b", 2, 4),
                                     ("c", 5, 6), ("a", 20, 21)])
    assert selfs == {"a": 10 - 2 - 1 + 1, "b": 2, "c": 1}


def test_summary_of_known_intervals():
    s = trace_reduce.summarize(synthetic_trace())
    assert s.window_s == pytest.approx(100e-6)
    assert s.searches == 2
    d0, d1 = s.devices
    assert d0.busy_s == pytest.approx(50e-6)          # 10-35, 60-80, 95-100
    assert d0.search_busy_s == pytest.approx(45e-6)   # all but 95-100
    assert d0.collective_s == pytest.approx(5e-6)
    assert d0.op_self_s["fusion.1"] == pytest.approx(38e-6)
    assert d1.busy_s == pytest.approx(30e-6)          # the Steps line is no op
    assert d1.collective_s == pytest.approx(10e-6)
    assert s.mean("busy_s") == pytest.approx(40e-6)
    assert s.device_ops()[0] == ("fusion.1", pytest.approx(19e-6))


def test_idle_gaps_are_named_by_host_span():
    s = trace_reduce.summarize(synthetic_trace())
    # device 0 idles 35-60 (middle 47.5, in a search), 80-95 (middle
    # 87.5, in a search) and 0-10 (between spans)
    assert [(n, round(g * 1e6)) for n, g in s.idle_gaps] == [
        ("bench.search", 25), ("bench.search", 15), ("host", 10)]


def test_collectives_are_found_by_opcode():
    coll = trace_reduce.COLLECTIVE
    assert coll.search("%all-gather-start.1 = (u32[2], u32[8]) "
                       "all-gather-start(u32[2] %a)")
    assert coll.search("%ar = f32[] all-reduce(f32[] %fusion.3)")
    assert not coll.search("%fusion.9 = u32[8] fusion(u32[8] "
                           "%collective-permute-done.1)")
    assert not coll.search("%copy-done = s32[8] copy-done(s32[8] %c)")


def test_a_trace_without_a_window_or_device_is_refused():
    from jax.profiler import ProfileData
    host = _plane(1, "/host:CPU", {"python": [("bench.window", 0, 10)]})
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace_reduce.summarize(ProfileData.from_text_proto(host))
    with pytest.raises(ValueError, match="one bench.window"):
        trace_reduce.summarize(ProfileData.from_text_proto(
            _plane(1, "/host:CPU", {"python": [("bench.search", 0, 10)]})))
