"""Scope attribution (bench/scopes.py) on a synthetic trace whose
intervals and scopes are known."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import scopes  # noqa: E402
from test_bench_trace import _plane  # noqa: E402

# one device: a search 10-50 us with a bottom-up level (a while
# envelope 10-48 holding a conditional 10-40 that holds the row lookup
# 12-32 and the update 32-38), the reduction 40-46 and an unscoped copy
# 46-48; an op at 55-60, outside every search, counts nowhere
OP_SCOPES = {"fusion.32": "bfs.bottomup/discover/edge_rows",
             "fusion.4": "bfs.bottomup/update",
             "all-reduce.1": "bfs.reduce",
             "fusion.9": "bfs.topdown/discover"}


def synthetic_trace():
    host = _plane(1, "/host:CPU", {"python": [
        ("bench.window", 0, 100), ("bench.search", 10, 40)]})
    dev = _plane(2, "/device:TPU:0", {"XLA Ops": [
        ("%while.2 = (s32[]) while((s32[]) %t), body=%b", 10, 38),
        ("%cond.1 = (s32[8]) conditional(s32[] %m, (s32[8]) %x)", 10, 30),
        ("%fusion.32 = s32[64]{0} fusion(s32[9]{0} %rp), kind=kLoop", 12, 20),
        ("%fusion.4 = s32[8]{0} fusion(s32[64]{0} %fusion.32)", 32, 6),
        ("%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %s)", 40, 6),
        ("%copy.8 = s32[8]{0} copy(s32[8]{0} %fusion.4)", 46, 2),
        ("%fusion.9 = s32[8]{0} fusion(s32[8]{0} %f)", 55, 5)]})
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(host + dev)


def test_instruction_names_from_either_trace():
    assert scopes.instr_name("%fusion.32 = s32[8]{0} fusion(s32[9] %a)") \
        == "fusion.32"
    assert scopes.instr_name("copy.8") == "copy.8"


def test_scope_summary_of_known_intervals():
    s = scopes.scope_summary(synthetic_trace(), OP_SCOPES)
    assert s["search_busy_s"] == pytest.approx(38e-6)
    want = {"bfs.bottomup/discover/edge_rows": 20e-6,
            "bfs.bottomup/update": 6e-6, "bfs.reduce": 6e-6,
            # the envelopes' own time (cond 10-12, 38-40) and the copy
            scopes.NONE: 4e-6 + 2e-6}
    assert s["scopes"] == pytest.approx(want)
    assert sum(s["scopes"].values()) == pytest.approx(s["search_busy_s"])
    label, scope, t = s["top_ops"][0]
    assert label.startswith("%fusion.32 = s32[64]")
    assert (scope, t) == ("bfs.bottomup/discover/edge_rows",
                          pytest.approx(20e-6))


def test_directions_read_the_used_rows():
    stats = np.zeros((8, 5), np.float32)
    stats[:4, 3] = 1
    stats[1:3, 2] = 1
    stats[:, 4] = np.nan
    assert scopes.directions(stats) == "TBBT"


def test_level_split_of_known_scopes():
    summary = {"search_busy_s": 10.0,
               "scopes": {"bfs.bottomup/discover/edge_rows": 6.0,
                          "bfs.bottomup/expand": 0.5,
                          "bfs.topdown/discover": 2.0,
                          "bfs.topdown/fold": 0.4, "bfs.reduce": 1.0,
                          scopes.NONE: 0.1}}
    got = scopes.level_split(summary, ["TTBBT", "TBB"])
    assert (got["bottomup_levels"], got["topdown_levels"]) == (4, 4)
    assert got["bottomup_level_ms"] == pytest.approx(6.5e3 / 4)
    assert got["topdown_level_ms"] == pytest.approx(2.4e3 / 4)
    assert got["discover_pct"] == pytest.approx(80.0)
    assert got["none_pct"] == pytest.approx(1.0)
    assert got["reduce_s"] == pytest.approx(1.0)
    none = scopes.level_split(summary, [])
    assert none["bottomup_level_ms"] is None
