"""The harness: what it finds by name, the traffic it draws, the
arithmetic of its metrics, the shape of its result line, and its refusal
to run anywhere but on a TPU."""
import json
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
import trace_reduce  # noqa: E402

BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_keeps_to_its_schema():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, want in keys.items():
        names = [e["name"] for e in BENCHMARK[group]]
        assert len(set(names)) == len(names)
        for e in BENCHMARK[group]:
            assert set(e) - {"workloads"} == want, e["name"]
            assert NAME.match(e["name"]), e["name"]
    for c in BENCHMARK["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) <= 1
    assert 1 <= BENCHMARK["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_config_traffic_and_metrics_by_name(cell):
    c = harness.load_cell(cell)
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    assert c.chips == entry["chips"]
    assert c.config["name"] == entry["config"]
    pr, pc = c.config["mesh"]
    assert pr * pc == c.chips
    assert c.traffic == traffic.load(entry["traffic"])
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "teps"}
    for m in c.per_layer:
        assert callable(harness.layer_reader(m["name"]))


def test_config_files_state_what_was_cut():
    for c in BENCHMARK["configs"]:
        cfg = json.loads((BENCH.parent / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        harness.layer_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        traffic.load("no-such-traffic")


def test_peaks_are_keyed_by_device_kind():
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and "source" in v5e
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks("TPU v9 imaginary")


GEN = {"kind": "kronecker", "a": 0.57, "b": 0.19, "c": 0.19,
       "edge_factor": 16, "seed": 1}


@pytest.fixture(scope="module")
def small_graph():
    return reference.generate(GEN, 11)


@pytest.fixture(scope="module")
def small_pool(small_graph):
    tr = dict(traffic.load("g500-keys"), pool=256)
    return tr, traffic.make_pool({"generator": GEN, "scale": 11}, tr,
                                 small_graph)


def test_pool_holds_uniform_candidates_and_their_eccentricities(
        small_graph, small_pool):
    tr, pool = small_pool
    cand = sorted(v for vs in pool["candidates"].values() for v in vs)
    assert tr["keys"] <= len(cand) <= tr["pool"]
    assert len(cand) == len(set(cand))
    assert (small_graph.degree[cand] >= tr["min_degree"]).all()
    degree = small_graph.degree.astype(np.float64)
    for cls, vs in pool["candidates"].items():
        depths = reference.bfs_depths_many(small_graph, vs)
        assert (depths.max(axis=1) == len(cls) - 1).all()
        for depth in depths:
            got, margin = traffic.work_class(depth, degree, tr["alpha"],
                                             tr["beta"])
            assert got == cls and margin >= tr["margin"]
    assert len(pool["sizes"]) == tr["keys"]
    assert small_graph.degree[pool["warmup_key"]] == small_graph.degree.min()


def test_work_class_follows_the_direction_switch():
    # a path 0-1-2 and a star on 3 with 40 leaves: from 0 the frontier's
    # edges never outweigh the rest by alpha; from the star's centre
    # they do at once, and its 40 leaves keep the search bottom-up
    n = 44
    src = np.array([0, 1] + [3] * 40)
    dst = np.array([1, 2] + list(range(4, 44)))
    g = reference.undirected_graph(n, src, dst)
    degree = g.degree.astype(np.float64)
    path, _ = traffic.work_class(reference.bfs_depths(g, 0), degree,
                                 alpha=14.0, beta=24.0)
    assert path == "TTT"
    star, _ = traffic.work_class(reference.bfs_depths(g, 3), degree,
                                 alpha=14.0, beta=24.0)
    assert star == "BB"


def test_keys_are_fixed_by_the_seed_and_keep_the_pool_sizes(small_pool):
    tr, pool = small_pool
    big = 2**31 + 12345
    a = traffic.draw_keys(pool, big)
    b = traffic.draw_keys(pool, big)
    c = traffic.draw_keys(pool, 7)
    np.testing.assert_array_equal(a, b)
    assert len(a) == tr["keys"] == len(set(a.tolist()))
    assert not np.array_equal(a, c)
    cls = {v: c for c, vs in pool["candidates"].items() for v in vs}
    assert [cls[v] for v in a.tolist()] == pool["sizes"]
    assert [cls[v] for v in c.tolist()] == pool["sizes"]


@pytest.mark.parametrize("cell", CELLS)
def test_kept_pool_belongs_to_its_cell(cell):
    c = harness.load_cell(cell)
    assert len(c.pool["sizes"]) == c.traffic["keys"]
    other = dict(c.config, scale=c.config["scale"] - 1)
    with pytest.raises(ValueError, match="another graph"):
        traffic.load_pool(other, c.traffic)


def _run(**kw):
    run = {"seed": 1, "build_s": 2.5, "session_s": 1.5, "setup_s": 12.0,
           "window_s": 20.0, "search_s": [4.0, 3.0, 5.0, 4.0, 4.5],
           "levels": [7, 6, 7, 7, 8], "graph_m": 1000, "m_input": 512,
           "chips": 1, "peaks": {"hbm_bytes_per_s": 800.0},
           "memory_peak_bytes": 3 << 29, "bad_vertices": [0] * 5}
    run.update(kw)
    return run


class _Trace:
    """What the readers use of a trace summary: mean over devices."""
    def __init__(self, busy, search_busy, window):
        self.values = {"busy_s": busy, "search_busy_s": search_busy}
        self.window_s = window

    def mean(self, attr):
        return self.values[attr]


def test_end_to_end_arithmetic():
    v = harness.end_to_end_values(_run())
    assert v["teps"] == pytest.approx(512 * 5 / 20.0)
    assert v["search_p50_ms"] == pytest.approx(4000.0)
    assert v["peak_hbm_gib"] == pytest.approx(1.5)
    assert v["setup_s"] == 12.0


def test_layer_metric_arithmetic():
    tr = _Trace(busy=19.0, search_busy=17.5, window=20.0)
    read = harness.layer_reader
    # 17.5 s over 1000 edges x 35 levels
    assert read("edge_level_ns")(_run(), tr) == pytest.approx(5e5)
    # least time 8 B x 1000 / 800 B/s = 10 s; 3.5 s of device per search
    assert read("search_roofline")(_run(chips=1), tr) == pytest.approx(
        100 * 10 / 3.5)
    assert read("search_roofline")(_run(chips=4), tr) == pytest.approx(
        100 * 2.5 / 3.5)
    assert read("idle_pct")(_run(), tr) == pytest.approx(5.0)
    assert read("build_s")(_run(), tr) == 2.5
    assert read("session_s")(_run(), tr) == 1.5
    nothing = _Trace(busy=0.0, search_busy=0.0, window=20.0)
    for name in ("edge_level_ns", "search_roofline"):
        assert read(name)(_run(), nothing) is None
        assert read(name)(_run(), None) is None


class _Device:
    platform, device_kind = "tpu", "TPU v5 lite"


@pytest.mark.parametrize("traced", [False, True])
def test_result_line_holds_the_contract_keys(traced):
    cell = harness.load_cell(CELLS[0])
    summary = None
    if traced:
        from test_bench_trace import synthetic_trace
        summary = trace_reduce.summarize(synthetic_trace())
    rec = harness.record(cell, _run(), summary, _Device())
    want = ["correct", "attempted", "failed", "metrics", "device"]
    want += ["breakdown", "checks"] if traced else ["checks"]
    assert list(rec) == want
    assert rec["correct"] is True and rec["attempted"] == 5
    assert set(rec["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    if traced:
        assert rec["device"]["busy_s"] > 0 and rec["device"]["window_s"] > 0
        assert set(rec["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(rec["breakdown"][k]) <= 10 for k in rec["breakdown"])
        assert set(rec["metrics"]) <= {m["name"] for m in cell.per_layer}
    else:
        assert set(rec["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in rec["metrics"].values():
        assert set(m) == {"value", "unit"}
    json.dumps(rec)


def test_a_bad_tree_makes_the_run_incorrect():
    rec = harness.record(harness.load_cell(CELLS[0]),
                         _run(bad_vertices=[0, 0, 3, 0, 0]), None, _Device())
    assert rec["correct"] is False and rec["failed"] == 1
    assert rec["checks"]["bad_vertices"] == {"value": 3, "limit": 0}


def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=BENCH.parent, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr
