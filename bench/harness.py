"""One run of one benchmark cell: build, session, warm-up, a timed window
of back-to-back searches, and the check of every searched root.

Everything that belongs to one configuration, traffic mix or per-layer
metric is data found by name: a cell of ``BENCHMARK.json`` names its
configuration (the file that entry gives), its traffic
(``bench/traffic/<name>.json``), the key pool of the two
(``bench/keys/<config>/<traffic>.json``) and, through the metric
entries, the per-layer readers (``bench/layer_metrics/<name>.py``, each a
``read(run, trace)`` that returns a number or None).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import pathlib
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass

import reference
import system
import trace_reduce
import traffic as traffic_gen

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
GIB = float(1 << 30)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    pool: dict            # the traffic's key pool on the config's graph
    end_to_end: list      # metric entries of BENCHMARK.json that apply
    per_layer: list


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = traffic_gen.load(w["traffic"])
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, pool=traffic_gen.load_pool(config, traffic),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def layer_reader(name: str):
    """The ``read(run, trace)`` of ``bench/layer_metrics/<name>.py``."""
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name}",
                                                  path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader {path} for metric {name!r}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; have {sorted(table)}")
    return table[device_kind]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, keeping every program, so only a cell's first run there
    compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check(g: reference.Graph, roots, parent_arrays) -> list[int]:
    """Bad vertices of each searched tree against the reference."""
    distinct = sorted({int(r) for r in roots})
    depth = dict(zip(distinct, reference.bfs_depths_many(g, distinct)))
    return [reference.bad_vertices(g, int(r), p, depth[int(r)])
            for r, p in zip(roots, parent_arrays)]


def run_once(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> tuple[dict, dict]:
    """Run the cell once: its result record, and the run's raw numbers.
    ``t_start`` is the host clock at process start, where set-up
    begins."""
    import jax
    from jax.profiler import TraceAnnotation

    keys = traffic_gen.draw_keys(cell.pool, seed)
    with TraceAnnotation("bench.build"):
        t0 = time.perf_counter()
        graph, mesh = system.build(cell.config)
        build_s = time.perf_counter() - t0
    with TraceAnnotation("bench.session"):
        t0 = time.perf_counter()
        engine = system.session(graph, mesh, cell.config)
        session_s = time.perf_counter() - t0
    with TraceAnnotation("bench.warmup"):
        # the same program as every search, in the fewest levels
        t0 = time.perf_counter()
        system.ready(system.search(engine, cell.pool["warmup_key"]))
        warmup_s = time.perf_counter() - t0

    trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-") \
        if trace else None
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir.name)
    outs, times = [], []
    with TraceAnnotation("bench.window"):
        w0 = time.perf_counter()
        setup_s = w0 - t_start
        while True:
            root = int(keys[len(outs) % len(keys)])
            with TraceAnnotation("bench.search"):
                t0 = time.perf_counter()
                out = system.search(engine, root)
                system.ready(out)
                t1 = time.perf_counter()
            outs.append((root, out))
            times.append(t1 - t0)
            if t1 - w0 >= seconds:
                break
    window_s = t1 - w0
    if trace_dir is not None:
        jax.profiler.stop_trace()

    devices = list(mesh.devices.flat)
    stats = [d.memory_stats() for d in devices]
    peak_bytes = max(s["peak_bytes_in_use"] for s in stats) \
        if all(stats) else None
    roots = [r for r, _ in outs]
    levels = [system.levels(o) for _, o in outs]
    parent_arrays = [system.parents(engine, o) for _, o in outs]
    graph_m, m_input = int(graph.m), int(graph.m_input)
    del outs, engine, graph
    gc.collect()

    with TraceAnnotation("bench.check"):
        t0 = time.perf_counter()
        g_ref = reference.generate(cell.config["generator"],
                                   cell.config["scale"])
        bad = check(g_ref, roots, parent_arrays)
        check_s = time.perf_counter() - t0
    summary = None
    if trace_dir is not None:
        summary = trace_reduce.read_dir(trace_dir.name)
        trace_dir.cleanup()

    run = {"seed": seed, "build_s": build_s, "session_s": session_s,
           "warmup_s": warmup_s, "setup_s": setup_s, "check_s": check_s, "window_s": window_s, "search_s": times,
           "roots": roots, "levels": levels, "graph_m": graph_m,
           "m_input": m_input, "chips": len(devices),
           "peaks": peaks(devices[0].device_kind),
           "memory_peak_bytes": peak_bytes, "bad_vertices": bad}
    return record(cell, run, summary, devices[0]), run


def end_to_end_values(run: dict) -> dict:
    values = {
        "teps": run["m_input"] * len(run["search_s"]) / run["window_s"],
        "search_p50_ms": statistics.median(run["search_s"]) * 1e3,
        "setup_s": run["setup_s"],
    }
    if run["memory_peak_bytes"] is not None:
        values["peak_hbm_gib"] = run["memory_peak_bytes"] / GIB
    return values


def record(cell: Cell, run: dict, summary, device) -> dict:
    """The result record: the keys the contract names, with the numbers
    compared for ``correct`` last."""
    bad = run["bad_vertices"]
    checks = {"bad_vertices": {"value": int(sum(bad)), "limit": 0},
              "bad_searches": {"value": sum(b > 0 for b in bad),
                               "limit": 0}}
    correct = bool(bad) and all(c["value"] <= c["limit"]
                                for c in checks.values())
    if summary is None:
        values = end_to_end_values(run)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    else:
        metrics = {}
        for m in cell.per_layer:
            v = layer_reader(m["name"])(run, summary)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": run["chips"],
           "memory_peak_bytes": run["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": len(bad),
           "failed": sum(b > 0 for b in bad), "metrics": metrics,
           "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.mean("busy_s")
        dev["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops()],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    out["checks"] = checks
    return out


def report(rec: dict, run: dict) -> None:
    """Print the run's raw numbers and then the numbers compared as the
    last lines of standard error, and the record as the last line of
    standard output."""
    detail = {k: v for k, v in run.items()
              if k not in ("peaks", "roots", "bad_vertices")}
    print("run " + json.dumps(detail), file=sys.stderr)
    for name, c in rec["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(rec), flush=True)
