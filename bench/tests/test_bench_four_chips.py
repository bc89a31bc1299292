"""The four-chip cell on the CPU: a whole harness run of its
configuration at a small scale on four forced host devices (in a
subprocess, since the device count is fixed when JAX starts), sound and
with one parent broken, and the arithmetic of its ``collective_pct``."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

CELL = next(w["name"] for w in harness.load_benchmark()["workloads"]
            if w["chips"] == 4)

# One run of the cell at scale 10 with a pool of 128 candidates, then a
# second on the same graph and session whose timed search names itself
# the parent of one vertex other than the root.
_MAIN = """
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {bench!r})
import jax.numpy as jnp
import harness, reference, system, traffic

harness.peaks = lambda kind: {{"hbm_bytes_per_s": 819e9}}
cell = harness.load_cell({cell!r})
cell.config = dict(cell.config, scale=10)
g = reference.generate(cell.config["generator"], 10)
cell.pool = traffic.make_pool(cell.config, dict(cell.traffic, pool=128), g)
build, session, search = system.build, system.session, system.search
kept = {{}}
system.build = lambda config: kept.setdefault("b", build(config))
system.session = lambda graph, mesh, config: kept.setdefault(
    "s", session(graph, mesh, config))
out = {{}}
rec, run = harness.run_once(cell, 2**31 + 7, 0.3, False, time.perf_counter())
out["sound"] = dict(rec["checks"], correct=rec["correct"],
                    chips=run["chips"], relabeled=kept["b"][0].relabel
                    is not None)

def broken(engine, root):
    pi, *rest = search(engine, root)
    flat = pi.reshape(-1)
    idx = jnp.arange(flat.shape[0])
    first = jnp.argmax((flat >= 0) & (idx != root))
    return (jnp.where(idx == first, first, flat).reshape(pi.shape), *rest)

system.search = broken
rec, run = harness.run_once(cell, 2**31 + 7, 0.3, False, time.perf_counter())
out["broken"] = dict(rec["checks"], correct=rec["correct"])
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_chip_runs():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(BENCH.parent / "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    code = _MAIN.format(bench=str(BENCH), cell=CELL)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.splitlines()[-1])


def test_sound_four_chip_run_is_correct(four_chip_runs):
    sound = four_chip_runs["sound"]
    assert sound["correct"] is True, sound
    assert sound["bad_vertices"]["value"] == 0
    assert sound["chips"] == 4 and sound["relabeled"]


def test_one_broken_parent_is_not_correct(four_chip_runs):
    broken = four_chip_runs["broken"]
    assert broken["correct"] is False, broken
    assert broken["bad_vertices"]["value"] > 0
    assert broken["bad_searches"]["value"] > 0


def _summary(busy, collective):
    devices = [trace_reduce.DeviceSummary(
        name=f"/device:TPU:{i}", busy_s=b, search_busy_s=b,
        collective_s=c, op_self_s={}, busy=None)
        for i, (b, c) in enumerate(zip(busy, collective))]
    return trace_reduce.TraceSummary(window_s=1.0, searches=1,
                                     devices=devices, idle_gaps=[])


def test_collective_pct_arithmetic():
    read = harness.layer_reader("collective_pct")
    # mean collective 0.15 s over mean search busy 0.75 s
    assert read({}, _summary([0.5, 1.0], [0.1, 0.2])) == pytest.approx(20.0)
    assert read({}, None) is None
    assert read({}, _summary([0.0, 0.0], [0.0, 0.0])) is None


def test_collective_pct_of_a_recorded_trace():
    """On the synthetic trace of test_bench_trace.py: an all-reduce of
    5 us and a collective-permute-done of 10 us, over 45 and 30 us of
    search busy time."""
    from test_bench_trace import synthetic_trace
    s = trace_reduce.summarize(synthetic_trace())
    got = harness.layer_reader("collective_pct")({}, s)
    assert got == pytest.approx(100 * 7.5 / 37.5)
