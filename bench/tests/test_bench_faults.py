"""A whole run on the CPU at a small size, with the harness's look for a
chip skipped: sound, it is correct; with the timed path's answer broken
where the search produces it, ``correct`` comes out false, once for each
fault the cells can have.  And the control, the reference with one
guarantee broken, fails the same check."""
import pathlib
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import control  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import system  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

SCALE = 9


def _cell():
    """The one-chip cell at ``SCALE``, with a key pool of that graph."""
    bench = harness.load_benchmark()
    name = next(w["name"] for w in bench["workloads"] if w["chips"] == 1)
    cell = harness.load_cell(name, bench)
    cell.config = dict(cell.config, scale=SCALE)
    g = reference.generate(cell.config["generator"], SCALE)
    cell.pool = traffic.make_pool(cell.config,
                                  dict(cell.traffic, pool=128), g)
    return cell


def _run(monkeypatch, fault=None, trace=False):
    """One run of the one-chip cell at ``SCALE``.  ``fault(pi, root)``
    rewrites the flat parent array (global vertex order on a 1x1 mesh)
    that each timed search returns."""
    if fault is not None:
        search = system.search

        def broken(engine, root):
            pi, *rest = search(engine, root)
            flat = fault(pi.reshape(-1), root).astype(pi.dtype)
            return (flat.reshape(pi.shape), *rest)
        monkeypatch.setattr(system, "search", broken)
    monkeypatch.setattr(harness, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    if trace:
        from test_bench_trace import synthetic_trace
        monkeypatch.setattr(trace_reduce, "read_dir",
                            lambda d: trace_reduce.summarize(
                                synthetic_trace()))
    rec, run = harness.run_once(_cell(), 2**31 + 99, 0.3, trace,
                                time.perf_counter())
    return rec


def test_sound_run_is_correct(monkeypatch):
    rec = _run(monkeypatch)
    assert rec["correct"] is True, rec["checks"]
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert set(rec["metrics"]) == {"teps", "search_p50_ms", "setup_s"}


def test_traced_run_reports_layer_metrics(monkeypatch):
    rec = _run(monkeypatch, trace=True)
    assert rec["correct"] is True
    assert {"build_s", "session_s", "idle_pct"} <= set(rec["metrics"])
    assert "breakdown" in rec and list(rec)[-1] == "checks"


def _unchanged(pi, root):
    """The search's state as it starts: only the root has a parent."""
    n = pi.shape[0]
    return jnp.where(jnp.arange(n) == root, root, -1)


def _half_left_out(pi, root):
    """Half of the vertices never searched."""
    n = pi.shape[0]
    return jnp.where(jnp.arange(n) < n // 2, pi, -1)


def _no_exchange(pi, root):
    """What one chip of a 2x2 mesh finds without the exchange: only the
    vertices of the root's own quarter of the ids."""
    n = pi.shape[0]
    q = n // 4
    return jnp.where(jnp.arange(n) // q == root // q, pi, -1)


def _altered(pi, root):
    """One answer altered where it is produced: the first vertex other
    than the root that has a parent names itself."""
    n = pi.shape[0]
    idx = jnp.arange(n)
    first = jnp.argmax((pi >= 0) & (idx != root))
    return jnp.where(idx == first, first, pi)


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out, _no_exchange,
                                   _altered],
                         ids=["state-unchanged", "half-left-out",
                              "exchange-left-out", "answer-altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    rec = _run(monkeypatch, fault)
    assert rec["correct"] is False
    assert rec["failed"] > 0
    assert rec["checks"]["bad_vertices"]["value"] > 0


def test_control_fails_the_check():
    g = reference.generate({"kind": "kronecker", "a": 0.57, "b": 0.19,
                            "c": 0.19, "edge_factor": 16, "seed": 1}, 12)
    roots = [0, 17, 400, 1234]
    bad = harness.check(g, roots, [control.control_parents(g, r)
                                   for r in roots])
    assert all(b > 0 for b in bad)
    depth = reference.bfs_depths(g, 17)
    par = control.control_parents(g, 17)
    # the reached set is right: only the parents' levels are wrong
    np.testing.assert_array_equal(par >= 0, depth >= 0)
