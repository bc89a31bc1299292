"""Data-pipeline determinism + comm-model closed forms."""
import jax
import numpy as np

from repro.configs.base import get_config, reduced
from repro.core import comm_model
from repro.data.pipeline import lm_batch, recsys_batch, step_stream


def test_lm_stream_step_indexed_determinism():
    cfg = reduced(get_config("smollm-135m"), vocab=512)
    a = lm_batch(cfg, 4, 32, step=17, seed=3)
    b = lm_batch(cfg, 4, 32, step=17, seed=3)
    c = lm_batch(cfg, 4, 32, step=18, seed=3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    # labels are next-token shifted views of the same stream
    assert a["tokens"].shape == a["labels"].shape == (4, 32)
    assert (a["tokens"] < cfg.vocab).all()


def test_recsys_stream_in_vocab():
    cfg = get_config("autoint")
    b = recsys_batch(cfg, 64, step=0)
    assert b["idx"].shape == (64, cfg.n_sparse)
    for f, v in enumerate(cfg.vocab_sizes):
        assert (b["idx"][:, f] < v).all()
    assert set(np.unique(b["labels"])) <= {0.0, 1.0}


def test_step_stream_resume():
    mk = lambda s: {"x": np.asarray([s])}
    it = step_stream(mk, start_step=5)
    assert next(it)["x"][0] == 5 and next(it)["x"][0] == 6


def test_comm_model_eq2_structure():
    # Eq 2 (paper §6): the gain grows with the degree k, shrinks with
    # more bottom-up steps s_b, and saturates at 64/(2 s_b) for large pc
    # (it is NOT monotone in pc — it peaks, then the rotation term wins)
    assert comm_model.ratio_eq2(64, 128, 4) > comm_model.ratio_eq2(16, 128, 4)
    assert comm_model.ratio_eq2(16, 128, 3) > comm_model.ratio_eq2(16, 128, 6)
    import numpy as np
    limit = 64 / (2 * 4)
    assert abs(comm_model.ratio_eq2(16, 10**6, 4) - limit) < 0.1
    assert comm_model.ratio_eq2(16, 128, 4) > 1   # bottom-up always wins
    # typical-value check from the paper: k=16, pc=128 -> s_b ~ 47.6 steps
    # to break even
    s_b = 47.6
    w_ratio = comm_model.ratio_eq2(16, 128, s_b)
    assert abs(w_ratio - 1.0) < 0.05


def test_bottomup_words_matches_table1_structure():
    n, pr, pc, s_b = 1 << 20, 8, 8, 3.0
    w = comm_model.bottomup_words(n, pr, pc, s_b)
    expect = n * (s_b * (pr + pc + 1) / 64 + 2)
    assert w == expect


def test_fold_bitmap_words_closed_form():
    """The bitmap fold is exactly 2 bitmap all_to_all rounds + 2 id
    all_to_alls (values + offsets): 2*nr/64 + 2*pc*cap_w words per
    device.  (The old counter charged a third bitmap round and the old
    docstring dropped one id exchange.)"""
    nr, pc, cap_w = 4096, 16, 64
    w = comm_model.fold_bitmap_level_words(nr, pc, cap_w)
    assert w == 2 * nr / 64 + 2 * pc * cap_w
    # cheaper than the dense alltoall fold once cap_w << chunk
    assert w < (pc - 1) * (nr // pc) * pc  # vs dense per-device * pc...
    assert w < nr                          # vs the dense (pc-1)*chunk ~ nr


def test_fold_bitmap_counter_matches_closed_form():
    """The live wire_fold counter must reproduce the closed form: one
    charge of p * fold_bitmap_level_words per top-down level."""
    import numpy as np
    from repro.configs.base import BFSConfig
    from repro.core.bfs import run_bfs
    from repro.graph.formats import build_blocked
    from repro.graph.rmat import rmat_graph
    from repro.launch.mesh import make_local_mesh

    e = rmat_graph(8, edge_factor=8, seed=4)
    g = build_blocked(e, 1, 1, align=32, cap_pad=32)
    part = g.part
    res = run_bfs(g, int(np.flatnonzero(e.out_degrees())[0]),
                  BFSConfig(fold_mode="bitmap"), make_local_mesh(1, 1))
    modes = res.level_stats[: res.n_levels, 2]
    used = res.level_stats[: res.n_levels, 3]
    n_td = int(((modes == 0) & (used > 0)).sum())
    assert n_td > 0
    cap_w = max(part.chunk // 16, 32)
    want = n_td * part.p * comm_model.fold_bitmap_level_words(
        part.pc * part.chunk, part.pc, cap_w)
    assert abs(res.counters["wire_fold"] - want) <= 1e-5 * want, (
        res.counters["wire_fold"], want)


def test_uninstrumented_runs_carry_no_wire_counters():
    """The satellite bugfix pin: an instrument=False run used to return
    zero-valued counters — a "1ds" dense-fallback level's wire_expand
    came back as a measured-looking 0.0, silently vanishing from
    aggregates that mix fast and instrumented runs (sum(fast, inst)
    == sum(inst), no error).  The fast path must now carry NO counters
    at all, so mixing modes is a KeyError instead of a wrong number,
    and the exchange helper itself reports wire=None uninstrumented."""
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import BFSConfig
    from repro.core.bfs import run_bfs
    from repro.core.steps_1d_sparse import sparse_exchange_1d
    from repro.graph.formats import build_blocked_1d
    from repro.graph.rmat import rmat_graph
    from repro.launch.mesh import make_local_mesh_1d

    e = rmat_graph(8, edge_factor=8, seed=4)
    g = build_blocked_1d(e, 1, align=32, cap_pad=32)
    root = int(np.flatnonzero(e.out_degrees())[0])
    mesh = make_local_mesh_1d(1)
    fast = run_bfs(g, root, BFSConfig(decomposition="1ds",
                                      instrument=False), mesh)
    inst = run_bfs(g, root, BFSConfig(decomposition="1ds"), mesh)
    assert fast.counters == {}
    assert np.array_equal(fast.parents, inst.parents)
    # the helper itself: wire is None (absent), never a fake 0.0 float
    part = g.part
    front = np.zeros((1, part.chunk), bool)
    front[0, root] = True

    def wire_of(instrument):
        captured = {}

        def body(f):
            f_words, wire, over = sparse_exchange_1d(
                f[0], "data", 32, part, instrument=instrument)
            captured["wire"] = wire
            return f_words[None]

        jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                      out_specs=P("data"), check_vma=False)(front)
        return captured["wire"]

    assert wire_of(False) is None
    assert wire_of(True) is not None
