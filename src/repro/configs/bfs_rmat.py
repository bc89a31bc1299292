"""The paper's own workload: direction-optimizing BFS on Graph500 R-MAT."""
from repro.configs.base import BFSConfig, register
import dataclasses

CONFIG = register(BFSConfig(arch="bfs-rmat", storage="dcsc"))
CONFIG_CSR = register(dataclasses.replace(
    CONFIG, arch="bfs-rmat-csr", storage="csr", fold_mode="alltoall"))
CONFIG_TOPDOWN = register(dataclasses.replace(
    CONFIG, arch="bfs-rmat-topdown", direction_optimizing=False))

# --- §Perf hillclimb variants (beyond-paper; see EXPERIMENTS.md §Perf) ---
# i1, i2: compact bitmap fold; opt: + compact parent updates.  *_pure
# folds are the steady-state path the roofline lowers; the runtime config
# (bfs-rmat-opt-rt) keeps capacity fallbacks.
CONFIG_I1 = register(dataclasses.replace(
    CONFIG, arch="bfs-rmat-i1", fold_mode="bitmap_pure"))
CONFIG_I2 = register(dataclasses.replace(
    CONFIG, arch="bfs-rmat-i2", fold_mode="bitmap_pure"))
CONFIG_OPT = register(dataclasses.replace(
    CONFIG, arch="bfs-rmat-opt", fold_mode="bitmap_pure",
    compact_updates=True))
CONFIG_OPT_RT = register(dataclasses.replace(
    CONFIG, arch="bfs-rmat-opt-rt", fold_mode="bitmap",
    compact_updates=True))
# batched roots sharded over the pod axis (multi-pod Graph500 pattern)
CONFIG_MULTIROOT = register(dataclasses.replace(
    CONFIG, arch="bfs-rmat-multiroot"))

# --- 1D row-decomposition baseline (the paper's comparison axis) ---
# Same R-MAT shapes and direction-optimizing heuristics; the benchmark
# harness sweeps bfs-rmat vs bfs-rmat-1d on identical graphs for the
# Eq. 2 wire-volume comparison.
CONFIG_1D = register(BFSConfig(arch="bfs-rmat-1d", decomposition="1d"))
CONFIG_1D_TOPDOWN = register(dataclasses.replace(
    CONFIG_1D, arch="bfs-rmat-1d-topdown", direction_optimizing=False))
# 1D with strip-DCSC compressed pointers — the previously missing half
# of the Fig. 6 CSR/DCSC x 1D/2D grid (run with local_mode="kernel" to
# take the Pallas strip SpMSV; see core/local_ops.py)
CONFIG_1D_DCSC = register(dataclasses.replace(
    CONFIG_1D, arch="bfs-rmat-1d-dcsc", storage="dcsc"))
# 1D with the SPARSE owner-directed frontier exchange ("1ds",
# core/steps_1d_sparse.py): capped frontier-id buckets broadcast per
# level with a dense bitmap fallback — the Buluc & Madduri formulation
# whose closed form is comm_model.topdown_1d_words
CONFIG_1DS = register(dataclasses.replace(
    CONFIG_1D, arch="bfs-rmat-1ds", decomposition="1ds"))
# raw-id buckets (frontier_codec="none"): the PR 5 wire baseline the
# packed codec is measured against, and the config whose wire_expand
# matches the uncompressed closed forms (sparse_expand_1d_words)
CONFIG_1DS_RAW = register(dataclasses.replace(
    CONFIG_1DS, arch="bfs-rmat-1ds-raw", frontier_codec="none"))

# --- Latency-lean fast path (instrument=False): counters compiled out
# (level_stats keep only what the loop reduces), one fused scalar
# reduction per level, batched bottom-up
# update exchange — the depth+time+TEPS configuration of the paper's §7
# runs (see README "performance"; instrumented variants above exist for
# Eq. 2 / crossover artifacts)
CONFIG_FAST = register(dataclasses.replace(
    CONFIG, arch="bfs-rmat-fast", instrument=False))
CONFIG_1DS_FAST = register(dataclasses.replace(
    CONFIG_1DS, arch="bfs-rmat-1ds-fast", instrument=False))

# --- Software-pipelined expand (expand_chunks > 1): the 1d/1ds top-down
# gather split into chunks consumed while the next is in flight; the 2d
# bottom-up ring pipelined via the R/G bitmap split (core/steps.py).
# Parents are bit-identical to the unpipelined configs; expand_chunks
# must divide the strip's packed word count (and cap_x for "1ds").
CONFIG_PIPE = register(dataclasses.replace(
    CONFIG_FAST, arch="bfs-rmat-pipe", expand_chunks=2))
CONFIG_1D_PIPE = register(dataclasses.replace(
    CONFIG_1D, arch="bfs-rmat-1d-pipe", instrument=False, expand_chunks=2))
CONFIG_1DS_PIPE = register(dataclasses.replace(
    CONFIG_1DS_FAST, arch="bfs-rmat-1ds-pipe", expand_chunks=4))
