"""Compile rehearsals for a TPU v5e that is described, not attached.

Every BFS Pallas kernel, and the whole search of every decomposition,
is compiled with Mosaic for one chip of a described ``v5e:2x2``
topology.  Nothing runs: this catches what the chip's compiler refuses
(tiling, unaligned slices, in-kernel gathers, fast-memory overflow)
without a chip.  Interpret-mode parity lives in test_kernels_bfs.py and
test_frontier_codec.py.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and
every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.base import BFSConfig
from repro.core.engine import hlo_op_scopes, plan_bfs
from repro.graph.formats import build_blocked, build_blocked_1d
from repro.graph.rmat import rmat_graph
from repro.kernels.bottomup.bottomup import bottomup_substep_kernel
from repro.kernels.frontier_codec.frontier_codec import (
    decode_buckets_kernel, encode_offsets_kernel)
from repro.kernels.spmsv.spmsv import gather_segments
from repro.kernels.spmsv.strip import (gather_strip_segments,
                                       gather_strip_segments_chunk)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def test_bottomup_substep_compiles(one_chip):
    chunk, cap_seg, nc = 4096, 65536, 16384
    c = _compile(
        lambda rp, ue, fw, cv, off, ne: bottomup_substep_kernel(
            rp, ue, fw, cv, off, ne, rt=128, interpret=False),
        _i32((chunk + 1,), one_chip), _i32((cap_seg,), one_chip),
        _u32((nc // 32,), one_chip), _i32((chunk,), one_chip),
        _i32((), one_chip), _i32((), one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_block_spmsv_gather_compiles(one_chip):
    c = _compile(
        lambda s, n, r: gather_segments(s, n, r, cap_f=4096, maxdeg=1000,
                                        interpret=False),
        _i32((4096,), one_chip), _i32((4096,), one_chip),
        _i32((65536,), one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_strip_gather_compiles(one_chip):
    n, cap_nzc = 16384, 4096
    c = _compile(
        lambda jc, cp, nzc, r, fw: gather_strip_segments(
            jc, cp, nzc, r, fw, maxdeg=1000, interpret=False),
        _i32((cap_nzc,), one_chip), _i32((cap_nzc + 1,), one_chip),
        _i32((), one_chip), _i32((65536,), one_chip),
        _u32((n // 32,), one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_strip_gather_chunk_compiles(one_chip):
    n, p, n_chunks, cap_nzc = 16384, 4, 2, 4096
    w_sub = (n // p) // 32 // n_chunks
    c = _compile(
        lambda jc, cp, nzc, r, fs: gather_strip_segments_chunk(
            jc, cp, nzc, r, fs, n=n, p=p, k=1, n_chunks=n_chunks,
            maxdeg=1000, interpret=False),
        _i32((cap_nzc,), one_chip), _i32((cap_nzc + 1,), one_chip),
        _i32((), one_chip), _i32((65536,), one_chip),
        _u32((p * w_sub,), one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("chunk", [4096, 1 << 20])
def test_codec_encode_compiles(one_chip, chunk):
    c = _compile(
        lambda off, cnt: encode_offsets_kernel(off, cnt, chunk,
                                               interpret=False),
        _i32((256,), one_chip), _i32((), one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("chunk", [4096, 1 << 20])
def test_codec_decode_compiles(one_chip, chunk):
    from repro.core.comm_model import codec_bits, codec_bucket_words
    p, cap = 4, 256
    words = codec_bucket_words(cap, codec_bits(chunk))
    c = _compile(
        lambda recv: decode_buckets_kernel(recv, chunk, cap, p * chunk, p,
                                           interpret=False),
        _u32((p * words,), one_chip))
    assert "tpu_custom_call" in c.as_text()


def _small_graph(decomposition):
    e = rmat_graph(10, edge_factor=16, seed=3)
    if decomposition == "2d":
        return build_blocked(e, 1, 1, align=128, cap_pad=128)
    return build_blocked_1d(e, 1, align=128, cap_pad=128)


def _topo_mesh(topo, decomposition):
    devs = np.asarray(topo.devices[:1])
    if decomposition == "2d":
        return Mesh(devs.reshape(1, 1), ("data", "model"))
    return Mesh(devs, ("data",))


@pytest.mark.parametrize("decomposition", ["1d", "1ds", "2d"])
def test_plan_on_described_tpu_mesh_compiles_kernels(topo, decomposition):
    """The interpret switch is read off the plan's mesh: a (described)
    TPU mesh never runs the Pallas interpreter (test_engine.py pins the
    CPU side)."""
    cfg = BFSConfig(decomposition=decomposition, storage="dcsc")
    plan = plan_bfs(_small_graph(decomposition), cfg,
                    _topo_mesh(topo, decomposition), local_mode="kernel")
    assert not plan.statics.interpret and not plan.level_args().interpret


def _search_hlo(topo, decomposition, local_mode):
    """The compiled single-root (instrument=False) search for one
    described v5e chip, as HLO text."""
    g = _small_graph(decomposition)
    mesh = _topo_mesh(topo, decomposition)
    cfg = BFSConfig(decomposition=decomposition, storage="dcsc",
                    instrument=False)
    plan = plan_bfs(g, cfg, mesh, local_mode=local_mode)
    sh = NamedSharding(mesh, P(*plan.axes))
    arrays = g.device_arrays()
    gspec = {k: jax.ShapeDtypeStruct(np.shape(arrays[k]),
                                     np.asarray(arrays[k]).dtype,
                                     sharding=sh) for k in plan.keys}
    root = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    return plan.build_fn().lower(gspec, root).compile().as_text()


@pytest.mark.parametrize("local_mode", ["dense", "kernel"])
@pytest.mark.parametrize("decomposition", ["1d", "1ds", "2d"])
def test_whole_search_compiles(topo, decomposition, local_mode):
    """The single-root search program of each decomposition compiles
    for one v5e chip; kernel mode carries the Mosaic kernels
    (``tpu_custom_call``), dense mode is plain XLA."""
    hlo = _search_hlo(topo, decomposition, local_mode)
    assert ("tpu_custom_call" in hlo) == (local_mode == "kernel")


def test_dense_one_chip_search_has_only_the_level_loop(topo):
    """The dense 2d search on one chip reads each edge's row from
    edge_dst: the level loop is its one while, and no loop lies under
    the bottom-up row lookup's scope."""
    hlo = _search_hlo(topo, "2d", "dense")
    loops = re.findall(r"^\s*(?:ROOT\s+)?%?(while[\w.-]*)\s*=", hlo,
                       re.MULTILINE)
    assert len(loops) == 1, loops
    assert loops[0] not in hlo_op_scopes(hlo)
