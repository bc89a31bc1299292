"""Frontier-codec + pack_ids/unpack_ids boundary coverage.

Three layers, innermost out:

  * ``frontier.pack_ids``/``unpack_ids`` boundary cases the sparse
    exchange depends on — a frontier of EXACTLY cap_x ids (the overflow
    predicate is ``>``, not ``>=``), the last slot of a chunk, and
    all-sentinel buckets roundtripping to an empty bitmap;
  * the packed codec (``kernels/frontier_codec``): property roundtrip,
    Pallas-kernel vs jnp-oracle bit-parity, count-word clamping;
  * ``sparse_exchange_1d`` at p=1: exact-capacity levels stay sparse,
    and the visited-bitmap sieve demonstrably strips already-discovered
    vertices from a deliberately dirty frontier (in the BFS loop the
    frontier is always fresh, so the sieve is invisible there — this is
    where its behavior is actually observable).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import comm_model
from repro.core.frontier import pack_ids, unpack_bits, unpack_ids
from repro.core.steps_1d_sparse import sparse_exchange_1d
from repro.graph.formats import build_blocked_1d
from repro.graph.rmat import rmat_graph
from repro.launch.mesh import make_local_mesh_1d
from repro.kernels.frontier_codec import ops as codec_ops
from repro.kernels.frontier_codec import ref as codec_ref


# ---------------------------------------------------------------------------
# pack_ids / unpack_ids boundaries (satellite coverage)
# ---------------------------------------------------------------------------


def test_pack_ids_exactly_cap_no_loss():
    """cap set bits fill the buffer exactly — no sentinel, no drop (the
    exchange's overflow predicate is n_local > cap_x, so == cap_x must
    go sparse and be lossless)."""
    chunk, cap = 128, 32
    idx = np.sort(np.random.default_rng(0).choice(chunk, cap, replace=False))
    mask = np.zeros(chunk, bool)
    mask[idx] = True
    ids = np.asarray(pack_ids(jnp.asarray(mask), cap, 1000, 9999))
    assert np.array_equal(ids, 1000 + idx)
    assert not (ids == 9999).any()


def test_pack_ids_last_slot_of_chunk():
    """The final vertex of the chunk (off == chunk-1) must survive the
    off < chunk sentinel comparison — an off-by-one there would silently
    drop exactly the last slot."""
    chunk, cap = 128, 8
    mask = np.zeros(chunk, bool)
    mask[chunk - 1] = True
    ids = np.asarray(pack_ids(jnp.asarray(mask), cap, 0, -1))
    assert ids[0] == chunk - 1
    assert (ids[1:] == -1).all()
    # and it roundtrips through the scatter into the last bitmap slot
    words = unpack_ids(jnp.asarray(ids), chunk)
    back = np.asarray(unpack_bits(words))
    assert back[chunk - 1] and back.sum() == 1


def test_all_sentinel_bucket_roundtrips_empty():
    """A bucket of nothing but sentinels (empty frontier, or a peer with
    no discoveries) must scatter to an all-zero bitmap — mode="drop"
    discards every out-of-range id."""
    n, cap = 256, 16
    ids = jnp.full((cap,), n, jnp.int32)          # the pack_ids sentinel
    assert not np.asarray(unpack_ids(ids, n)).any()
    empty = pack_ids(jnp.zeros((64,), bool), cap, 0, n)
    assert (np.asarray(empty) == n).all()
    assert not np.asarray(unpack_ids(empty, n)).any()


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_pack_unpack_roundtrip_under_cap(seed):
    rng = np.random.default_rng(seed)
    chunk = 32 * int(rng.integers(1, 8))
    cap = int(rng.integers(1, chunk + 1))
    k = int(rng.integers(0, cap + 1))
    idx = np.sort(rng.choice(chunk, k, replace=False))
    mask = np.zeros(chunk, bool)
    mask[idx] = True
    ids = pack_ids(jnp.asarray(mask), cap, 0, chunk)
    back = unpack_bits(unpack_ids(ids, chunk))
    assert np.array_equal(np.asarray(back), mask)


# ---------------------------------------------------------------------------
# Packed codec: roundtrip + Pallas/oracle parity
# ---------------------------------------------------------------------------


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_codec_roundtrip_property(seed):
    """encode -> concat buckets -> decode recovers exactly the live ids
    of every bucket (rebased by owner), sentinels elsewhere — for random
    chunk sizes, capacities, and fills, Pallas bit-identical to the
    oracle."""
    rng = np.random.default_rng(seed)
    chunk = 32 * int(rng.integers(1, 40))
    cap = int(rng.integers(1, min(chunk, 160) + 1))
    p = int(rng.choice([1, 2, 4, 8]))
    n = chunk * p
    bufs, want = [], []
    for k in range(p):
        cnt = int(rng.integers(0, cap + 1))
        off = np.sort(rng.choice(chunk, cnt, replace=False)).astype(np.int32)
        offp = np.concatenate([off, np.full(cap - cnt, chunk, np.int32)])
        e_ref = codec_ref.encode_offsets(jnp.asarray(offp), jnp.int32(cnt),
                                         chunk)
        e_ker = codec_ops.encode_offsets(jnp.asarray(offp), jnp.int32(cnt),
                                         chunk, interpret=True)
        assert np.array_equal(np.asarray(e_ref), np.asarray(e_ker))
        assert int(np.asarray(e_ref)[0]) == cnt     # count word is first
        bufs.append(np.asarray(e_ref))
        want.append(k * chunk + off)
    recv = jnp.asarray(np.concatenate(bufs))
    d_ref = np.asarray(codec_ref.decode_buckets(recv, chunk, cap, n))
    d_ker = np.asarray(codec_ops.decode_buckets(recv, chunk, cap, n, p,
                                                interpret=True))
    assert np.array_equal(d_ref, d_ker)
    live = d_ref[d_ref < n]
    assert np.array_equal(np.sort(live), np.sort(np.concatenate(want)))
    # decoded buffer is (p, cap) bucket-major: slots past count are n
    rows = d_ref.reshape(p, cap)
    for k in range(p):
        cnt = int(bufs[k][0])
        assert (rows[k][cnt:] == n).all()


def test_codec_buffer_layout_and_count_clamp():
    chunk, cap = 1024, 32
    bits = comm_model.codec_bits(chunk)
    w = comm_model.codec_packed_words(cap, bits)
    off = jnp.arange(cap, dtype=jnp.int32)
    buf = codec_ref.encode_offsets(off, jnp.int32(cap), chunk)
    assert buf.shape == (1 + w,) and buf.dtype == jnp.uint32
    # an over-large count word (corrupt input) clamps to cap on encode
    buf2 = codec_ref.encode_offsets(off, jnp.int32(cap + 100), chunk)
    assert int(np.asarray(buf2)[0]) == cap
    # encoded buckets really are smaller than raw id buckets
    assert (1 + w) < cap  # u32 words vs cap i32 id slots


# ---------------------------------------------------------------------------
# sparse_exchange_1d: exact capacity + the observable sieve
# ---------------------------------------------------------------------------


def _exchange(front, part, cap_x, visited=None, codec="none",
              use_kernel=False):
    """Run the exchange in a p=1 shard_map; returns (bitmap bool[n],
    over bool)."""
    mesh = make_local_mesh_1d(1)

    def body(f, v):
        f_words, wire, over = sparse_exchange_1d(
            f[0], "data", cap_x, part, instrument=True,
            visited=None if visited is None else v[0],
            codec=codec, use_kernel=use_kernel, interpret=True)
        return f_words[None], over.reshape(1)

    v_in = np.zeros_like(front) if visited is None else visited
    words, over = jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data")), check_vma=False)(front, v_in)
    return (np.asarray(unpack_bits(jnp.asarray(words[0]))),
            bool(np.asarray(over)[0]))


@pytest.fixture(scope="module")
def part1():
    e = rmat_graph(8, edge_factor=8, seed=4)
    return build_blocked_1d(e, 1, align=32, cap_pad=32).part


@pytest.mark.parametrize("codec", ["none", "packed"])
def test_exchange_exactly_cap_stays_sparse(part1, codec):
    """== cap_x send bits must take the sparse branch (predicate is >)
    and reproduce the frontier exactly; cap_x+1 overflows to dense —
    and BOTH produce the same bitmap."""
    cap = 32
    rng = np.random.default_rng(1)
    for extra in (0, 1):
        idx = np.sort(rng.choice(part1.chunk, cap + extra, replace=False))
        front = np.zeros((1, part1.chunk), bool)
        front[0, idx] = True
        bitmap, over = _exchange(front, part1, cap, codec=codec)
        assert over == bool(extra)
        assert np.array_equal(bitmap[: part1.chunk], front[0])


@pytest.mark.parametrize("codec,use_kernel",
                         [("none", False), ("packed", False),
                          ("packed", True)])
def test_sieve_strips_visited_from_dirty_frontier(part1, codec, use_kernel):
    """With a deliberately DIRTY frontier (re-listing already-visited
    vertices — never produced by the BFS loop, which is why parents stay
    bit-identical there), the sieve must remove the visited bits from
    the exchanged bitmap and from the overflow count."""
    cap = 32
    rng = np.random.default_rng(2)
    idx = np.sort(rng.choice(part1.chunk, 48, replace=False))
    front = np.zeros((1, part1.chunk), bool)
    front[0, idx] = True
    visited = np.zeros((1, part1.chunk), bool)
    visited[0, idx[:20]] = True                  # 20 stale re-listings
    # unsieved: 48 > cap -> dense fallback, all 48 bits ship
    bitmap, over = _exchange(front, part1, cap, codec=codec,
                             use_kernel=use_kernel)
    assert over and bitmap[: part1.chunk].sum() == 48
    # sieved: 28 live bits fit the buckets -> sparse, visited bits gone
    bitmap, over = _exchange(front, part1, cap, visited=visited,
                             codec=codec, use_kernel=use_kernel)
    assert not over
    want = front[0] & ~visited[0]
    assert np.array_equal(bitmap[: part1.chunk], want)
    assert bitmap[: part1.chunk].sum() == 28


def test_sieve_excludes_frontier_itself(part1):
    """visited masks built as (pi != -1) & ~front keep the frontier: a
    visited mask that (wrongly) included frontier vertices would zero
    the exchange.  Guard the exchange-level contract: visited ∩ front
    is removed, so callers MUST exclude the frontier — exactly what
    topdown_level_1ds does."""
    front = np.zeros((1, part1.chunk), bool)
    front[0, :8] = True
    visited = front.copy()                       # pathological caller
    bitmap, _ = _exchange(front, part1, 32, visited=visited)
    assert not bitmap.any()
