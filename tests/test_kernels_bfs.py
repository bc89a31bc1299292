"""Per-kernel allclose vs pure-jnp oracles: spmsv gather + bottom-up
sub-step, swept over shapes and frontier densities (interpret mode)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.frontier import INT_INF, pack_bits
from repro.kernels.bottomup.ops import bottomup_substep as bu_kernel
from repro.kernels.bottomup.ref import bottomup_substep as bu_ref
from repro.kernels.spmsv import ops as spmsv_ops
from repro.kernels.spmsv.ref import spmsv_dense


def _random_block(rng, nc, nr, density):
    """Random CSC block + matching CSR orientation arrays."""
    mask = rng.random((nr, nc)) < density
    v, u = np.nonzero(mask)
    order = np.lexsort((v, u))                       # CSC: by (u, v)
    u_c, v_c = u[order], v[order]
    col_ptr = np.zeros(nc + 1, np.int32)
    np.add.at(col_ptr, u_c + 1, 1)
    col_ptr = np.cumsum(col_ptr).astype(np.int32)
    order_r = np.lexsort((u, v))                     # CSR: by (v, u)
    u_r, v_r = u[order_r], v[order_r]
    row_ptr = np.zeros(nr + 1, np.int32)
    np.add.at(row_ptr, v_r + 1, 1)
    row_ptr = np.cumsum(row_ptr).astype(np.int32)
    return (col_ptr, v_c.astype(np.int32), u_c.astype(np.int32),
            row_ptr, u_r.astype(np.int32))


@pytest.mark.parametrize("nc,nr,density", [
    (64, 64, 0.05), (128, 64, 0.2), (32, 96, 0.5), (256, 128, 0.01),
])
@pytest.mark.parametrize("fdensity", [0.0, 0.1, 1.0])
def test_spmsv_kernel_matches_dense(nc, nr, density, fdensity):
    rng = np.random.default_rng(nc + nr + int(100 * (density + fdensity)))
    col_ptr, row_idx, edge_src, _, _ = _random_block(rng, nc, nr, density)
    nnz = int(col_ptr[-1])
    f_cj = jnp.asarray(rng.random(nc) < fdensity)
    col_offset = jnp.int32(1000)
    want = spmsv_dense(jnp.asarray(edge_src), jnp.asarray(row_idx),
                       jnp.int32(nnz), f_cj, nr, col_offset)
    maxdeg = max(int(np.diff(col_ptr).max()), 1)
    ridx = jnp.pad(jnp.asarray(row_idx), (0, 256))
    got = spmsv_ops.spmsv_block_csr(jnp.asarray(col_ptr), ridx, f_cj, nr,
                                    col_offset, cap_f=nc, maxdeg=maxdeg,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # DCSC path: build compressed pointers and require identical output
    deg = np.diff(col_ptr)
    nzcols = np.flatnonzero(deg)
    jc = np.full(max(len(nzcols), 1) + 3, nc, np.int32)
    cp = np.zeros(jc.size + 1, np.int32)
    jc[:len(nzcols)] = nzcols
    cp[:len(nzcols)] = col_ptr[nzcols]
    cp[len(nzcols):] = nnz
    got2 = spmsv_ops.spmsv_block_dcsc(
        jnp.asarray(jc), jnp.asarray(cp), jnp.int32(len(nzcols)), ridx,
        f_cj, nr, col_offset, cap_f=nc, maxdeg=maxdeg, interpret=True)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want))


@pytest.mark.parametrize("chunk,n", [(32, 64), (64, 256), (96, 32)])
@pytest.mark.parametrize("fdensity", [0.0, 0.2, 1.0])
def test_spmsv_strip_kernel_matches_dense(chunk, n, fdensity):
    """The 1D strip kernel (global column ids, bitmap test inside the
    kernel, col_offset structurally 0) must match the dense oracle."""
    rng = np.random.default_rng(chunk + n + int(10 * fdensity))
    m = 4 * chunk
    u = np.sort(rng.integers(0, n, m)).astype(np.int32)   # global sources
    v = rng.integers(0, chunk, m).astype(np.int32)        # local dests
    order = np.lexsort((v, u))
    u, v = u[order], v[order]
    f = rng.random(n) < fdensity
    f_words = pack_bits(jnp.asarray(f))
    want = spmsv_dense(jnp.asarray(u), jnp.asarray(v), jnp.int32(m),
                       jnp.asarray(f), chunk, jnp.int32(0))
    # strip DCSC over the sorted edges
    cols, first = np.unique(u, return_index=True)
    nzc = len(cols)
    cap_nzc = nzc + 5
    jc = np.full(cap_nzc, n, np.int32)
    cp = np.full(cap_nzc + 1, m, np.int32)
    jc[:nzc], cp[:nzc] = cols, first
    maxdeg = int(np.diff(np.append(first, m)).max())
    got = spmsv_ops.spmsv_strip_dcsc(
        jnp.asarray(jc), jnp.asarray(cp), jnp.int32(nzc),
        jnp.pad(jnp.asarray(v), (0, 256)), f_words, chunk, maxdeg=maxdeg,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("chunk,nc", [(32, 64), (128, 128), (256, 32)])
@pytest.mark.parametrize("fdensity,cdensity", [
    (0.0, 0.0), (0.3, 0.0), (0.3, 0.5), (1.0, 0.9), (1.0, 1.0),
])
def test_bottomup_kernel_matches_ref(chunk, nc, fdensity, cdensity):
    rng = np.random.default_rng(chunk + nc + int(10 * (fdensity + cdensity)))
    # a segment of `chunk` rows with random degrees
    deg = rng.integers(0, 9, chunk)
    rp = np.zeros(chunk + 1, np.int32)
    rp[1:] = np.cumsum(deg)
    n_edges = int(rp[-1])
    cap_seg = ((n_edges + 127) // 128) * 128 + 128
    ue = np.zeros(cap_seg, np.int32)
    ue[:n_edges] = rng.integers(0, nc, n_edges)
    f = rng.random(nc) < fdensity
    f_words = pack_bits(jnp.asarray(f))
    cvec = (rng.random(chunk) < cdensity).astype(np.int32)
    col_offset, ne = jnp.int32(7 * nc), jnp.int32(n_edges)
    want = bu_ref(jnp.asarray(rp), jnp.asarray(ue), f_words,
                  jnp.asarray(cvec), col_offset, ne)
    got = bu_kernel(jnp.asarray(rp), jnp.pad(jnp.asarray(ue), (0, 512)),
                    f_words, jnp.asarray(cvec), col_offset, ne,
                    rt=min(128, chunk), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_bottomup_kernel_property(seed):
    rng = np.random.default_rng(seed)
    chunk = 32 * int(rng.integers(1, 5))
    nc = 32 * int(rng.integers(1, 6))
    deg = rng.integers(0, 6, chunk)
    rp = np.zeros(chunk + 1, np.int32)
    rp[1:] = np.cumsum(deg)
    n_edges = int(rp[-1])
    cap_seg = max(((n_edges + 127) // 128) * 128, 128)
    ue = np.zeros(cap_seg, np.int32)
    ue[:n_edges] = rng.integers(0, nc, n_edges)
    f = rng.random(nc) < rng.random()
    f_words = pack_bits(jnp.asarray(f))
    cvec = (rng.random(chunk) < rng.random()).astype(np.int32)
    args = (jnp.asarray(rp), jnp.asarray(ue), f_words, jnp.asarray(cvec),
            jnp.int32(0), jnp.int32(n_edges))
    want = bu_ref(*args)
    got = bu_kernel(args[0], jnp.pad(args[1], (0, 512)), *args[2:], rt=32,
                    interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # invariants: completed rows never get parents; parents are in frontier
    out = np.asarray(got)
    assert (out[cvec == 1] == INT_INF).all()
    disc = np.flatnonzero(out != INT_INF)
    assert all(f[out[d]] for d in disc)


@pytest.mark.parametrize("chunk,max_deg,tail", [
    (32, 0, 128),     # every row empty: nothing in the window is live
    (64, 9, 96),      # padded tail: n_edges < cap
    (1, 40, 24),      # a single-row chunk
    (128, 7, 0),      # the window is full: n_edges == cap
], ids=["empty-rows", "padded-tail", "single-row", "full-window"])
def test_bottomup_ref_rows_read_matches_search(chunk, max_deg, tail):
    """The dense steps hand the oracle each window edge's row (edge_dst
    rebased to the segment) instead of letting it search rp_seg: both
    give the same parents.  The tail past n_edges holds rows of the next
    segment, as the 2d window slice reads them."""
    rng = np.random.default_rng(chunk + max_deg + tail)
    nc = 64
    deg = rng.integers(0, max_deg + 1, chunk)
    rp = np.zeros(chunk + 1, np.int32)
    rp[1:] = np.cumsum(deg)
    n_edges = int(rp[-1])
    cap = n_edges + tail
    ue = rng.integers(0, nc, cap).astype(np.int32)
    rows = np.concatenate([np.repeat(np.arange(chunk), deg),
                           chunk + rng.integers(0, chunk, tail)])
    f_words = pack_bits(jnp.asarray(rng.random(nc) < 0.4))
    cvec = (rng.random(chunk) < 0.3).astype(np.int32)
    args = (jnp.asarray(rp), jnp.asarray(ue), f_words, jnp.asarray(cvec),
            jnp.int32(3 * nc), jnp.int32(n_edges))
    want = np.asarray(bu_ref(*args))
    got = np.asarray(bu_ref(*args, ve_win=jnp.asarray(rows, jnp.int32)))
    np.testing.assert_array_equal(got, want)
    if n_edges:
        assert (want != INT_INF).any()
