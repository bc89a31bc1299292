"""Kernel-vs-oracle sweeps: embedding_bag (TBE) and flash attention."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.embedding_bag import ops as eb_ops
from repro.kernels.embedding_bag import ref as eb_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref


@pytest.mark.parametrize("V,D,B,L", [
    (64, 16, 32, 1), (128, 32, 64, 4), (1000, 16, 128, 8), (32, 8, 256, 2),
])
@pytest.mark.parametrize("mode", ["sum", "mean"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_kernel(V, D, B, L, mode, dtype):
    rng = np.random.default_rng(V + B + L)
    table = jnp.asarray(rng.normal(size=(V, D)), dtype)
    ids = rng.integers(-1, V, (B, L)).astype(np.int32)
    w = jnp.asarray(rng.random((B, L)), jnp.float32)
    want = eb_ref.embedding_bag(table, jnp.asarray(ids), w, mode=mode)
    got = eb_ops.embedding_bag(table, jnp.asarray(ids), w, mode=mode,
                               bt=min(32, B), interpret=True)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_embedding_bag_no_weights_all_padded():
    table = jnp.ones((16, 8), jnp.float32)
    ids = jnp.full((32, 4), -1, jnp.int32)
    out = eb_ops.embedding_bag(table, ids, bt=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("Sq,Sk,dh,causal,window,q_off", [
    (128, 128, 64, True, None, 0),
    (64, 64, 32, False, None, 0),
    (128, 256, 64, True, 64, 0),      # sliding window
    (1, 256, 64, True, None, 255),    # decode: 1 query over long KV
    (64, 192, 128, True, None, 128),  # chunked-prefill continuation
    (96, 100, 64, True, None, 4),     # ragged Sk (pad path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(Sq, Sk, dh, causal, window, q_off, dtype):
    rng = np.random.default_rng(Sq + Sk + dh)
    BH = 3
    q = jnp.asarray(rng.normal(size=(BH, Sq, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(BH, Sk, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(BH, Sk, dh)), dtype)
    want = fa_ref.attention(q, k, v, causal=causal, window=window,
                            q_offset=q_off)
    got = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_off, bq=64, bk=64,
                                 interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_chunked_attention():
    """The model's pure-jnp chunked attention and the kernel agree."""
    from repro.models.common import chunked_attention
    rng = np.random.default_rng(0)
    B, S, Hq, Hkv, dh = 2, 128, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, Hq, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), jnp.float32)
    out_model = chunked_attention(q, k, v, q_offset=0, causal=True,
                                  kv_chunk=32)
    # kernel path: flatten (B, H) and repeat KV for GQA
    rep = Hq // Hkv
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, dh)
    kf = jnp.repeat(k, rep, 2).transpose(0, 2, 1, 3).reshape(B * Hq, S, dh)
    vf = jnp.repeat(v, rep, 2).transpose(0, 2, 1, 3).reshape(B * Hq, S, dh)
    out_k = fa_ops.flash_attention(qf, kf, vf, causal=True, bq=64, bk=64,
                                   interpret=True)
    out_k = out_k.reshape(B, Hq, S, dh).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_model),
                               rtol=2e-5, atol=2e-5)
