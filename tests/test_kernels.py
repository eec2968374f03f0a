"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps.

Every kernel is validated against ref.py; the chunked refs are additionally
validated against the naive materialized-scores oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention
from repro.kernels.entropy_features import byte_entropy
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quant_pack import quant_pack
from repro.kernels.ssd_scan import ssd_scan


def _qkv(key, B, Sq, Sk, Hq, Hkv, D, dtype, Dv=None):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, Sq, Hq, D), dtype)
    k = jax.random.normal(k2, (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(k3, (B, Sk, Hkv, Dv or D), dtype)
    return q, k, v


# ------------------------------------------------------------- chunked refs
@pytest.mark.parametrize("Sq,Sk,window", [(32, 32, None), (64, 64, 16),
                                          (16, 48, None)])
def test_flash_ref_matches_naive(Sq, Sk, window):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, Sq, Sk, 4, 2, 16, jnp.float32)
    out_ref = R.flash_attention_ref(q, k, v, causal=True, window=window,
                                    chunk=16)
    out_naive = R.attention_naive(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_naive),
                               rtol=2e-5, atol=2e-5)


def test_decode_ref_matches_naive():
    key = jax.random.PRNGKey(1)
    B, S, Hq, Hkv, D = 3, 40, 8, 2, 16
    q = jax.random.normal(key, (B, Hq, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, D))
    kv_len = jnp.array([5, 17, 40])
    out = R.decode_attention_ref(q, k, v, kv_len, chunk=16)
    for b in range(B):
        L = int(kv_len[b])
        ref = R.attention_naive(q[b:b + 1, None], k[b:b + 1, :L],
                                v[b:b + 1, :L], causal=False)
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(ref[0, 0]), rtol=2e-5, atol=2e-5)


# -------------------------------------------------------- flash kernel sweep
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,D,window,softcap", [
    (1, 128, 4, 4, 64, None, None),      # MHA
    (2, 96, 8, 2, 32, None, None),       # GQA, non-multiple seq
    (1, 256, 4, 1, 64, 64, None),        # MQA + sliding window
    (1, 128, 2, 2, 64, None, 50.0),      # logit softcap (gemma2)
])
def test_flash_kernel_vs_ref(B, S, Hq, Hkv, D, window, softcap, dtype):
    q, k, v = _qkv(jax.random.PRNGKey(2), B, S, S, Hq, Hkv, D, dtype)
    out = flash_attention(q, k, v, causal=True, window=window,
                          softcap=softcap, block_q=64, block_k=64,
                          interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=True, window=window,
                                softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_kernel_noncausal_and_dv():
    """Cross-attention shape: non-causal, Dv != Dk (MLA-style)."""
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, 64, 64, 4, 2, 48,
                   jnp.float32, Dv=32)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                          interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------- decode kernel sweep
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,D,window", [
    (2, 256, 8, 2, 64, None),
    (1, 512, 4, 1, 128, None),           # MQA long cache
    (3, 200, 8, 8, 32, 64),              # MHA + window, ragged lengths
])
def test_decode_kernel_vs_ref(B, S, Hq, Hkv, D, window, dtype):
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (B, Hq, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, D), dtype)
    kv_len = jnp.asarray(np.random.default_rng(0).integers(window or 2, S + 1,
                                                           B))
    out = decode_attention(q, k, v, kv_len, window=window, block_k=64,
                           interpret=True)
    ref = R.decode_attention_ref(q, k, v, kv_len, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


# --------------------------------------------------------------- SSD kernel
def _ssd_inputs(key, b, s, h, p, g, n, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, g, n), dtype) * 0.5
    C = jax.random.normal(jax.random.fold_in(key, 9), (b, s, g, n), dtype) * 0.5
    D = jnp.ones((h,))
    return x, dt, A, B, C, D


def test_ssd_ref_matches_sequential():
    """Chunked SSD ref == naive per-step recurrence."""
    x, dt, A, B, C, D = _ssd_inputs(jax.random.PRNGKey(5), 1, 24, 2, 4, 1, 8)
    y_ref, st_ref = R.ssd_scan_ref(x, dt, A, B, C, D, chunk=8)
    # sequential oracle
    state = jnp.zeros((1, 2, 4, 8))
    ys = []
    for t in range(24):
        y_t, state = R.ssd_step_ref(state, x[:, t], dt[:, t], A, B[:, t],
                                    C[:, t], D)
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_seq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_ref), np.asarray(state),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 64, 2, 8, 1, 16, 16),
    (2, 48, 4, 16, 2, 8, 16),     # grouped B/C, non-multiple seq
    (1, 100, 3, 8, 1, 8, 32),     # ragged tail chunk
])
def test_ssd_kernel_vs_ref(b, s, h, p, g, n, chunk):
    x, dt, A, B, C, D = _ssd_inputs(jax.random.PRNGKey(6), b, s, h, p, g, n)
    y, st = ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    y_ref, st_ref = R.ssd_scan_ref(x, dt, A, B, C, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st.reshape(st_ref.shape)),
                               np.asarray(st_ref), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ entropy kernel
@pytest.mark.parametrize("n,block", [(1000, 256), (8192, 1024), (37, 64)])
def test_entropy_kernel_vs_ref(n, block):
    data = jnp.asarray(np.random.default_rng(0).integers(0, 256, n), jnp.uint8)
    hist, ent = byte_entropy(data, block=block, interpret=True)
    hist_ref, ent_ref = R.byte_entropy_ref(data)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(hist_ref))
    np.testing.assert_allclose(float(ent), float(ent_ref), rtol=1e-5)


def test_entropy_matches_numpy_oracle():
    data = np.random.default_rng(1).integers(0, 16, 4096).astype(np.uint8)
    _, ent = byte_entropy(jnp.asarray(data), interpret=True)
    counts = np.bincount(data, minlength=256)
    p = counts / counts.sum()
    ent_np = -(p[p > 0] * np.log2(p[p > 0])).sum()
    assert abs(float(ent) - ent_np) < 1e-4


def _entropy_numpy(data: np.ndarray):
    """Histogram/entropy golden reference (bits per byte)."""
    hist = np.bincount(data, minlength=256)
    p = hist / max(len(data), 1)
    nz = p[p > 0]
    return hist, float(-(nz * np.log2(nz)).sum())


@pytest.mark.parametrize("n,block", [
    (4096, 1024),     # n % block == 0: empty-pad block boundary
    (4097, 1024),     # one byte spills into a heavily padded final block
    (5000, 1024),     # n not divisible by block
    (100, 1024),      # n < block: block clamps to n, no pad
    (1, 64),          # single byte
])
def test_entropy_golden_vs_numpy(n, block):
    """interpret=True kernel vs the NumPy histogram/entropy reference; pad
    bytes (zeros) must never leak into the histogram."""
    data = np.random.default_rng(n).integers(1, 256, n).astype(np.uint8)
    hist, ent = byte_entropy(jnp.asarray(data), block=block, interpret=True)
    hist_np, ent_np = _entropy_numpy(data)
    np.testing.assert_array_equal(np.asarray(hist), hist_np)
    assert int(np.asarray(hist)[0]) == 0, "zero-pad leaked into histogram"
    assert float(ent) == pytest.approx(ent_np, abs=1e-4)


def test_entropy_all_identical_bytes_is_zero():
    """A constant payload carries 0 bits/byte, exactly."""
    data = np.full(3000, 7, np.uint8)
    hist, ent = byte_entropy(jnp.asarray(data), block=512, interpret=True)
    assert float(ent) == 0.0
    assert int(np.asarray(hist)[7]) == 3000 and int(np.asarray(hist).sum()) == 3000


@pytest.mark.parametrize("n_symbols,expect_bits", [(2, 1.0), (4, 2.0),
                                                   (256, 8.0)])
def test_entropy_uniform_alphabet_golden(n_symbols, expect_bits):
    """Uniform k-symbol alphabets have exactly log2(k) bits/byte."""
    data = np.tile(np.arange(n_symbols, dtype=np.uint8), 16)
    _, ent = byte_entropy(jnp.asarray(data), block=128, interpret=True)
    assert float(ent) == pytest.approx(expect_bits, abs=1e-5)


# -------------------------------------------------------------- quant kernel
@pytest.mark.parametrize("shape", [(4, 256), (1024,), (3, 2, 512)])
def test_quant_kernel_vs_ref(shape):
    x = jax.random.normal(jax.random.PRNGKey(7), shape) * 5.0
    q, s = quant_pack(x, interpret=True)
    q_ref, s_ref = R.quant_pack_ref(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6)
    back = ops.quant_unpack(q, s)
    assert float(jnp.abs(back - x).max()) <= float(jnp.abs(x).max()) / 127.0 + 1e-6


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_quant_roundtrip_property(seed):
    """|dequant(quant(x)) - x| <= blockmax/127 for every block."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (8, 256)) * \
        (1.0 + (seed % 7))
    q, s = R.quant_pack_ref(x)
    back = R.quant_unpack_ref(q, s)
    err = jnp.abs(back - x).max(axis=1)
    bound = jnp.abs(x).max(axis=1) / 127.0 * 0.5 + 1e-7
    assert bool((err <= bound + 1e-6).all())


# ----------------------------------------------------------- ops dispatcher
def test_ops_dispatch_ref_on_cpu():
    q, k, v = _qkv(jax.random.PRNGKey(8), 1, 32, 32, 2, 2, 16, jnp.float32)
    a = ops.flash_attention(q, k, v)          # auto -> ref on CPU
    b = ops.flash_attention(q, k, v, impl="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
