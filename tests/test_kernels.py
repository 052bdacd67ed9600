"""Pallas kernel validation: sweep shapes/dtypes and assert_allclose each
kernel (interpret=True on CPU) against its ref.py oracle.  Operands are in
the kernels' head-major layouts.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                              # optional dev dependency
    from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssd_scan import ssd_scan

TOL = dict(rtol=2e-2, atol=2e-2)      # bf16 inputs, fp32 accumulation
TOL32 = dict(rtol=2e-5, atol=2e-5)


def _qkv(key, B, T, S, Hq, Hkv, D, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Hq, T, D), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, Hkv, S, D), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, Hkv, S, D), jnp.float32).astype(dtype)
    return q, k, v


# =========================================================================
# flash attention
# =========================================================================

@pytest.mark.parametrize("B,T,S,Hq,Hkv,D", [
    (1, 128, 128, 4, 4, 64),        # MHA square
    (2, 128, 256, 8, 2, 64),        # GQA, chunked prefill (q = last T of S)
    (1, 64, 64, 4, 1, 128),         # MQA, D=128
    (1, 100, 100, 2, 2, 64),        # non-multiple-of-block T
    (1, 32, 160, 4, 4, 32),         # small D, long KV
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(B, T, S, Hq, Hkv, D, dtype):
    q, k, v = _qkv(jax.random.key(0), B, T, S, Hq, Hkv, D, dtype)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    tol = TOL if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **tol)


@pytest.mark.parametrize("window", [16, 64, 4096])
def test_flash_sliding_window(window):
    q, k, v = _qkv(jax.random.key(1), 1, 128, 128, 4, 2, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


def test_flash_non_causal():
    q, k, v = _qkv(jax.random.key(2), 2, 64, 64, 4, 4, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


def test_flash_custom_scale():
    q, k, v = _qkv(jax.random.key(3), 1, 64, 64, 2, 2, 64, jnp.float32)
    out = flash_attention(q, k, v, softmax_scale=0.5, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, softmax_scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=12, deadline=None)
@given(
    T=st.sampled_from([8, 33, 64, 127]),
    extra=st.sampled_from([0, 16, 93]),
    Hkv=st.sampled_from([1, 2]),
    G=st.sampled_from([1, 2, 4]),
    D=st.sampled_from([32, 64]),
)
def test_flash_property_sweep(T, extra, Hkv, G, D):
    """Property sweep: arbitrary (T, S≥T, GQA group, D) agree with oracle."""
    S = T + extra
    q, k, v = _qkv(jax.random.key(42), 1, T, S, Hkv * G, Hkv, D, jnp.float32)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=5e-4, atol=5e-4)


# =========================================================================
# paged attention
# =========================================================================

def _paged_inputs(key, B, Hq, Hkv, D, page_size, pages_per_seq, dtype,
                  num_pages=None):
    kq, kk, kv, kc = jax.random.split(key, 4)
    num_pages = num_pages or (B * pages_per_seq + 1)
    q = jax.random.normal(kq, (B, Hq, D), jnp.float32).astype(dtype)
    k_pages = jax.random.normal(
        kk, (Hkv, num_pages, page_size, D), jnp.float32).astype(dtype)
    v_pages = jax.random.normal(
        kv, (Hkv, num_pages, page_size, D), jnp.float32).astype(dtype)
    # each sequence owns a disjoint page range (as the BlockManager produces)
    tables = np.arange(B * pages_per_seq, dtype=np.int32).reshape(B, pages_per_seq)
    max_ctx = page_size * pages_per_seq
    ctx = np.asarray(jax.random.randint(kc, (B,), 1, max_ctx + 1), np.int32)
    return q, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(ctx)


@pytest.mark.parametrize("B,Hq,Hkv,D,page,pps", [
    (2, 4, 4, 64, 16, 4),      # MHA
    (3, 8, 2, 64, 16, 3),      # GQA
    (1, 4, 1, 128, 32, 2),     # MQA, D=128
    (4, 2, 2, 32, 8, 5),       # small heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_vs_ref(B, Hq, Hkv, D, page, pps, dtype):
    q, kp, vp, bt, cl = _paged_inputs(
        jax.random.key(0), B, Hq, Hkv, D, page, pps, dtype)
    out = paged_attention(q, kp, vp, bt, cl, interpret=True)
    exp = ref.paged_attention_ref(q, kp, vp, bt, cl)
    tol = TOL if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **tol)


def test_paged_scattered_tables():
    """Non-contiguous page assignment (realistic after frees/reuse)."""
    key = jax.random.key(7)
    q, kp, vp, _, _ = _paged_inputs(key, 2, 4, 2, 64, 16, 3, jnp.float32,
                                    num_pages=32)
    bt = jnp.asarray([[31, 2, 17], [9, 25, 0]], jnp.int32)
    cl = jnp.asarray([40, 33], jnp.int32)
    out = paged_attention(q, kp, vp, bt, cl, interpret=True)
    exp = ref.paged_attention_ref(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


def test_paged_single_token_context():
    """ctx=1: softmax over one key must return exactly that value row."""
    key = jax.random.key(8)
    q, kp, vp, bt, _ = _paged_inputs(key, 1, 2, 2, 32, 8, 2, jnp.float32)
    cl = jnp.asarray([1], jnp.int32)
    out = paged_attention(q, kp, vp, bt, cl, interpret=True)
    exp = ref.paged_attention_ref(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(
    B=st.integers(1, 4),
    Hkv=st.sampled_from([1, 2]),
    G=st.sampled_from([1, 2, 4]),
    page=st.sampled_from([8, 16]),
    pps=st.integers(1, 5),
)
def test_paged_property_sweep(B, Hkv, G, page, pps):
    q, kp, vp, bt, cl = _paged_inputs(
        jax.random.key(3), B, Hkv * G, Hkv, 32, page, pps, jnp.float32)
    out = paged_attention(q, kp, vp, bt, cl, interpret=True)
    exp = ref.paged_attention_ref(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=5e-4, atol=5e-4)


# =========================================================================
# SSD scan
# =========================================================================

def _ssd_inputs(key, B, T, H, P, N, dtype=jnp.float32):
    kx, ka, kb, kc = jax.random.split(key, 4)
    xdt = jax.random.normal(kx, (B, H, T, P), jnp.float32).astype(dtype)
    # realistic decays: dA = -softplus(...) in (−∞, 0); keep moderate
    dA = -jax.nn.softplus(jax.random.normal(ka, (B, H, T), jnp.float32))
    Bm = jax.random.normal(kb, (B, T, N), jnp.float32).astype(dtype)
    Cm = jax.random.normal(kc, (B, T, N), jnp.float32).astype(dtype)
    return xdt, dA.astype(dtype), Bm, Cm


@pytest.mark.parametrize("B,T,H,P,N,chunk", [
    (1, 128, 2, 64, 32, 128),    # single chunk
    (2, 256, 2, 64, 32, 128),    # two chunks — exercises the recurrence
    (1, 512, 1, 32, 64, 128),    # four chunks
    (2, 64, 4, 16, 16, 32),      # small chunks
    (1, 96, 2, 32, 32, 32),      # T a non-power-of-two multiple of chunk
])
def test_ssd_vs_ref(B, T, H, P, N, chunk):
    xdt, dA, Bm, Cm = _ssd_inputs(jax.random.key(0), B, T, H, P, N)
    y, state = ssd_scan(xdt, dA, Bm, Cm, chunk=chunk, interpret=True)
    y_exp, state_exp = ref.ssd_scan_ref(xdt, dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_exp),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_exp),
                               rtol=2e-4, atol=2e-4)


def test_ssd_bf16_inputs():
    xdt, dA, Bm, Cm = _ssd_inputs(jax.random.key(1), 1, 128, 2, 32, 32,
                                  dtype=jnp.bfloat16)
    y, state = ssd_scan(xdt, dA, Bm, Cm, chunk=64, interpret=True)
    y_exp, state_exp = ref.ssd_scan_ref(xdt, dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_exp), **TOL)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_exp), **TOL)


def test_ssd_state_continuation():
    """Scanning [0:T] must equal scanning [0:T/2] then [T/2:T] with the
    carried state (the property chunked prefill of SSM archs relies on)."""
    xdt, dA, Bm, Cm = _ssd_inputs(jax.random.key(2), 1, 256, 2, 32, 32)
    y_full, s_full = ref.ssd_scan_ref(xdt, dA, Bm, Cm)
    y_a, s_a = ref.ssd_scan_ref(xdt[:, :, :128], dA[:, :, :128],
                                Bm[:, :128], Cm[:, :128])
    y_b, s_b = ref.ssd_scan_ref(xdt[:, :, 128:], dA[:, :, 128:],
                                Bm[:, 128:], Cm[:, 128:], initial_state=s_a)
    np.testing.assert_allclose(np.asarray(y_full[:, :, 128:]), np.asarray(y_b),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s_b),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    T_chunks=st.integers(1, 4),
    chunk=st.sampled_from([16, 32, 64]),
    H=st.integers(1, 3),
    P=st.sampled_from([16, 32]),
    N=st.sampled_from([16, 32]),
)
def test_ssd_property_sweep(T_chunks, chunk, H, P, N):
    T = T_chunks * chunk
    xdt, dA, Bm, Cm = _ssd_inputs(jax.random.key(9), 1, T, H, P, N)
    y, state = ssd_scan(xdt, dA, Bm, Cm, chunk=chunk, interpret=True)
    y_exp, state_exp = ref.ssd_scan_ref(xdt, dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_exp),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_exp),
                               rtol=5e-4, atol=5e-4)
