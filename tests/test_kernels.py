"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU; TPU target).

Per the deliverable: sweep shapes/dtypes/modes and assert_allclose against
the ref.py oracles.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.approx_linear import QuantizedDense
from repro.core.policy import paper_policies
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.quant.quantize import (PackedLinear, QuantParams,
                                  build_blocked_layout, quantized_linear)

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("mode,m", [
    ("exact", 0), ("perforated", 1), ("perforated", 3),
    ("recursive", 2), ("recursive", 4), ("truncated", 5), ("truncated", 7),
])
@pytest.mark.parametrize("shape", [(8, 32, 16), (64, 200, 48), (128, 512, 128)])
def test_approx_matmul_kernel_vs_ref(mode, m, shape):
    mm, kk, nn = shape
    a_q = RNG.integers(0, 256, (mm, kk)).astype(np.uint8)
    w_q = RNG.integers(0, 256, (kk, nn)).astype(np.uint8)
    c = RNG.normal(100, 30, (nn,)).astype(np.float32)
    c0 = RNG.normal(0, 10, (nn,)).astype(np.float32)
    sqw = np.asarray(w_q, np.int64).sum(0).astype(np.int32)
    bias = RNG.normal(0, 1, (nn,)).astype(np.float32)
    args = (a_q, w_q, c, c0, sqw, bias, 0.015, 0.02, 7.0, 131.0)
    out_k = np.asarray(ops.approx_matmul_cv_op(*args, mode=mode, m=m, interpret=True))
    out_r = np.asarray(ref.approx_matmul_cv_ref(*args, mode=mode, m=m))
    np.testing.assert_allclose(out_k, out_r, rtol=2e-5, atol=2e-3)


@pytest.mark.parametrize("use_cv", [True, False])
def test_approx_matmul_kernel_cv_flag(use_cv):
    a_q = RNG.integers(0, 256, (16, 64)).astype(np.uint8)
    w_q = RNG.integers(0, 256, (64, 16)).astype(np.uint8)
    c = RNG.normal(50, 10, (16,)).astype(np.float32)
    c0 = np.zeros(16, np.float32)
    sqw = np.asarray(w_q, np.int64).sum(0).astype(np.int32)
    bias = np.zeros(16, np.float32)
    args = (a_q, w_q, c, c0, sqw, bias, 0.01, 0.01, 0.0, 0.0)
    k = np.asarray(ops.approx_matmul_cv_op(*args, mode="perforated", m=2,
                                           use_cv=use_cv, interpret=True))
    r = np.asarray(ref.approx_matmul_cv_ref(*args, mode="perforated", m=2,
                                            use_cv=use_cv))
    np.testing.assert_allclose(k, r, rtol=1e-5, atol=1e-4)


def test_approx_matmul_batched_leading_dims():
    a_q = RNG.integers(0, 256, (3, 5, 40)).astype(np.uint8)
    w_q = RNG.integers(0, 256, (40, 24)).astype(np.uint8)
    c = RNG.normal(0, 5, (24,)).astype(np.float32)
    c0 = np.zeros(24, np.float32)
    sqw = np.asarray(w_q, np.int64).sum(0).astype(np.int32)
    bias = np.zeros(24, np.float32)
    args = (a_q.reshape(-1, 40), w_q, c, c0, sqw, bias, 0.01, 0.02, 1.0, 2.0)
    flat = np.asarray(ref.approx_matmul_cv_ref(*args, mode="recursive", m=3))
    out = np.asarray(ops.approx_matmul_cv_op(
        a_q, w_q, c, c0, sqw, bias, 0.01, 0.02, 1.0, 2.0,
        mode="recursive", m=3, interpret=True))
    np.testing.assert_allclose(out.reshape(-1, 24), flat, rtol=1e-5, atol=1e-3)


def _transparent_dense(k: int, n: int, policy) -> QuantizedDense:
    """A pack whose float epilogue is exact: scales 1, integer zero points
    (za = 128, zw = 7), CV constants C = 1 and C0 = 0.  Its output is the
    integer bracket  acc - zw*sumqa - za*sum_qw + k*za*zw  (plus sum_x with
    CV), so any slip in the int8 operand shift, its fold-back or the
    zero-point terms shows as a mismatch, not as float noise."""
    w_q = jnp.asarray(RNG.integers(0, 256, (k, n)), jnp.uint8)
    pack = PackedLinear(
        w_q=w_q, w_scale=jnp.float32(1.0), w_zp=jnp.int32(7),
        sum_qw=jnp.sum(w_q.astype(jnp.int32), axis=0),
        c=jnp.ones((n,), jnp.float32), c0=jnp.zeros((n,), jnp.float32),
        bias=None)
    a_qp = QuantParams(jnp.float32(1.0), jnp.int32(128))
    return QuantizedDense(pack=pack, a_qp=a_qp, policy=policy,
                          blocked=build_blocked_layout(pack, a_qp))


@pytest.mark.parametrize("use_cv", [True, False])
@pytest.mark.parametrize("policy", paper_policies(backend="pallas"),
                         ids=lambda p: f"{p.mode}{p.m}")
@pytest.mark.parametrize("k,rows", [(300, 5), (1024, 130)])
def test_int8_operand_kernels_bit_exact(policy, use_cv, k, rows):
    """Both CV kernels, fed int8 operands, reproduce quantized_linear's
    integer result bit for bit across the paper grid at fan-ins above 258
    (K padding at 300, several K tiles at 1024), on decode- and
    prefill-shaped rows, with activation codes over the whole 0..255
    range (both int8 extremes after the shift) and nonzero zero points."""
    policy = dataclasses.replace(policy, use_cv=use_cv)
    qd = _transparent_dense(k, 136, policy)
    codes = RNG.integers(0, 256, (rows, k))
    codes[0, :2] = (0, 255)
    x = jnp.asarray(codes - 128, jnp.float32)
    want = np.asarray(quantized_linear(x, qd.pack, qd.a_qp, policy.mode,
                                       policy.m, use_cv=use_cv))
    fused = ops.quantized_dense_fused_op(x, qd.blocked, mode=policy.mode,
                                         m=policy.m, use_cv=use_cv,
                                         interpret=True)
    plain = ops.quantized_dense_pallas(x, dataclasses.replace(qd,
                                                              blocked=None))
    np.testing.assert_array_equal(np.asarray(fused), want)
    np.testing.assert_array_equal(np.asarray(plain), want)


@pytest.mark.parametrize("t,dk,dv", [(64, 64, 64), (96, 32, 32)])
def test_rwkv6_scan_vs_sequential(t, dk, dv):
    b, h = 2, 2
    r = RNG.normal(0, 1, (b, t, h, dk)).astype(np.float32)
    k = RNG.normal(0, 1, (b, t, h, dk)).astype(np.float32)
    v = RNG.normal(0, 1, (b, t, h, dv)).astype(np.float32)
    w = np.clip(np.exp(-np.exp(RNG.normal(-1, 1.5, (b, t, h, dk)))),
                np.exp(-8.0), 0.9999).astype(np.float32)
    u = RNG.normal(0, 0.5, (h, dk)).astype(np.float32)
    out_k = np.asarray(rwkv6_scan(r, k, v, w, u, chunk=32, interpret=True))
    out_r, _ = ref.rwkv6_scan_ref(
        jnp.asarray(r), jnp.asarray(k), jnp.asarray(v), jnp.asarray(w),
        jnp.asarray(u), jnp.zeros((b, h, dk, dv)))
    np.testing.assert_allclose(out_k, np.asarray(out_r), rtol=2e-4, atol=5e-4)


@pytest.mark.parametrize("causal,window,hq,hkv", [
    (True, None, 4, 4), (True, None, 8, 2), (False, None, 4, 4),
    (True, 64, 4, 2),
])
def test_flash_attention_vs_ref(causal, window, hq, hkv):
    b, t, d = 2, 128, 32
    q = RNG.normal(0, 1, (b, hq, t, d)).astype(np.float32)
    k = RNG.normal(0, 1, (b, hkv, t, d)).astype(np.float32)
    v = RNG.normal(0, 1, (b, hkv, t, d)).astype(np.float32)
    out_k = np.asarray(flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=causal, window=window, bq=64, bk=64, interpret=True))
    out_r = np.asarray(ref.flash_attention_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=causal, window=window))
    np.testing.assert_allclose(out_k, out_r, rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_shape():
    # tq < tk (chunked decode): rows aligned to the end of the kv axis
    b, hq, hkv, tq, tk, d = 1, 4, 2, 64, 256, 64
    q = RNG.normal(0, 1, (b, hq, tq, d)).astype(np.float32)
    k = RNG.normal(0, 1, (b, hkv, tk, d)).astype(np.float32)
    v = RNG.normal(0, 1, (b, hkv, tk, d)).astype(np.float32)
    out_k = np.asarray(flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                       causal=True, bq=64, bk=64, interpret=True))
    out_r = np.asarray(ref.flash_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                               jnp.asarray(v), causal=True))
    np.testing.assert_allclose(out_k, out_r, rtol=2e-5, atol=2e-5)
