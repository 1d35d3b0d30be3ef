"""Quantization substrate + approximate quantized linear behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import quant
from repro.core.approx_linear import QuantizedDense, dense, pack_dense, pack_params
from repro.core.policy import ApproxPolicy


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (64, 64)).astype(np.float32)
    qp = quant.calibrate_tensor(jnp.asarray(x))
    x2 = np.asarray(quant.dequantize(quant.quantize(jnp.asarray(x), qp), qp))
    step = float(np.asarray(qp.scale))
    assert np.abs(x - x2).max() <= step * 0.501 + 1e-7


@given(st.floats(-100, 0, allow_nan=False), st.floats(0, 100, allow_nan=False))
@settings(max_examples=50, deadline=None)
def test_calibration_contains_zero(lo, hi):
    qp = quant.calibrate_minmax(lo, hi)
    zero = np.asarray(quant.dequantize(quant.quantize(jnp.zeros(()), qp), qp))
    assert abs(float(zero)) <= float(np.asarray(qp.scale)) * 0.5 + 1e-7


def test_exact_int8_linear_close_to_float():
    rng = np.random.default_rng(1)
    k, n = 128, 32
    w = rng.normal(0, 0.1, (k, n)).astype(np.float32)
    x = rng.normal(0, 0.8, (16, k)).astype(np.float32)
    pack = quant.pack_linear(jnp.asarray(w), None, "exact", 0)
    aqp = quant.calibrate_tensor(jnp.asarray(x))
    y = np.asarray(quant.quantized_linear(jnp.asarray(x), pack, aqp, "exact", 0))
    ref = x @ w
    rel = np.abs(y - ref).max() / np.abs(ref).max()
    assert rel < 0.03, rel


@pytest.mark.parametrize("mode,m", [("perforated", 2), ("recursive", 3), ("truncated", 6)])
def test_cv_beats_no_cv_at_layer_level(mode, m):
    """The paper's claim, at one linear layer: adding V cuts the error."""
    rng = np.random.default_rng(2)
    k, n = 256, 64
    w = rng.normal(0, 0.05, (k, n)).astype(np.float32)
    x = rng.normal(0.3, 0.5, (32, k)).astype(np.float32)
    ref = x @ w
    pack = quant.pack_linear(jnp.asarray(w), None, mode, m)
    aqp = quant.calibrate_tensor(jnp.asarray(x))
    y_cv = np.asarray(quant.quantized_linear(jnp.asarray(x), pack, aqp, mode, m, use_cv=True))
    y_no = np.asarray(quant.quantized_linear(jnp.asarray(x), pack, aqp, mode, m, use_cv=False))
    err_cv = np.abs(y_cv - ref).mean()
    err_no = np.abs(y_no - ref).mean()
    assert err_cv < 0.5 * err_no, (err_cv, err_no)


def test_pack_params_walks_tree_and_skips():
    from repro.numerics import Rule, apply_numerics, uniform_spec

    params = {
        "blocks": {"attn": {"q": {"w": jnp.ones((8, 8))}},
                   "norm": {"scale": jnp.ones(8)}},
        "router": {"w": jnp.ones((8, 4))},
    }
    spec = uniform_spec(ApproxPolicy("perforated", 2), rules=(Rule("router"),))
    packed = apply_numerics(params, spec.resolve(params))
    assert isinstance(packed["blocks"]["attn"]["q"], QuantizedDense)
    assert isinstance(packed["router"], dict)  # kept float by the rule
    assert "scale" in packed["blocks"]["norm"]


def test_stacked_pack_scan_sliceable():
    """(L, k, n) stacked linears pack to per-layer constants that lax.scan
    can slice (per-layer quant scales + CV constants)."""
    import jax

    L, k, n = 3, 16, 8
    w = jnp.asarray(np.random.default_rng(0).normal(0, 0.1, (L, k, n)), jnp.float32)
    qd = pack_dense({"w": w}, ApproxPolicy("perforated", 2), (-4.0, 4.0))
    assert qd.pack.w_q.shape == (L, k, n)
    assert qd.pack.c.shape == (L, n)
    assert qd.a_qp.scale.shape == (L,)

    x = jnp.ones((2, k))

    def body(carry, qd_l):
        return carry + dense(qd_l, x).sum(), None

    total, _ = jax.lax.scan(body, jnp.float32(0), qd)
    assert np.isfinite(float(total))


def test_grouped_cv_policy_path():
    rng = np.random.default_rng(4)
    w = rng.normal(0, 0.1, (64, 16)).astype(np.float32)
    x = rng.normal(0, 0.5, (8, 64)).astype(np.float32)
    qd = pack_dense({"w": jnp.asarray(w)}, ApproxPolicy("perforated", 3, groups=4),
                    (float(x.min()), float(x.max())))
    y = np.asarray(dense(qd, jnp.asarray(x)))
    ref = x @ w
    assert np.abs(y - ref).mean() < 0.05 * np.abs(ref).mean() + 0.05
