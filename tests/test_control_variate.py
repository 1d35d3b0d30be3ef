"""The paper's central claims at convolution level (Sec. 3, Eqs. 12-32).

* without CV: error mean/std follow Eq. 12 (k*mu, sqrt(k)*sigma);
* with CV: mean is nullified (Eqs. 22/28) and variance shrinks;
* C = E[W] is the variance-minimizing constant (Eq. 21's argmin);
* Eq. 20 predicts the with-CV variance for perforated/recursive;
* grouped CV (beyond paper) only improves on the paper's single group.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import control_variate as cv
from repro.core import multipliers as am

MODES = ["perforated", "recursive", "truncated"]


def _conv_errors(mode, m, k, n_trials, seed=0, use_cv=True, groups=1, c_override=None):
    """Empirical distribution of the convolution error over random uniform
    activations, for ONE fixed random weight vector."""
    rng = np.random.default_rng(seed)
    w = rng.integers(0, 256, (k, 1))
    a = rng.integers(0, 256, (n_trials, k))
    exact = a.astype(np.int64) @ w.astype(np.int64)
    acc = np.asarray(am.approx_matmul(a, w, mode, m)).astype(np.float64)
    if use_cv:
        if c_override is not None:
            const = cv.CVConstants(
                c=np.asarray([c_override], np.float32), c0=np.zeros(1, np.float32))
        elif groups == 1:
            const = cv.cv_constants(w, mode, m)
        else:
            const = cv.cv_constants_grouped(w, mode, m, groups)
        if groups == 1:
            v = np.asarray(cv.cv_term(a, const, mode, m))
        else:
            v = np.asarray(cv.cv_term_grouped(a, const, mode, m, groups))
        acc = acc + v
    return (exact[:, 0] - acc[:, 0]), w


@pytest.mark.parametrize("mode,m", [("perforated", 2), ("recursive", 3), ("truncated", 6)])
def test_no_cv_error_follows_eq12(mode, m):
    """Eq. 12 (k*mu, sqrt(k)*sigma) holds when BOTH operands are random —
    the i.i.d. setting of the paper's derivation."""
    k, n = 256, 4000
    rng = np.random.default_rng(11)
    w = rng.integers(0, 256, (n, k))
    a = rng.integers(0, 256, (n, k))
    errs = np.asarray(am.am_error(w, a, mode, m)).sum(axis=1).astype(np.float64)
    mu_pred, sig_pred = cv.predicted_conv_error_no_cv_uniform(mode, m, k)
    assert abs(errs.mean() - mu_pred) < 5 * sig_pred / np.sqrt(n) + 1e-9
    assert abs(errs.std() - sig_pred) / sig_pred < 0.10


@pytest.mark.parametrize("mode,m", [("perforated", 1), ("perforated", 3),
                                    ("recursive", 3), ("truncated", 5),
                                    ("truncated", 7)])
def test_cv_nullifies_mean(mode, m):
    """Eqs. 22/28: with the paper's (C, C0) the mean convolution error is 0."""
    k, n = 256, 8000
    errs, _ = _conv_errors(mode, m, k, n, use_cv=True)
    se = errs.std() / np.sqrt(n)
    assert abs(errs.mean()) < 5 * se + 1e-9, (errs.mean(), se)


@pytest.mark.parametrize("mode,m", [("perforated", 2), ("recursive", 4)])
def test_cv_reduces_variance(mode, m):
    """Perforated/recursive: V is proportional to the error -> variance drops
    (Eq. 20 vs Eq. 12)."""
    k, n = 256, 4000
    e_cv, _ = _conv_errors(mode, m, k, n, use_cv=True)
    e_no, _ = _conv_errors(mode, m, k, n, use_cv=False)
    assert e_cv.std() < 0.7 * e_no.std(), (e_cv.std(), e_no.std())


@pytest.mark.parametrize("mode,m", [("perforated", 2), ("recursive", 4), ("truncated", 6)])
def test_cv_reduces_rms(mode, m):
    """All three multipliers: total RMS error (bias included — what accuracy
    actually sees) collapses with the CV.  For the truncated multiplier the
    win is mostly the nullified mean (Sec. 3.2), so RMS is the right metric."""
    k, n = 256, 4000
    e_cv, _ = _conv_errors(mode, m, k, n, use_cv=True)
    e_no, _ = _conv_errors(mode, m, k, n, use_cv=False)
    rms = lambda e: np.sqrt((e**2).mean())
    assert rms(e_cv) < 0.25 * rms(e_no), (rms(e_cv), rms(e_no))


def test_c_is_variance_argmin_perforated():
    """Eq. 21: C = E[W] minimizes Var(eps_G*) — perturbing C is never better."""
    mode, m, k, n = "perforated", 2, 128, 6000
    rng = np.random.default_rng(3)
    w = rng.integers(0, 256, (k, 1))
    c_star = float(w.mean())
    best, _ = _conv_errors(mode, m, k, n, seed=3, c_override=c_star)
    for delta in (-30, -10, 10, 30):
        worse, _ = _conv_errors(mode, m, k, n, seed=3, c_override=c_star + delta)
        assert worse.var() >= best.var() * 0.999, delta


def test_eq20_variance_prediction():
    """Eq. 20 evaluated at C = E[W] predicts the empirical variance."""
    mode, m, k, n = "perforated", 2, 128, 20000
    rng = np.random.default_rng(5)
    w = rng.integers(0, 256, (k, 1))
    errs, _ = _conv_errors(mode, m, k, n, seed=5)
    pred = cv.predicted_var_with_cv_perforated(w[:, 0], m)
    assert abs(errs.var() - pred) / pred < 0.1


def test_grouped_cv_improves():
    """Beyond paper: per-group constants reduce variance further (or tie)."""
    mode, m, k, n = "perforated", 3, 256, 6000
    e1, _ = _conv_errors(mode, m, k, n, groups=1)
    e4, _ = _conv_errors(mode, m, k, n, groups=4)
    e16, _ = _conv_errors(mode, m, k, n, groups=16)
    assert e4.var() <= e1.var() * 1.02
    assert e16.var() <= e4.var() * 1.02


@given(st.integers(0, 2**32 - 1), st.sampled_from(MODES), st.integers(1, 7))
@settings(max_examples=25, deadline=None)
def test_cv_term_matches_manual(seed, mode, m):
    """V == C * sum(x_j) + C0 for random inputs (structure property)."""
    rng = np.random.default_rng(seed)
    k = 32
    w = rng.integers(0, 256, (k, 3))
    a = rng.integers(0, 256, (5, k))
    const = cv.cv_constants(w, mode, m)
    v = np.asarray(cv.cv_term(a, const, mode, m))
    sx = np.asarray(cv.sum_x(a, mode, m))
    manual = sx[:, None] * np.asarray(const.c)[None, :] + np.asarray(const.c0)[None, :]
    assert np.allclose(v, manual, rtol=1e-6, atol=1e-4)
