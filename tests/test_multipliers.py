"""Bit-exactness of the approximate-multiplier models (paper Sec. 2).

Unit + hypothesis property tests: the elementwise definitions, the error
identities (Eqs. 3/6/8), the partial-product-matrix oracle, the MXU bit-slice
matmul algebra, and the analytic Table 1 moments.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import multipliers as am

MODES = ["perforated", "recursive", "truncated"]
code = st.integers(0, 255)
mval = st.integers(0, 8)


@given(code, code, mval)
@settings(max_examples=300, deadline=None)
def test_perforated_definition(w, a, m):
    # AM_P = W * (A - A mod 2^m)  (Eq. 2/3 closed form)
    expected = w * (a - (a % (1 << m)))
    assert int(am.am_perforated(w, a, m)) == expected


@given(code, code, mval)
@settings(max_examples=300, deadline=None)
def test_recursive_definition(w, a, m):
    # w*a - AM_R = (w mod 2^m) * (a mod 2^m)  (Eq. 6)
    err = (w % (1 << m)) * (a % (1 << m))
    assert int(am.am_recursive(w, a, m)) == w * a - err


@given(code, code, mval)
@settings(max_examples=200, deadline=None)
def test_truncated_matches_ppmatrix(w, a, m):
    # Eq. 7/8 closed form == literal partial-product-matrix truncation
    assert int(am.am_truncated(w, a, m)) == int(am.am_truncated_ppmatrix(w, a, m))


@given(code, code, mval, st.sampled_from(MODES))
@settings(max_examples=300, deadline=None)
def test_error_identity(w, a, m, mode):
    # am + error == exact product, always
    assert int(am.am(w, a, mode, m)) + int(am.am_error(w, a, mode, m)) == w * a


@given(code, code, st.sampled_from(MODES))
@settings(max_examples=100, deadline=None)
def test_m0_is_exact(w, a, mode):
    assert int(am.am(w, a, mode, 0)) == w * a


@given(code, code, mval, st.sampled_from(MODES))
@settings(max_examples=200, deadline=None)
def test_error_nonnegative_and_bounded(w, a, m, mode):
    # all three multipliers under-approximate: 0 <= eps <= w*a
    eps = int(am.am_error(w, a, mode, m))
    assert 0 <= eps <= w * a or (w * a == 0 and eps == 0)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("m", [1, 2, 3, 5, 7])
def test_matmul_algebra_exact(mode, m):
    rng = np.random.default_rng(42)
    a = rng.integers(0, 256, (7, 33))
    w = rng.integers(0, 256, (33, 9))
    ref = np.asarray(am.approx_matmul_ref(a, w, mode, m))
    fast = np.asarray(am.approx_matmul(a, w, mode, m))
    assert np.array_equal(ref, fast)


@pytest.mark.parametrize(
    "mode,m,mu_paper,sigma_paper",
    [
        ("perforated", 1, 63.7, 82), ("perforated", 2, 191, 198),
        ("perforated", 3, 447, 425),
        ("recursive", 2, 2.24, 2.67), ("recursive", 3, 12.26, 12.51),
        ("recursive", 4, 56, 53.4), ("recursive", 5, 239, 219),
        ("truncated", 4, 12, 9.9), ("truncated", 5, 32, 23),
        ("truncated", 6, 80, 52), ("truncated", 7, 192, 115),
    ],
)
def test_table1_analytic_matches_paper(mode, m, mu_paper, sigma_paper):
    """Table 1 (uniform operands): analytic moments within 3% of the paper's
    1M-sample measurements (the paper rounds, e.g. 12.25 -> "12")."""
    mu, sigma = am.analytic_error_moments_uniform(mode, m)
    assert abs(mu - mu_paper) / max(mu_paper, 1) < 0.03
    assert abs(sigma - sigma_paper) / max(sigma_paper, 1) < 0.03


@pytest.mark.parametrize("mode,m", [("perforated", 2), ("recursive", 3), ("truncated", 5)])
def test_table1_empirical_matches_analytic(mode, m):
    rng = np.random.default_rng(0)
    w = rng.integers(0, 256, 200_000)
    a = rng.integers(0, 256, 200_000)
    mu_e, sig_e = am.empirical_error_moments(mode, m, w, a)
    mu_a, sig_a = am.analytic_error_moments_uniform(mode, m)
    assert abs(mu_e - mu_a) / max(mu_a, 1e-9) < 0.02
    assert abs(sig_e - sig_a) / max(sig_a, 1e-9) < 0.02


def test_error_mean_per_weight():
    # E_A[eps | W] tables used by the CV: verify against brute force
    for mode, m in [("perforated", 2), ("recursive", 3), ("truncated", 5)]:
        table = am.error_mean_per_weight_uniform_a(mode, m)
        a_all = np.arange(256)
        for w in (0, 1, 77, 200, 255):
            brute = np.asarray(am.am_error(w, a_all, mode, m)).mean()
            assert abs(table[w] - brute) < 1e-6, (mode, m, w)
