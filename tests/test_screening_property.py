"""Hypothesis property tests for the system's core invariant:

    SAFETY — a safe screening rule never discards a feature that is active
    in the exact solution (paper's definition of "safe", §1).

plus the geometric invariants the EDPP construction rests on.
"""

import numpy as np
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis package")
from hypothesis import example, given, settings, strategies as st  # noqa: E402

from repro.core import (DualState, dpp_mask, edpp_mask, imp1_mask, imp2_mask,
                        lambda_max, make_dual_state, v2_perp)

from ref_lasso import cd_lasso

problem = st.tuples(
    st.integers(min_value=8, max_value=24),     # n
    st.integers(min_value=10, max_value=60),    # p
    st.integers(min_value=0, max_value=2**31 - 1),
    st.floats(min_value=0.05, max_value=0.95),  # λ/λmax
    st.floats(min_value=0.0, max_value=0.6),    # column correlation
)


def _make(n, p, seed, corr):
    rng = np.random.default_rng(seed)
    if corr > 0:
        base = rng.standard_normal((n, p))
        X = np.empty((n, p))
        X[:, 0] = base[:, 0]
        a = np.sqrt(1 - corr * corr)
        for j in range(1, p):
            X[:, j] = corr * X[:, j - 1] + a * base[:, j]
    else:
        X = rng.standard_normal((n, p))
    nnz = max(1, p // 10)
    beta = np.zeros(p)
    beta[rng.choice(p, nnz, replace=False)] = rng.uniform(-1, 1, nnz)
    y = X @ beta + 0.1 * rng.standard_normal(n)
    if np.linalg.norm(y) < 1e-9:
        y = y + 1.0
    return X, y


@settings(max_examples=25, deadline=None, derandomize=True)
@given(problem)
def test_safety_from_lambda_max(args):
    n, p, seed, frac, corr = args
    X, y = _make(n, p, seed, corr)
    Xf, yf = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)
    lmax = float(lambda_max(Xf, yf))
    lam = frac * lmax
    oracle = cd_lasso(X, y, lam)
    active = np.abs(oracle) > 1e-9
    state = DualState.at_lambda_max(Xf, yf)
    for fn in (dpp_mask, imp1_mask, imp2_mask, edpp_mask):
        mask = np.asarray(fn(Xf, yf, lam, state))
        assert not np.any(mask & active), fn.__name__


@settings(max_examples=15, deadline=None, derandomize=True)
@given(problem)
def test_safety_sequential(args):
    n, p, seed, frac, corr = args
    X, y = _make(n, p, seed, corr)
    Xf, yf = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)
    lmax = float(lambda_max(Xf, yf))
    lam0 = (0.5 + 0.5 * frac) * lmax          # λ0 ∈ (λ, λmax)
    lam1 = frac * lmax * 0.9
    beta0 = cd_lasso(X, y, lam0)
    oracle = cd_lasso(X, y, lam1)
    active = np.abs(oracle) > 1e-9
    state = make_dual_state(Xf, yf, jnp.asarray(beta0, jnp.float32),
                            lam0, lmax)
    mask = np.asarray(edpp_mask(Xf, yf, lam1, state))
    assert not np.any(mask & active)


@settings(max_examples=30, deadline=None, derandomize=True)
@given(problem)
def test_radius_hierarchy(args):
    """‖v₂⊥‖ ≤ ‖v₂‖ and EDPP's radius = ½‖v₂⊥‖ ≤ DPP's |1/λ−1/λ₀|‖y‖."""
    n, p, seed, frac, corr = args
    X, y = _make(n, p, seed, corr)
    Xf, yf = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)
    lmax = float(lambda_max(Xf, yf))
    lam = frac * lmax
    state = DualState.at_lambda_max(Xf, yf)
    vp = np.asarray(v2_perp(yf, lam, state))
    v2 = np.asarray(yf / lam - state.theta)
    assert np.linalg.norm(vp) <= np.linalg.norm(v2) + 1e-4
    dpp_r = (1 / lam - 1 / lmax) * float(np.linalg.norm(y))
    assert 0.5 * np.linalg.norm(vp) <= dpp_r + 1e-4


@settings(max_examples=30, deadline=None, derandomize=True)
@given(problem)
@example((8, 53, 8, 0.0546875, 0.0))   # support of size n: CD alone stalls
def test_dual_point_feasible(args):
    """θ*(λ) estimated from an exact solve is feasible: ‖Xᵀθ‖∞ ≤ 1+ε."""
    n, p, seed, frac, corr = args
    X, y = _make(n, p, seed, corr)
    lmax = float(np.abs(X.T @ y).max())
    lam = frac * lmax
    beta = cd_lasso(X, y, lam)
    theta = (y - X @ beta) / lam
    assert np.abs(X.T @ theta).max() <= 1.0 + 1e-5
