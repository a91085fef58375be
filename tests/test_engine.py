"""ScreeningEngine correctness: engine masks must be IDENTICAL to the
pure-jnp oracle masks of repro.core.screening, for every rule and every
backend (jnp reference + Pallas interpret), on states built both at λ_max
and from exact sequential solutions (tests/ref_lasso.py oracles). The
engines hold a batch of queries; one query is a batch of one, (1, n), and
its row 0 is compared with the single-query oracle."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (DualState, GroupScreeningEngine, PathConfig,
                        PathWorkspace, RULES, ScreeningEngine, available_backends,
                        dome_mask, engine_x_passes, group_lambda_max,
                        group_screen, group_spectral_norms,
                        group_state_at_lambda_max, lambda_max, lasso_path,
                        lambda_grid, make_dual_state, make_sphere,
                        oracle_x_passes, safe_mask, sphere_mask)

from conftest import small_problem
from ref_lasso import cd_lasso

BACKENDS = ["jnp", "interpret"]
ALL_RULES = list(RULES) + ["safe", "dome"]


def _problem(seed=0, n=40, p=150):
    X, y, _ = small_problem(None, n=n, p=p, seed=seed)
    return jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32), X, y


def _lam(lam):
    """One query's λ as the engines take it: per-query λ (1,)."""
    return jnp.asarray([lam], jnp.float32)


# ---------------------------------------------------------------------------
# workspace caching
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_workspace_caches_path_geometry(backend):
    Xf, yf, _, _ = _problem()
    ws = PathWorkspace(Xf, yf[None], backend=backend)
    np.testing.assert_allclose(np.asarray(ws.col_norms),
                               np.asarray(jnp.linalg.norm(Xf, axis=0)),
                               rtol=2e-5)
    # atol for near-zero correlations: f32 summation order differs per backend
    np.testing.assert_allclose(np.asarray(ws.abs_xty)[0],
                               np.asarray(jnp.abs(Xf.T @ yf)),
                               rtol=2e-5, atol=1e-4)
    lmax = ws.lam_max[0]
    assert abs(lmax - float(lambda_max(Xf, yf))) < 1e-4 * lmax
    st = ws.state_at_lambda_max()
    st_ref = DualState.at_lambda_max(Xf, yf)
    np.testing.assert_allclose(np.asarray(st.theta)[0],
                               np.asarray(st_ref.theta), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(st.v1)[0],
                                  np.asarray(st_ref.v1))


# ---------------------------------------------------------------------------
# engine mask == pure-jnp oracle mask, all rules × backends × states
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("rule", ALL_RULES)
def test_engine_matches_oracle_from_lmax(rule, backend):
    Xf, yf, _, _ = _problem(seed=1)
    eng = ScreeningEngine(Xf, yf[None], backend=backend)
    lmax = float(eng.lam_max[0])
    state = eng.state_at_lambda_max()
    state_ref = DualState.at_lambda_max(Xf, yf)
    for frac in (0.9, 0.5, 0.15):
        lam = frac * lmax
        got = np.asarray(eng.screen(_lam(lam), state, rule))[0]
        if rule == "safe":
            want = safe_mask(Xf, yf, lam, lmax)
        elif rule == "dome":
            want = dome_mask(Xf, yf, lam, lmax)
        else:
            want = RULES[rule](Xf, yf, lam, state_ref)
        np.testing.assert_array_equal(got, np.asarray(want), err_msg=rule)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("rule", list(RULES))
def test_engine_matches_oracle_sequential(rule, backend):
    """Sequential states from exact float64 solves (ref_lasso oracle)."""
    Xf, yf, X, y = _problem(seed=2)
    eng = ScreeningEngine(Xf, yf[None], backend=backend)
    lmax = float(eng.lam_max[0])
    for frac0, frac1 in [(0.7, 0.5), (0.4, 0.2)]:
        beta0 = jnp.asarray(cd_lasso(X, y, frac0 * lmax), jnp.float32)
        state = eng.make_state(beta0[None], _lam(frac0 * lmax))
        state_ref = make_dual_state(Xf, yf, beta0, frac0 * lmax, lmax)
        got = np.asarray(eng.screen(_lam(frac1 * lmax), state, rule))[0]
        want = np.asarray(RULES[rule](Xf, yf, frac1 * lmax, state_ref))
        np.testing.assert_array_equal(got, want, err_msg=rule)


@pytest.mark.parametrize("backend", BACKENDS)
def test_sphere_constructors_match_masks(backend):
    """sphere_mask(X, <rule>_sphere(...)) == <rule>_mask(...) for the whole
    ball family — the geometry refactor is lossless."""
    Xf, yf, X, y = _problem(seed=3)
    lmax = float(lambda_max(Xf, yf))
    beta0 = jnp.asarray(cd_lasso(X, y, 0.6 * lmax), jnp.float32)
    state = make_dual_state(Xf, yf, beta0, 0.6 * lmax, lmax)
    lam = 0.4 * lmax
    for rule in ("dpp", "imp1", "imp2", "edpp", "seq_safe"):
        test = make_sphere(rule, yf, lam, state)
        np.testing.assert_array_equal(
            np.asarray(sphere_mask(Xf, test)),
            np.asarray(RULES[rule](Xf, yf, lam, state)), err_msg=rule)


# ---------------------------------------------------------------------------
# gap rule: safe + fires
# ---------------------------------------------------------------------------

def test_gap_rule_safety_and_discards():
    Xf, yf, X, y = _problem(seed=4, p=200)
    eng = ScreeningEngine(Xf, yf[None])
    lmax = float(eng.lam_max[0])
    beta0 = jnp.asarray(cd_lasso(X, y, 0.5 * lmax), jnp.float32)
    state = eng.make_state(beta0[None], _lam(0.5 * lmax))
    lam = 0.4 * lmax
    oracle = cd_lasso(X, y, lam)
    active = np.abs(oracle) > 1e-10
    mask = np.asarray(eng.screen(_lam(lam), state, "gap"))[0]
    assert not np.any(mask & active), "gap discarded an active feature"
    assert mask.sum() > 0, "gap should fire near the previous grid point"


# ---------------------------------------------------------------------------
# full path through the engine: masks identical for every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule", ["edpp", "gap", "strong", "dome"])
def test_path_masks_identical_across_backends(rule):
    Xf, yf, X, y = _problem(seed=5, n=30, p=120)
    grid = lambda_grid(float(lambda_max(Xf, yf)), num=8)
    runs = {
        b: lasso_path(X, y, grid,
                      PathConfig(rule=rule, solver_tol=1e-10, backend=b))
        for b in BACKENDS
    }
    ref, res = runs["jnp"], runs["interpret"]
    np.testing.assert_allclose(res.betas, ref.betas, atol=5e-5)
    for s_ref, s_res in zip(ref.stats, res.stats):
        assert s_ref.n_discarded == s_res.n_discarded
        assert s_ref.n_kept == s_res.n_kept


# ---------------------------------------------------------------------------
# data-movement accounting: 1 fused pass vs ≥2 in the hand-rolled jnp masks
# ---------------------------------------------------------------------------

def test_engine_single_pass_accounting():
    Xf, yf, X, y = _problem(seed=6)
    grid = lambda_grid(float(lambda_max(Xf, yf)), num=6)
    res = lasso_path(X, y, grid, PathConfig(rule="edpp"))
    screened = [s for s in res.stats if s.screen_time_s > 0]
    assert screened and all(s.x_passes == 1 for s in screened)
    assert engine_x_passes("edpp") == 1 < oracle_x_passes("edpp") == 2
    assert engine_x_passes("dome") == 2 < oracle_x_passes("dome") == 4


def test_unknown_backend_raises():
    Xf, yf, _, _ = _problem(seed=7)
    with pytest.raises(ValueError, match="unknown screening backend"):
        ScreeningEngine(Xf, yf[None], backend="mosaic-gpu")


# ---------------------------------------------------------------------------
# group engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("rule", ["edpp", "strong"])
def test_group_engine_matches_oracle(rule, backend):
    rng = np.random.default_rng(8)
    n, p, m = 30, 120, 4
    X = rng.standard_normal((n, p)).astype(np.float32)
    y = (X[:, :8] @ rng.uniform(-1, 1, 8)
         + 0.1 * rng.standard_normal(n)).astype(np.float32)
    Xf, yf = jnp.asarray(X), jnp.asarray(y)
    eng = GroupScreeningEngine(Xf, yf[None], m, backend=backend)
    lmax = float(eng.lam_max[0])
    assert abs(lmax - float(group_lambda_max(Xf, yf, m))) < 1e-5
    state = eng.state_at_lambda_max()
    state_ref = group_state_at_lambda_max(Xf, yf, m)
    sn = group_spectral_norms(Xf, m)
    for frac in (0.8, 0.4):
        lam = frac * lmax
        got = np.asarray(eng.screen(_lam(lam), state, rule))[0]
        want = np.asarray(group_screen(Xf, yf, lam, state_ref, m,
                                       rule=rule, spec_norms=sn))
        np.testing.assert_array_equal(got, want, err_msg=rule)
