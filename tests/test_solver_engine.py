"""SolverEngine correctness (tests/test_engine.py's twin for solvers):
strategy × backend results must match the certified float64 oracles
(tests/ref_lasso.py) to solver tolerance, lasso paths must agree across
solver backends, the Gram-CD crossover must fire where advertised, warm
starts must be a no-op at tight tolerance across bucket transitions, and
the gap-check cadence must be counted in PathStepStats.gap_checks. The
engine holds a batch of queries; one query is a batch of one, (1, n)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (GroupPathConfig, PathConfig, SOLVERS, SolverEngine,
                        available_solvers, cd, fista, group_fista,
                        group_lasso_path, group_lambda_max, lambda_grid,
                        lambda_max, lasso_path, power_iteration,
                        register_solver, top_eigenpair)

from conftest import small_problem
from ref_lasso import cd_lasso, fista_group

BACKENDS = ["jnp", "interpret"]


def _problem(seed=0, n=30, p=80):
    X, y, _ = small_problem(None, n=n, p=p, seed=seed)
    return jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32), X, y


def _lam(lam):
    """One query's λ as the engine takes it: per-query λ (1,)."""
    return jnp.asarray([lam], jnp.float32)


# ---------------------------------------------------------------------------
# engine solve == float64 oracle, strategies × backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("solver", ["fista", "cd"])
def test_engine_matches_oracle(backend, solver):
    Xf, yf, X, y = _problem(seed=1)
    tol = 1e-9 if solver == "fista" else 1e-11
    eng = SolverEngine(yf[None], solver=solver, backend=backend, tol=tol,
                       max_iter=20000)
    assert eng.backend_name == backend
    for frac in (0.8, 0.5, 0.2):
        lam = frac * float(lambda_max(Xf, yf))
        res = eng.solve(Xf, _lam(lam))
        oracle = cd_lasso(X, y, lam)
        np.testing.assert_allclose(np.asarray(res.beta)[0], oracle,
                                   rtol=2e-3, atol=2e-4)
        assert float(res.gap[0]) >= -1e-5
        assert int(res.gap_checks) >= 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_group_engine_matches_oracle(backend):
    rng = np.random.default_rng(2)
    n, p, m = 30, 80, 4
    X = rng.standard_normal((n, p))
    y = X[:, :8] @ rng.uniform(-1, 1, 8) + 0.1 * rng.standard_normal(n)
    Xf, yf = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)
    eng = SolverEngine(yf[None], solver="group_fista", backend=backend,
                       tol=1e-9, max_iter=20000)
    lam = 0.4 * float(group_lambda_max(Xf, yf, m))
    res = eng.solve(Xf, _lam(lam), m=m)
    oracle = fista_group(X, y, lam, m)
    np.testing.assert_allclose(np.asarray(res.beta)[0], oracle,
                               rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------------------
# Gram-vs-matvec CD crossover
# ---------------------------------------------------------------------------

def test_cd_gram_crossover(rng):
    Xf, yf, X, y = _problem(seed=3, n=40, p=120)
    eng = SolverEngine(yf[None], solver="cd", backend="jnp", tol=1e-11,
                       max_iter=20000)
    lam = 0.5 * float(lambda_max(Xf, yf))
    res_wide = eng.solve(Xf, _lam(lam))           # bucket 120 > n 40: matvec
    assert not eng.last_used_gram
    res_narrow = eng.solve(Xf[:, :32], _lam(lam))  # bucket 32 ≤ n 40: Gram
    assert eng.last_used_gram
    # the two regimes agree where they overlap
    oracle = cd_lasso(X[:, :32], y, lam)
    np.testing.assert_allclose(np.asarray(res_narrow.beta)[0], oracle,
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(res_wide.beta)[0, :32],
                               np.asarray(cd_lasso(X, y, lam))[:32],
                               rtol=2e-3, atol=2e-4)
    assert eng.gram_solves == 1 and eng.n_solves == 2


# ---------------------------------------------------------------------------
# full paths: betas identical across solver backends, lasso + group
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("solver", ["fista", "cd"])
def test_path_parity_across_solver_backends(solver):
    Xf, yf, X, y = _problem(seed=4, n=30, p=120)
    grid = lambda_grid(float(lambda_max(Xf, yf)), num=8)
    runs = {
        b: lasso_path(X, y, grid,
                      PathConfig(rule="edpp", solver=solver,
                                 solver_tol=1e-10, solver_backend=b))
        for b in BACKENDS
    }
    ref, res = runs["jnp"], runs["interpret"]
    np.testing.assert_allclose(res.betas, ref.betas, atol=5e-5)
    for s_ref, s_res in zip(ref.stats, res.stats):
        assert s_ref.n_kept == s_res.n_kept
        if s_res.bucket:                     # trivial λ ≥ λmax steps: no solve
            assert s_res.solver_backend == "interpret"
            assert s_ref.solver_backend == "jnp"


def test_group_path_parity_across_solver_backends():
    rng = np.random.default_rng(5)
    n, p, m = 30, 120, 4
    X = rng.standard_normal((n, p))
    y = X[:, :8] @ rng.uniform(-1, 1, 8) + 0.1 * rng.standard_normal(n)
    grid = lambda_grid(float(group_lambda_max(jnp.asarray(X, jnp.float32),
                                              jnp.asarray(y, jnp.float32),
                                              m)), num=6)
    runs = {
        b: group_lasso_path(X, y, m, grid,
                            GroupPathConfig(rule="edpp", solver_tol=1e-9,
                                            solver_backend=b))
        for b in BACKENDS
    }
    np.testing.assert_allclose(runs["interpret"].betas, runs["jnp"].betas,
                               atol=5e-5)


# ---------------------------------------------------------------------------
# warm-start property across bucket transitions (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("solver", ["fista", "cd"])
@pytest.mark.parametrize("seed", [6, 7, 8])
def test_warm_start_noop_across_bucket_change(solver, seed):
    """Warm starting only moves the start point: at tight tol, path
    solutions (warm-started, bucket-gathered) match independent cold-start
    full-problem solves to solver precision — including right after a
    bucket-size change, where the warm β is scatter/gathered between
    buffers of different widths."""
    Xf, yf, X, y = _problem(seed=seed, n=30, p=150)
    tol = 1e-10
    # lo_frac 0.15: the active set grows through ≥2 bucket sizes without
    # entering the ill-conditioned kept≈n regime where the f32 gap floor
    # dominates the comparison
    grid = lambda_grid(float(lambda_max(Xf, yf)), num=10, lo_frac=0.15)
    res = lasso_path(X, y, grid,
                     PathConfig(rule="edpp", solver=solver, solver_tol=tol))
    buckets = [s.bucket for s in res.stats if s.bucket > 0]
    assert len(set(buckets)) > 1, "grid must cross a bucket-size change"
    transitions = [k for k in range(1, len(res.stats))
                   if res.stats[k].bucket not in (0, res.stats[k - 1].bucket)]
    solve_cold = fista if solver == "fista" else cd
    for k in transitions:
        lam = float(res.lambdas[k])
        if solver == "fista":
            cold = solve_cold(Xf, yf, lam, tol=tol, max_iter=30000)
        else:
            cold = solve_cold(Xf, yf, lam, tol=tol, max_epochs=3000)
        # f32 floors the reachable gap, so "bit-identical at tight tol"
        # means: within f32 solver precision, with identical support
        diff = np.abs(res.betas[k] - np.asarray(cold.beta)).max()
        assert diff < 5e-4, (solver, k, diff)
        np.testing.assert_array_equal(np.abs(res.betas[k]) > 1e-3,
                                      np.abs(np.asarray(cold.beta)) > 1e-3)


# ---------------------------------------------------------------------------
# gap-check cadence: counted, and fewer checks at higher cadence
# ---------------------------------------------------------------------------

def test_gap_check_cadence_counted():
    Xf, yf, X, y = _problem(seed=9, n=30, p=120)
    grid = lambda_grid(float(lambda_max(Xf, yf)), num=6)
    res1 = lasso_path(X, y, grid, PathConfig(rule="edpp", solver_tol=1e-7,
                                             gap_check_cadence=1))
    res10 = lasso_path(X, y, grid, PathConfig(rule="edpp", solver_tol=1e-7,
                                              gap_check_cadence=10))
    checks1 = sum(s.gap_checks for s in res1.stats)
    checks10 = sum(s.gap_checks for s in res10.stats)
    assert checks10 > 0
    assert 2 * checks10 <= checks1, (checks1, checks10)
    # unchanged solutions (cadence only affects when we *notice* convergence)
    np.testing.assert_allclose(res10.betas, res1.betas, atol=5e-5)
    for s in res1.stats:
        if s.solve_time_s > 0 and s.n_kept:
            assert s.gap_checks >= 1


# ---------------------------------------------------------------------------
# registry + Lipschitz cache (satellites)
# ---------------------------------------------------------------------------

def test_unknown_solver_raises():
    yf = jnp.zeros((1, 4), jnp.float32)
    with pytest.raises(ValueError, match="unknown solver"):
        SolverEngine(yf, solver="lars")


def test_unknown_solver_backend_raises():
    yf = jnp.zeros((1, 4), jnp.float32)
    with pytest.raises(ValueError, match="unknown solver backend"):
        SolverEngine(yf, backend="mosaic-gpu")


def test_register_solver_dispatches():
    calls = []

    def traced_fista(eng, Xr, lam, beta0, valid, m):
        calls.append(Xr.shape)
        return SOLVERS["fista"](eng, Xr, lam, beta0, valid, m)

    register_solver("traced_fista", traced_fista)
    try:
        assert "traced_fista" in available_solvers()
        Xf, yf, X, y = _problem(seed=10)
        grid = lambda_grid(float(lambda_max(Xf, yf)), num=4)
        res = lasso_path(X, y, grid, PathConfig(rule="edpp",
                                                solver="traced_fista"))
        assert calls, "registered strategy was never dispatched"
        ref = lasso_path(X, y, grid, PathConfig(rule="edpp"))
        np.testing.assert_allclose(res.betas, ref.betas, atol=1e-6)
    finally:
        SOLVERS.pop("traced_fista", None)


def test_power_iteration_warm_start_and_plumbing():
    Xf, yf, X, y = _problem(seed=11, n=40, p=100)
    eig_np = float(np.linalg.norm(X, 2) ** 2)
    cold = float(power_iteration(Xf, iters=100))
    assert abs(cold - eig_np) < 1e-2 * eig_np
    # explicit key/dtype plumbing
    import jax
    keyed = float(power_iteration(Xf, iters=100, key=jax.random.PRNGKey(3),
                                  dtype=jnp.float32))
    assert abs(keyed - eig_np) < 1e-2 * eig_np
    # warm start: a handful of iterations from the cached eigenvector
    # matches the cold estimate
    _, v = top_eigenpair(Xf, iters=100)
    warm, _ = top_eigenpair(Xf, iters=3, v0=v)
    assert abs(float(warm) - cold) < 1e-3 * cold   # f32 matvec noise


def test_engine_lipschitz_cache_per_bucket():
    Xf, yf, X, y = _problem(seed=12, n=40, p=128)
    eng = SolverEngine(yf[None], solver="fista", backend="jnp")
    L1 = float(eng.lipschitz(Xf[:, :64]))
    assert set(eng._eig_cache) == {64}
    L2 = float(eng.lipschitz(Xf[:, :64]))       # warm re-estimate, same bucket
    assert abs(L1 - L2) < 1e-3 * L1
    eng.lipschitz(Xf)                           # new bucket → new cache entry
    assert set(eng._eig_cache) == {64, 128}
    # 1.05 safety margin over the true norm
    assert L1 >= float(np.linalg.norm(X[:, :64], 2) ** 2)


def test_group_fista_wrapper_compat():
    """The back-compat wrappers keep their seed signatures/semantics."""
    rng = np.random.default_rng(13)
    X = rng.standard_normal((30, 60)).astype(np.float32)
    y = (X[:, :6] @ rng.uniform(-1, 1, 6)).astype(np.float32)
    res = group_fista(X, y, 0.3 * float(group_lambda_max(jnp.asarray(X),
                                                         jnp.asarray(y), 4)),
                      4, max_iter=20000, tol=1e-9)
    oracle = fista_group(X, y, 0.3 * float(group_lambda_max(
        jnp.asarray(X), jnp.asarray(y), 4)), 4)
    np.testing.assert_allclose(np.asarray(res.beta), oracle,
                               rtol=2e-3, atol=2e-4)
    assert bool(res.converged)
