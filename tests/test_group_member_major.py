"""Block-FISTA on the member-major view of a group bucket.

The group solver reorders a bucket of G groups of m columns from group-
major (column g·m + i) to member-major (column i·G + g) inside its jitted
body and back on exit. The arithmetic is unchanged, only the order of the
sums in each product, so the solves must agree with the group-major body
they replaced, kept here as the oracle: β to f32 tolerance, the gaps, the
convergence flags, and the iterations within one gap-check cadence. Cases
cover zero-padded groups, per-query ``valid`` masks and a batch whose
queries converge, and freeze, at different iterations.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import LassoSession
from repro.core import (PathConfig, group_gap_from_residual,
                        group_lambda_max, group_soft_threshold,
                        lambda_grid, lambda_max)
from repro.core.group_lasso import (member_major_gap_from_residual,
                                    member_major_norms,
                                    member_major_soft_threshold,
                                    to_group_major, to_member_major)
from repro.core.solver import SolveResult, _group_fista_solve_batched

CADENCE = 10
MAX_ITER = 3000
TOL = 1e-6
MS = (2, 3, 10)


# ---------------------------------------------------------------------------
# The group-major block-FISTA body the member-major one replaced (oracle)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "max_iter", "cadence"))
def group_major_solve_batched(X, Y, lam, m, beta0, valid, lipschitz, tol,
                              max_iter, cadence):
    dtype = X.dtype
    step = 1.0 / jnp.maximum(lipschitz, 1e-12)
    scale = 0.5 * jnp.sum(jnp.square(Y), axis=-1) + 1e-30
    gap_rows = jax.vmap(functools.partial(group_gap_from_residual, m=m))
    block_soft = jax.vmap(functools.partial(group_soft_threshold, m=m))

    def gap_of(beta):
        r = Y - beta @ X.T
        return gap_rows(r, r @ X, beta, lam, y=Y)

    def body(state):
        beta, z, t, k, _, conv, iters, checks = state
        frozen = conv[:, None]

        def one_step(carry, _):
            beta, z, t = carry
            g = (z @ X.T - Y) @ X
            beta_new = (block_soft(z - step * g, step * lam)
                        * valid).astype(dtype)
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            z_new = (beta_new + ((t - 1.0) / t_new) * (beta_new - beta))
            z_new = (z_new * valid).astype(dtype)
            beta_new = jnp.where(frozen, beta, beta_new)
            z_new = jnp.where(frozen, z, z_new)
            return (beta_new, z_new, t_new), None

        (beta, z, t), _ = jax.lax.scan(one_step, (beta, z, t), None,
                                       length=cadence)
        iters = iters + jnp.where(conv, 0, cadence)
        gap = gap_of(beta)
        conv = jnp.logical_or(conv, gap <= tol * scale)
        return beta, z, t, k + cadence, gap, conv, iters, checks + 1

    def cond(state):
        _, _, _, k, _, conv, _, _ = state
        return jnp.logical_and(k < max_iter, jnp.any(~conv))

    t0 = jnp.asarray(1.0, dtype=dtype)
    gap0 = gap_of(beta0)
    conv0 = gap0 <= tol * scale
    iters0 = jnp.zeros(Y.shape[:1], jnp.int32)
    state = (beta0, beta0, t0, jnp.asarray(0), gap0, conv0, iters0,
             jnp.asarray(1))
    beta, _, _, _, gap, conv, iters, checks = jax.lax.while_loop(
        cond, body, state)
    return SolveResult(beta, gap, iters, conv, checks)


# ---------------------------------------------------------------------------
# Problems: a bucket of G groups, the last `pad` of them zero (as the path
# driver pads a bucket to a power of two), B responses of distinct sparsity
# ---------------------------------------------------------------------------

def _bucket(m, seed, G=16, pad=3, n=40, B=4):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, G * m))
    X[:, (G - pad) * m:] = 0.0
    Y = []
    for q in range(B):
        beta = np.zeros(G * m)
        for g in rng.choice(G - pad, 2 + q, replace=False):
            beta[g * m:(g + 1) * m] = rng.uniform(-1, 1, m)
        Y.append(X @ beta + 0.05 * rng.standard_normal(n))
    X, Y = np.float32(X), np.float32(Y)
    lam = np.float32([f * float(group_lambda_max(X, y, m))
                      for f, y in zip((0.7, 0.4, 0.25, 0.55)[:B], Y)])
    L = np.float32(1.05 * np.linalg.norm(X, 2) ** 2)
    return jnp.asarray(X), jnp.asarray(Y), jnp.asarray(lam), jnp.asarray(L)


def _valid(X, Y, lam, L, m, seed):
    """Per-query kept groups on the union bucket, constant over each
    group's m columns, as a safe screen leaves them: each query drops, at
    random, half of the groups that are inactive at its solution with
    room (‖X_gᵀr*‖ < 0.8·λ√m); query 0 keeps every group, the zero-padded
    tail included."""
    B, G = Y.shape[0], X.shape[1] // m
    ones = jnp.ones((B, G * m), jnp.float32)
    sol = group_major_solve_batched(X, Y, lam, m, jnp.zeros_like(ones), ones,
                                    L, TOL, MAX_ITER, CADENCE).beta
    corr = np.asarray((Y - sol @ X.T) @ X).reshape(B, G, m)
    slack = (np.linalg.norm(corr, axis=-1)
             < 0.8 * np.asarray(lam)[:, None] * np.sqrt(m))
    drop = slack & (np.random.default_rng(seed).random((B, G)) < 0.5)
    drop[0] = False
    assert drop.any()
    return jnp.asarray(np.repeat(~drop, m, axis=1).astype(np.float32))


def _compare(run, Y, tol=TOL):
    """Run the member-major solve and the group-major oracle through
    ``run(solve, tol, max_iter)`` and check: β to f32 tolerance, the same
    convergence flags, the gaps within a quarter of the stopping width
    where both stopped at the same iteration (a converged query's
    certificates lie in [0, tol·½‖y‖²] in any case), and the iterations
    within one gap-check cadence. Where they lie further
    apart, the two stop decisions may differ only at checks whose gaps
    both sit within that quarter of the threshold: the gap of an
    accelerated method ripples, and a check on the threshold can fall
    either way under another summation order."""
    res = run(_group_fista_solve_batched, tol, MAX_ITER)
    ref = run(group_major_solve_batched, tol, MAX_ITER)
    beta, beta_ref = np.asarray(res.beta), np.asarray(ref.beta)
    assert beta.shape == beta_ref.shape
    np.testing.assert_allclose(beta, beta_ref, rtol=2e-3,
                               atol=2e-4 * float(np.max(np.abs(beta_ref))))
    np.testing.assert_array_equal(np.asarray(res.converged),
                                  np.asarray(ref.converged))
    thresh = np.atleast_1d(tol * 0.5 * np.sum(np.square(np.asarray(Y)),
                                              axis=-1))
    band = 0.25 * thresh
    gaps = np.atleast_1d(np.asarray(res.gap))
    gaps_ref = np.atleast_1d(np.asarray(ref.gap))
    its = np.atleast_1d(np.asarray(res.iters))
    its_ref = np.atleast_1d(np.asarray(ref.iters))
    conv = np.atleast_1d(np.asarray(ref.converged))
    for g in (gaps, gaps_ref):
        assert np.all((g[conv] <= thresh[conv]) & (g[conv] >= -band[conv]))
    same = its == its_ref
    assert np.all(np.abs(gaps - gaps_ref)[same]
                  <= (band + 1e-3 * np.abs(gaps_ref))[same])
    for q in np.flatnonzero(np.abs(its - its_ref) > CADENCE):
        for k in range(min(its[q], its_ref[q]), max(its[q], its_ref[q]),
                       CADENCE):
            # never stopping (tol < 0): the gaps at iteration k
            g = np.atleast_1d(np.asarray(
                run(_group_fista_solve_batched, -1.0, k).gap))[q]
            g_ref = np.atleast_1d(np.asarray(
                run(group_major_solve_batched, -1.0, k).gap))[q]
            assert abs(g - g_ref) <= band[q]
            if (g <= thresh[q]) != (g_ref <= thresh[q]):
                assert max(abs(g - thresh[q]),
                           abs(g_ref - thresh[q])) <= band[q]
    return res, ref


# ---------------------------------------------------------------------------
# The layout helpers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", MS)
@pytest.mark.parametrize("axis", [0, -1])
def test_member_major_round_trip_is_identity(m, axis):
    G = 7
    a = jnp.arange(3 * G * m, dtype=jnp.float32).reshape(
        (G * m, 3) if axis == 0 else (3, G * m))
    mm = to_member_major(a, m, axis=axis)
    # entry g·m + i lands at i·G + g
    src = np.arange(G * m).reshape(G, m).T.reshape(-1)
    np.testing.assert_array_equal(np.asarray(mm),
                                  np.take(np.asarray(a), src, axis=axis))
    np.testing.assert_array_equal(np.asarray(to_group_major(mm, m,
                                                            axis=axis)),
                                  np.asarray(a))


@pytest.mark.parametrize("m", MS)
def test_member_major_prox_and_gap_match_group_major(m):
    rng = np.random.default_rng(m)
    G, n, B = 9, 12, 3
    X = jnp.asarray(rng.standard_normal((n, G * m)), jnp.float32)
    beta = jnp.asarray(rng.standard_normal((B, G * m)), jnp.float32)
    beta = beta.at[:, :m].set(0.0)                 # a zero group
    Y = jnp.asarray(rng.standard_normal((B, n)), jnp.float32)
    lam = jnp.asarray([0.3, 1.0, 4.0], jnp.float32)
    soft = jax.vmap(functools.partial(group_soft_threshold, m=m))
    gap = jax.vmap(functools.partial(group_gap_from_residual, m=m))
    r = Y - beta @ X.T
    want_soft, want_gap = soft(beta, lam), gap(r, r @ X, beta, lam, y=Y)
    bm = to_member_major(beta, m)
    got_soft = to_group_major(member_major_soft_threshold(bm, lam, m), m)
    got_gap = member_major_gap_from_residual(
        r, r @ to_member_major(X, m), bm, lam, m, Y)
    np.testing.assert_allclose(np.asarray(got_soft), np.asarray(want_soft),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_gap), np.asarray(want_gap),
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(member_major_norms(bm, m)),
        np.linalg.norm(np.asarray(beta).reshape(B, G, m), axis=-1),
        rtol=1e-6)


# ---------------------------------------------------------------------------
# The solves against the group-major oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [4, 1])
@pytest.mark.parametrize("m", MS)
def test_batched_member_major_solve_matches_group_major(m, B):
    """B = 4 queries with per-query kept groups, and one query as a batch
    of one (every group kept, the zero-padded tail included)."""
    X, Y, lam, L = _bucket(m, seed=100 + m if B > 1 else 300 + m, B=B)
    G = X.shape[1] // m
    valid = (_valid(X, Y, lam, L, m, seed=m) if B > 1
             else jnp.ones((1, X.shape[1]), jnp.float32))
    beta0 = jnp.zeros_like(valid)
    res, ref = _compare(lambda solve, tol, it: solve(
        X, Y, lam, m, beta0, valid, L, tol, it, CADENCE), Y)
    assert bool(np.all(np.asarray(ref.converged)))
    if B > 1:
        # queries stopped at different checks: the early ones froze
        assert len(set(np.asarray(ref.iters).tolist())) > 1
    # screened-out and padded groups stay exactly zero
    assert np.all(np.asarray(res.beta)[np.asarray(valid) == 0] == 0.0)
    assert np.all(np.asarray(res.beta)[:, (G - 3) * m:] == 0.0)


@pytest.mark.parametrize("m", MS)
def test_batched_member_major_solve_warm_start_matches_group_major(m):
    """A warm start from a neighbouring λ, as the path driver passes."""
    X, Y, lam, L = _bucket(m, seed=200 + m)
    valid = jnp.ones((Y.shape[0], X.shape[1]), jnp.float32)
    warm = group_major_solve_batched(X, Y, 1.2 * lam, m,
                                     jnp.zeros_like(valid), valid, L, TOL,
                                     MAX_ITER, CADENCE).beta
    _compare(lambda solve, tol, it: solve(
        X, Y, lam, m, warm, valid, L, tol, it, CADENCE), Y)


def test_unconverged_queries_match_group_major():
    """Stopped by max_iter before the gap: the same iterate either way."""
    m = 10
    X, Y, lam, L = _bucket(m, seed=7)
    valid = jnp.ones((Y.shape[0], X.shape[1]), jnp.float32)
    args = (X, Y, 0.05 * lam, m, jnp.zeros_like(valid), valid, L, 1e-12,
            40, CADENCE)
    res, ref = _group_fista_solve_batched(*args), \
        group_major_solve_batched(*args)
    assert not np.any(np.asarray(ref.converged))
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(ref.beta),
                               rtol=2e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.gap), np.asarray(ref.gap),
                               rtol=1e-3)
    np.testing.assert_array_equal(np.asarray(res.iters), 40)


# ---------------------------------------------------------------------------
# PathStepStats.member_major_solves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", [1, 3])
def test_member_major_solves_counted_per_step(batch):
    rng = np.random.default_rng(5)
    n, p, m = 30, 120, 10
    X = rng.standard_normal((n, p)).astype(np.float32)
    Y = (X[:, :20] @ rng.uniform(-1, 1, 20)
         + 0.1 * rng.standard_normal((batch, n))).astype(np.float32)
    Yq = Y[0] if batch == 1 else Y
    cfg = PathConfig(solver_tol=TOL)
    grp = LassoSession.fit(X, groups=m, config=cfg)
    lmax = float(group_lambda_max(X, Y[0], m))
    res = grp.path(Yq, lambda_grid(lmax, 4, lo_frac=0.4))
    live = [s for s in res.stats if s.n_kept > 0]
    assert live
    assert all(s.member_major_solves == 1 for s in live)
    lasso = LassoSession.fit(X, config=cfg)
    res = lasso.path(Yq, lambda_grid(float(lambda_max(X, Y[0])), 4,
                                     lo_frac=0.4))
    assert any(s.n_kept > 0 for s in res.stats)
    assert all(s.member_major_solves == 0 for s in res.stats)
