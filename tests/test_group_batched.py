"""Batched group-Lasso paths: B queries against one fitted dictionary in
groups of m go through ONE path driver — per λ step one group screen over
X for the whole batch and one batched block-FISTA solve on the union of
kept groups — and reproduce B single-query group paths.

Each case checks:

  * per-query discard masks bit-identical to the single-query group path
    run on the same grid (edpp, the strong rule after its KKT rounds, and
    no screening);
  * per-query β within the float64 block-FISTA oracle's tolerance
    (``ref_lasso.fista_group``, a solution of the full problem);
  * one pass over X per live step for the batch (none for ``rule="none"``),
    where looping the single-query path would make B.
"""

import numpy as np
import pytest

from repro import LassoSession
from repro.core import PathConfig, group_lambda_max, lambda_grid

from ref_lasso import fista_group

N, P, K = 30, 120, 5
TOL = 1e-6


def _problem(b, m, seed, active=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((N, P))
    Y = []
    for _ in range(b):
        beta = np.zeros(P)
        for g in rng.choice(P // m, active, replace=False):
            beta[g * m:(g + 1) * m] = rng.uniform(-1, 1, m)
        Y.append(X @ beta + 0.1 * rng.standard_normal(N))
    return X.astype(np.float32), np.asarray(Y, np.float32)


def _lam_max(X, y, m):
    return float(group_lambda_max(np.asarray(X, np.float64),
                                  np.asarray(y, np.float64), m))


def _beta_tol(y):
    """Two optima within relative gap TOL lie within κ√(TOL·½‖y‖²)
    (tests/test_batched_path.py); κ = 25 as there."""
    return 25.0 * float(np.sqrt(TOL * 0.5 * np.dot(y, y)))


CASES = [dict(b=b, rule=rule, m=m, seed=10 * b + m)
         for b in (1, 3, 8) for rule in ("edpp", "strong", "none")
         for m in (4, 10)]
# one query far below the others' λ̄_max: on the shared grid its first
# steps sit at or above its own λ̄_max, where β = 0 and every group goes
CASES.append(dict(b=3, rule="edpp", m=4, seed=7, dead_query=True))


def _id(case):
    tail = "-dead" if case.get("dead_query") else ""
    return f"B{case['b']}-{case['rule']}-m{case['m']}{tail}"


@pytest.mark.parametrize("case", CASES, ids=[_id(c) for c in CASES])
def test_batched_group_path_reproduces_single_paths(case):
    b, rule, m = case["b"], case["rule"], case["m"]
    X, Y = _problem(b, m, case["seed"])
    lmax = np.array([_lam_max(X, y, m) for y in Y])
    if case.get("dead_query"):
        Y[1] *= 0.3
        lmax[1] *= 0.3
        grids = np.broadcast_to(lambda_grid(lmax.max(), num=K,
                                            hi_frac=0.95, lo_frac=0.3),
                                (b, K))
        dead = grids[1] >= lmax[1]
        assert dead.any() and not dead.all()
    else:
        grids = np.stack([lambda_grid(lm, num=K, hi_frac=0.95, lo_frac=0.3)
                          for lm in lmax])
    cfg = PathConfig(rule=rule, solver_tol=TOL, max_iter=20000,
                     kkt_tol=1e-6)
    sess = LassoSession.fit(X, groups=m, config=cfg)
    res = sess.path(Y, grids)
    assert res.betas.shape == (b, K, P)
    assert res.masks.shape == (b, K, P // m)
    assert res.query_converged.all()

    for q in range(b):
        single = sess.path(Y[q], grids[q]).squeeze()
        np.testing.assert_array_equal(res.masks[q], single.masks,
                                      err_msg=f"query {q}")
        for k in range(K):
            lam = grids[q, k]
            if lam >= lmax[q]:
                assert not res.betas[q, k].any()
                assert res.masks[q, k].all()
                continue
            oracle = fista_group(X, Y[q], lam, m, tol=1e-12)
            err = np.abs(res.betas[q, k] - oracle).max()
            assert err <= _beta_tol(Y[q]), (q, k, err)

    live = [s for k, s in enumerate(res.stats)
            if (grids[:, k] < lmax * (1 - 1e-6)).any()]
    assert live
    passes = 0 if rule == "none" else 1
    assert all(s.x_passes == passes for s in live), \
        [s.x_passes for s in live]
    assert all(s.batch_size == b for s in live)
