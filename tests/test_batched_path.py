"""Batched multi-query paths: B queries against one fitted dictionary must
reproduce B independent single-query runs.

The contract (ISSUE 4 acceptance / docs/serving.md):

  * per-query screening masks from the batched driver are IDENTICAL
    bit-for-bit to the single-query runs (safe rules and the strong rule's
    post-KKT masks), on the jnp and interpret backends, through both
    engines (batched fused screens + batched solver strategies);
  * per-query β agrees within ``beta_err_tol`` (two gap-ε optima);
  * a converged query's β is a FIXED POINT of further batched iterations
    (the convergence mask freezes it inside the solver while_loop);
  * per-query λ-grids: a query in its trivial region (λ ≥ its own λ_max)
    stays at β = 0 and discards everything;
  * the batched screen costs ONE X pass for the whole batch
    (``x_passes_per_query`` = 1/B);
  * a single query (n,) is a batch of one: ``path(y)`` ≡ ``path(y[None])``.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (DictionaryGeometry, PathConfig, RULES,
                        ScreeningEngine, SolverEngine, lambda_grid,
                        lambda_max, lasso_path, lasso_path_batched)
from repro.data import QueryStream

BACKENDS = ["jnp", "interpret"]
N, P, B, K = 40, 200, 8, 8


def _stream_problem(b=B, n=N, p=P, seed=3):
    stream = QueryStream(n=n, p=p, batch=b, nnz=10, seed=seed)
    X = stream.dictionary()
    Y = stream.host_batch(0)["y"]
    return X, Y


# ---------------------------------------------------------------------------
# batched engine screens == per-query oracle screens, bit-for-bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_screens_match_per_query(backend):
    X, Y = _stream_problem()
    Xf = jnp.asarray(X, jnp.float32)
    Yf = jnp.asarray(Y, jnp.float32)
    geom = DictionaryGeometry(Xf, backend)
    eng = ScreeningEngine(Xf, Yf, backend=backend, geometry=geom)
    singles = [ScreeningEngine(Xf, Yf[b:b + 1], backend=backend)
               for b in range(B)]
    state = eng.state_at_lambda_max()
    states = [e.state_at_lambda_max() for e in singles]
    lam_vec = jnp.asarray(eng.lam_max * 0.5, jnp.float32)
    for rule in list(RULES) + ["safe", "dome"]:
        got = np.asarray(eng.screen(lam_vec, state, rule))
        assert got.shape == (B, P)
        for b in range(B):
            want = np.asarray(singles[b].screen(lam_vec[b:b + 1],
                                                states[b], rule))[0]
            np.testing.assert_array_equal(got[b], want,
                                          err_msg=f"{rule} query {b}")
    # one fused pass for the whole batch
    eng.screen(lam_vec, state, "edpp")
    assert eng.last_x_passes == 1


# ---------------------------------------------------------------------------
# batched path == B single-query paths (masks bitwise, β to tolerance)
# ---------------------------------------------------------------------------

def beta_err_tol(y, solver_tol, kappa=25.0):
    """benchmarks/common.py's bound: two gap-ε optima differ ≤ κ√(ε·½‖y‖²)."""
    return kappa * float(np.sqrt(solver_tol * 0.5 * np.dot(y, y)))


def _inside_grids(X, Y, num):
    """Per-query grids strictly INSIDE (0, λ_max): the λ = λ_max grid point
    is degenerate (β = 0 trivially, and its live/trivial classification
    flips on the last bit of λ_max, which differs between the batched and
    single kernel reductions) — parity there is not meaningful."""
    return np.stack([
        lambda_grid(float(np.max(np.abs(X.T @ Y[b]))), num=num,
                    hi_frac=0.95) for b in range(Y.shape[0])])


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("solver", ["fista", "cd"])
def test_batched_path_reproduces_single_runs(backend, solver):
    X, Y = _stream_problem()
    tol = 1e-10
    cfg = PathConfig(rule="edpp", solver=solver, solver_tol=tol,
                     backend=backend, solver_backend=backend)
    grids = _inside_grids(X, Y, K)
    res_b = lasso_path_batched(X, Y, grids, cfg)
    assert res_b.betas.shape == (B, K, P)
    assert res_b.masks.shape == (B, K, P)
    for b in range(B):
        res_1 = lasso_path(X, Y[b], grids[b], cfg)
        np.testing.assert_array_equal(res_b.masks[b], res_1.masks,
                                      err_msg=f"query {b}")
        err = np.abs(res_b.betas[b] - res_1.betas).max()
        assert err <= beta_err_tol(Y[b], tol), (b, err)
    # the shared screen pass amortises 1/B per query
    screened = [s for s in res_b.stats if s.screen_time_s > 0]
    assert screened
    assert all(s.batch_size == B for s in screened)
    assert all(s.x_passes_per_query == s.x_passes / B for s in screened)


def test_batched_strong_rule_kkt_per_query():
    """The heuristic strong rule's KKT re-add loop must act per query."""
    X, Y = _stream_problem(seed=5)
    cfg = PathConfig(rule="strong", solver="fista", solver_tol=1e-10,
                     kkt_tol=1e-8)
    grids = _inside_grids(X, Y, K)
    res_b = lasso_path_batched(X, Y, grids, cfg)
    for b in range(B):
        res_1 = lasso_path(X, Y[b], grids[b], cfg)
        np.testing.assert_array_equal(res_b.masks[b], res_1.masks,
                                      err_msg=f"query {b}")
        assert np.abs(res_b.betas[b] - res_1.betas).max() < 5e-3


# ---------------------------------------------------------------------------
# converged queries are fixed points of further batched iterations
# ---------------------------------------------------------------------------

def test_converged_query_beta_untouched_by_more_iterations():
    X, Y = _stream_problem(b=4, seed=7)
    Xf = jnp.asarray(X, jnp.float32)
    Yf = jnp.asarray(Y, jnp.float32)
    lmaxes = np.array([float(lambda_max(Xf, Yf[b])) for b in range(4)])
    # easy queries (λ near λ_max: tiny active set) converge quickly; the
    # hard query (λ = 0.05·λ_max) keeps iterating long after
    fracs = np.array([0.9, 0.8, 0.7, 0.05])
    lam = jnp.asarray(fracs * lmaxes, jnp.float32)
    short = SolverEngine(Yf, solver="fista", backend="jnp", tol=1e-7,
                         max_iter=300)
    res_short = short.solve(Xf, lam)
    longer = SolverEngine(Yf, solver="fista", backend="jnp", tol=1e-7,
                          max_iter=5000)
    res_long = longer.solve(Xf, lam)
    conv = np.asarray(res_short.converged)
    assert conv[:3].all(), "easy queries should converge inside 300 iters"
    assert not conv.all(), "the hard query must still be iterating"
    for b in range(4):
        if conv[b]:
            # bitwise: the frozen query's β did not move in the extra
            # thousands of batched iterations
            np.testing.assert_array_equal(np.asarray(res_short.beta[b]),
                                          np.asarray(res_long.beta[b]),
                                          err_msg=f"query {b}")
    # per-query iteration counters stop at the freeze
    iters = np.asarray(res_short.iters)
    assert iters[:3].max() < iters[3]


# ---------------------------------------------------------------------------
# per-query trivial region + per-query grids
# ---------------------------------------------------------------------------

def test_per_query_trivial_region_on_shared_grid():
    X, Y = _stream_problem(b=2, seed=9)
    # scale query 1 down so its λ_max is far below query 0's
    Y = np.stack([Y[0], 0.3 * Y[1]])
    lmax0 = float(lambda_max(jnp.asarray(X), jnp.asarray(Y[0])))
    lmax1 = float(lambda_max(jnp.asarray(X), jnp.asarray(Y[1])))
    assert lmax1 < 0.5 * lmax0
    grid = lambda_grid(lmax0, num=6)
    cfg = PathConfig(rule="edpp", solver_tol=1e-9)
    res_b = lasso_path_batched(X, Y, grid, cfg)     # shared (K,) grid
    dead = grid >= lmax1
    assert dead.any() and not dead.all()
    for k in np.flatnonzero(dead):
        assert np.all(res_b.betas[1, k] == 0.0)
        assert res_b.masks[1, k].all()
    # both queries still reproduce their single runs on that grid
    for b in range(2):
        res_1 = lasso_path(X, Y[b], grid, cfg)
        np.testing.assert_array_equal(res_b.masks[b], res_1.masks)
        assert np.abs(res_b.betas[b] - res_1.betas).max() < 5e-3


def test_per_query_grids_scale_with_own_lam_max():
    X, Y = _stream_problem(b=3, seed=11)
    res = lasso_path_batched(X, Y, None, PathConfig(rule="edpp"),
                             num_lambdas=5)
    lmaxes = [float(lambda_max(jnp.asarray(X), jnp.asarray(Y[b])))
              for b in range(3)]
    for b in range(3):
        np.testing.assert_allclose(res.lambdas[b],
                                   lambda_grid(lmaxes[b], num=5), rtol=1e-4)


# ---------------------------------------------------------------------------
# registered strategies: one strategy per solver, over the batch
# ---------------------------------------------------------------------------

def test_solve_batched_fallback_loops_single_strategy():
    """A strategy registered under a new name solves the whole batch as
    the built-in one does."""
    from repro.core import SOLVERS, register_solver
    X, Y = _stream_problem(b=3, seed=13)
    Xf = jnp.asarray(X, jnp.float32)
    Yf = jnp.asarray(Y, jnp.float32)
    lam = jnp.asarray([0.5 * float(lambda_max(Xf, Yf[b]))
                       for b in range(3)], jnp.float32)
    register_solver("fista_noname", SOLVERS["fista"])
    try:
        eng = SolverEngine(Yf, solver="fista_noname", backend="jnp",
                           tol=1e-6, max_iter=20000)
        res = eng.solve(Xf, lam)
        native = SolverEngine(Yf, solver="fista", backend="jnp", tol=1e-6,
                              max_iter=20000).solve(Xf, lam)
        assert res.beta.shape == native.beta.shape
        np.testing.assert_allclose(np.asarray(res.beta),
                                   np.asarray(native.beta), atol=5e-3)
    finally:
        SOLVERS.pop("fista_noname", None)


def test_fallback_solver_through_strong_rule_path():
    """A registered strategy under the strong rule's KKT loop solves each
    query's OWN reduced problem (union-buffer columns a query screened out
    are pinned per query) and reproduces the single-query runs."""
    from repro.core import SOLVERS, register_solver
    X, Y = _stream_problem(b=4, seed=21, n=40, p=150)
    grids = _inside_grids(X, Y, 6)
    register_solver("fista_fallback", SOLVERS["fista"])
    try:
        cfg = PathConfig(rule="strong", solver="fista_fallback",
                         solver_tol=1e-9, kkt_tol=1e-8)
        res_b = lasso_path_batched(X, Y, grids, cfg)
        assert not np.isnan(res_b.betas).any()
        for b in range(4):
            res_1 = lasso_path(X, Y[b], grids[b], cfg)
            np.testing.assert_array_equal(res_b.masks[b], res_1.masks,
                                          err_msg=f"query {b}")
            assert np.abs(res_b.betas[b] - res_1.betas).max() < 5e-3, b
    finally:
        SOLVERS.pop("fista_fallback", None)


# ---------------------------------------------------------------------------
# one query is a batch of one
# ---------------------------------------------------------------------------

_COUNTERS = ("lam", "n_discarded", "n_kept", "solver_iters", "gap",
             "kkt_rounds", "x_passes", "gap_checks", "gram_step_frac",
             "bucket", "solver_x_passes", "batch_size", "queries_converged",
             "x_passes_per_query", "host_syncs", "member_major_solves")


@pytest.mark.parametrize("groups", [None, 4], ids=["lasso", "group"])
def test_single_query_is_a_batch_of_one(groups):
    """``path(y)`` and ``path(y[None])`` run the same driver: masks and β
    bit-identical, the same per-step counters, and the checkpoint hook
    called with the single-query shapes for the 1-D call."""
    from repro.core import LassoSession
    X, Y = _stream_problem(b=1, seed=17)
    y = jnp.asarray(Y[0])
    calls = {1: [], 2: []}

    def run(q, key):
        # a fresh session per call: the Lipschitz eig-cache warms on use,
        # which shifts the power iteration's start for the next call
        cfg = PathConfig(solver_tol=1e-8, checkpoint_fn=lambda k, lam, b:
                         calls[key].append((k, np.shape(lam), b.shape)))
        sess = LassoSession.fit(jnp.asarray(X, jnp.float32), groups=groups,
                                config=cfg)
        return sess.path(q, num_lambdas=6, hi_frac=0.95)

    one, batch = run(y, 1), run(y[None], 2)
    assert one.batched and one.batch == 1
    for attr in ("lambdas", "betas", "masks", "query_converged"):
        np.testing.assert_array_equal(getattr(one, attr),
                                      getattr(batch, attr), err_msg=attr)
    assert any(s.n_kept for s in one.stats)
    for s1, sb in zip(one.stats, batch.stats, strict=True):
        for f in _COUNTERS:
            assert getattr(s1, f) == getattr(sb, f), f
    assert calls[1] == [(k, (), (P,)) for k in range(6)]
    assert calls[2] == [(k, (1,), (1, P)) for k in range(6)]


def test_query_converged_reports_per_query():
    """PathResult.query_converged: per-query completion flag the serve loop
    surfaces on tickets (True iff every non-trivial reduced solve hit its
    duality-gap stop)."""
    X, Y = _stream_problem(b=3, seed=19)
    grids = _inside_grids(X, Y, 5)
    res = lasso_path_batched(X, Y, grids,
                             PathConfig(rule="edpp", solver_tol=1e-6))
    assert res.query_converged.shape == (3,)
    assert res.query_converged.all()
    assert res.query(1).query_converged.shape == (1,)   # narrows per query
    # a solver capped far below convergence still returns β (best-effort)
    # but reports every query unconverged
    res2 = lasso_path_batched(X, Y, grids,
                              PathConfig(rule="edpp", solver_tol=1e-12,
                                         max_iter=2))
    assert not res2.query_converged.any()
    assert np.isfinite(res2.betas).all()


# ---------------------------------------------------------------------------
# QueryStream determinism (the serving/bench data contract)
# ---------------------------------------------------------------------------

def test_query_stream_deterministic_and_sharded():
    s = QueryStream(n=20, p=50, batch=4, nnz=5, seed=1)
    a = s.host_batch(step=3, shard=2, n_shards=4)
    b = s.host_batch(step=3, shard=2, n_shards=4)
    np.testing.assert_array_equal(a["y"], b["y"])       # replayable
    c = s.host_batch(step=3, shard=1, n_shards=4)
    assert not np.array_equal(a["y"], c["y"])           # shards differ
    d = s.host_batch(step=4, shard=2, n_shards=4)
    assert not np.array_equal(a["y"], d["y"])           # steps differ
    np.testing.assert_array_equal(s.dictionary(), s.dictionary())
    assert a["y"].shape == (1, 20) and a["beta"].shape == (1, 50)
    # queries are consistent with their ground truth: y = Xβ + σε
    full = QueryStream(n=20, p=50, batch=4, nnz=5, seed=1).host_batch(0)
    resid = full["y"] - full["beta"] @ s.dictionary().T
    assert np.abs(resid).max() < 1.0                    # σ = 0.1 noise
