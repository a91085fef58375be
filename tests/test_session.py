"""LassoSession — the fit-once / query-many front door (ISSUE 5).

The contract under test (docs/api.md):

  * the fused dictionary-fit pass over X runs EXACTLY once per session,
    however many ``path`` calls are made (``session.fit_passes``), and the
    per-step screen telemetry (``PathStepStats.x_passes``) is identical
    across consecutive calls — no hidden re-fits;
  * every deprecated entry point (``lasso_path``, ``lasso_path_batched``,
    ``group_lasso_path``) delegates through a session and produces
    BIT-IDENTICAL screen masks (and β within ``beta_err_tol``) on grid
    points strictly inside (0, λ_max), on the jnp and interpret backends;
  * dispatch is structural: input rank picks single vs batched, ``groups``
    the group drivers, ``mesh`` the placed/GSPMD path — one unified
    PathResult with a leading batch axis (``squeeze()`` for B = 1);
  * configs are validated at construction (ScreenSpec + SolveSpec), and
    the legacy flat keywords build the same PathConfig;
  * the λ = λ_max grid endpoint is excluded from the bitwise contract
    (its live/dead classification flips on the last bit of λ_max between
    batched and single reductions) — grids pin ``hi_frac=0.95``.
"""

import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (GroupPathConfig, LassoSession, PathConfig,
                        ScreenSpec, SolveSpec, group_lasso_path, lambda_grid,
                        lambda_max, lasso_path, lasso_path_batched)
from repro.data import QueryStream

BACKENDS = ["jnp", "interpret"]
N, P, B, K = 40, 200, 4, 8


def beta_err_tol(y, solver_tol, kappa=25.0):
    """benchmarks/common.py's bound: two gap-ε optima differ ≤ κ√(ε·½‖y‖²)."""
    return kappa * float(np.sqrt(solver_tol * 0.5 * np.dot(y, y)))


def _problem(b=B, n=N, p=P, seed=3):
    stream = QueryStream(n=n, p=p, batch=b, nnz=10, seed=seed)
    return stream.dictionary(), stream.host_batch(0)["y"]


def _grids(X, Y, num=K, hi_frac=0.95):
    """Per-query grids strictly inside (0, λ_max): the λ = λ_max endpoint
    is excluded from the bitwise contract (docs/api.md#exactness-contract)."""
    return np.stack([
        lambda_grid(float(np.max(np.abs(X.T @ Y[b]))), num=num,
                    hi_frac=hi_frac) for b in range(Y.shape[0])])


# ---------------------------------------------------------------------------
# acceptance: fit-once / query-many
# ---------------------------------------------------------------------------

def test_fused_fit_pass_runs_exactly_once_per_session():
    X, Y = _problem()
    y = Y[0]
    sess = LassoSession.fit(X)
    assert sess.fit_passes == 1          # fitted at fit(), before any query
    grid = _grids(X, Y[:1])[0]
    res1 = sess.path(y, grid)
    res2 = sess.path(y, grid)
    # no hidden re-fit: still the one fused pass, one cheap attach per call
    assert sess.fit_passes == 1
    assert sess.query_passes == 2
    # per-step screen passes are identical across consecutive calls and
    # come from the per-step screens alone (1 pass per EDPP screen)
    p1 = [s.x_passes for s in res1.stats]
    p2 = [s.x_passes for s in res2.stats]
    assert p1 == p2
    assert all(s.x_passes == 1 for s in res1.stats if s.screen_time_s > 0)
    np.testing.assert_array_equal(res1.masks, res2.masks)


def test_geometry_object_is_shared_across_calls():
    X, Y = _problem()
    sess = LassoSession.fit(X)
    g0 = sess.geometry
    sess.path(Y[0], _grids(X, Y[:1])[0])
    sess.path(Y, _grids(X, Y))
    assert sess.geometry is g0


# ---------------------------------------------------------------------------
# deprecation shims: bit-identical masks through the session
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_lasso_path_shim_matches_session(backend):
    X, Y = _problem()
    y = Y[0]
    tol = 1e-10
    cfg = PathConfig(rule="edpp", solver_tol=tol, backend=backend,
                     solver_backend=backend)
    grid = _grids(X, Y[:1])[0]
    sess = LassoSession.fit(X, config=cfg)
    res_s = sess.path(y, grid).squeeze()
    with pytest.deprecated_call():
        res_old = lasso_path(X, y, grid, cfg)
    assert res_old.betas.shape == (K, P)           # squeezed legacy layout
    np.testing.assert_array_equal(res_old.masks, res_s.masks)
    assert np.abs(res_old.betas - res_s.betas).max() <= beta_err_tol(y, tol)


@pytest.mark.parametrize("backend", BACKENDS)
def test_lasso_path_batched_shim_matches_session(backend):
    X, Y = _problem()
    tol = 1e-10
    cfg = PathConfig(rule="edpp", solver_tol=tol, backend=backend,
                     solver_backend=backend)
    grids = _grids(X, Y)
    sess = LassoSession.fit(X, config=cfg)
    res_s = sess.path(Y, grids)
    with pytest.deprecated_call():
        res_old = lasso_path_batched(X, Y, grids, cfg)
    assert res_old.betas.shape == (B, K, P)
    np.testing.assert_array_equal(res_old.masks, res_s.masks)
    for b in range(B):
        assert (np.abs(res_old.betas[b] - res_s.betas[b]).max()
                <= beta_err_tol(Y[b], tol)), b


@pytest.mark.parametrize("backend", BACKENDS)
def test_group_lasso_path_shim_matches_session(backend):
    X, Y = _problem()
    y, m = Y[0], 4
    tol = 1e-10
    cfg = PathConfig(rule="edpp", solver_tol=tol, backend=backend,
                     solver_backend=backend)
    grid = _grids(X, Y[:1], num=5)[0]
    sess = LassoSession.fit(X, groups=m, config=cfg)
    res_s = sess.path(y, grid).squeeze()
    with pytest.deprecated_call():
        res_old = group_lasso_path(X, y, m, grid, cfg)
    assert res_old.masks.shape == (5, P // m)
    np.testing.assert_array_equal(res_old.masks, res_s.masks)
    assert np.abs(res_old.betas - res_s.betas).max() <= beta_err_tol(y, tol)


def test_group_path_config_factory_is_deprecated_pathconfig():
    with pytest.deprecated_call():
        cfg = GroupPathConfig(rule="edpp", solver_tol=1e-9)
    assert isinstance(cfg, PathConfig)
    assert cfg.solver == "group_fista" and cfg.bucket_min == 16
    assert cfg.solver_tol == 1e-9


# ---------------------------------------------------------------------------
# structural dispatch + the unified result
# ---------------------------------------------------------------------------

def test_dispatch_by_rank_and_unified_result():
    X, Y = _problem()
    sess = LassoSession.fit(X)
    grids = _grids(X, Y)
    single = sess.path(Y[0], grids[0])
    assert single.batched and single.batch == 1
    assert single.betas.shape == (1, K, P)
    assert single.lambdas.shape == (1, K)
    sq = single.squeeze()
    assert sq.betas.shape == (K, P) and not sq.batched
    np.testing.assert_array_equal(sq.betas, single.betas[0])   # bitwise view

    batched = sess.path(Y, grids)
    assert batched.batch == B and batched.betas.shape == (B, K, P)
    q = batched.query(1)
    np.testing.assert_array_equal(q.masks, batched.masks[1])
    with pytest.raises(ValueError):
        batched.squeeze()                      # B>1 must not silently squeeze
    with pytest.raises(ValueError):
        sq.query(0)                            # squeezed result has no batch
    with pytest.raises(ValueError):
        sess.path(Y[None])                     # rank-3 queries
    with pytest.raises(ValueError):
        sess.path(np.zeros(N + 1))             # wrong query length


def test_batched_path_through_session_matches_singles():
    X, Y = _problem()
    tol = 1e-10
    sess = LassoSession.fit(X, config=PathConfig(rule="edpp",
                                                 solver_tol=tol))
    grids = _grids(X, Y)
    res_b = sess.path(Y, grids)
    for b in range(B):
        res_1 = sess.path(Y[b], grids[b]).squeeze()
        np.testing.assert_array_equal(res_b.masks[b], res_1.masks,
                                      err_msg=f"query {b}")
        assert (np.abs(res_b.betas[b] - res_1.betas).max()
                <= beta_err_tol(Y[b], tol)), b


def test_group_batched_dispatch_loops_with_shared_fit():
    X, Y = _problem(b=3)
    m = 4
    sess = LassoSession.fit(X, groups=m)
    grids = _grids(X, Y, num=4)
    res = sess.path(Y, grids)
    assert res.betas.shape == (3, 4, P)
    assert res.masks.shape == (3, 4, P // m)
    assert sess.fit_passes == 1                # spectral norms fitted once
    assert all(s.batch_size == 3 for s in res.stats)
    for b in range(3):
        res_1 = sess.path(Y[b], grids[b]).squeeze()
        np.testing.assert_array_equal(res.masks[b], res_1.masks,
                                      err_msg=f"query {b}")


def test_per_query_default_grids_over_own_lam_max():
    X, Y = _problem(b=3)
    sess = LassoSession.fit(X)
    res = sess.path(Y, num_lambdas=5)
    for b in range(3):
        lm = float(lambda_max(jnp.asarray(X), jnp.asarray(Y[b])))
        np.testing.assert_allclose(res.lambdas[b], lambda_grid(lm, num=5),
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# config composition + validation
# ---------------------------------------------------------------------------

def test_legacy_flat_kwargs_build_the_same_config():
    flat = PathConfig(rule="dpp", backend="jnp", solver="cd",
                      solver_backend="jnp", solver_tol=1e-9,
                      gap_check_cadence=5, kkt_tol=1e-6, paranoid=True,
                      sequential=False, bucket_min=8, max_iter=100,
                      max_kkt_rounds=3, eps=1e-7)
    spec = PathConfig(
        screen=ScreenSpec(rule="dpp", backend="jnp", sequential=False,
                          eps=1e-7, paranoid=True, kkt_tol=1e-6,
                          max_kkt_rounds=3),
        solve=SolveSpec(strategy="cd", backend="jnp", tol=1e-9,
                        max_iter=100, gap_check_cadence=5, bucket_min=8))
    assert flat == spec
    # legacy read accessors round-trip
    assert flat.rule == "dpp" and flat.solver == "cd"
    assert flat.solver_tol == 1e-9 and flat.gap_check_cadence == 5
    assert flat.bucket_min == 8 and not flat.sequential


def test_specs_validate_at_construction():
    with pytest.raises(ValueError, match="unknown screening rule"):
        ScreenSpec(rule="frobnicate")
    with pytest.raises(ValueError, match="unknown screening backend"):
        ScreenSpec(backend="cuda")
    with pytest.raises(ValueError, match="unknown solver strategy"):
        SolveSpec(strategy="newton")
    with pytest.raises(ValueError, match="tol"):
        SolveSpec(tol=0.0)
    with pytest.raises(ValueError, match="gap_check_cadence"):
        SolveSpec(gap_check_cadence=0)
    with pytest.raises(ValueError, match="eps"):
        ScreenSpec(eps=-1.0)
    with pytest.raises(TypeError, match="unknown field"):
        PathConfig(solver_tolerance=1e-9)
    with pytest.raises(ValueError, match="unknown screening rule"):
        PathConfig(rule="zzz")
    with pytest.raises(TypeError):
        PathConfig(screen="edpp")              # spec objects, not strings
    with pytest.raises(TypeError):
        LassoSession.fit(np.zeros((4, 8)), config="edpp")
    with pytest.raises(ValueError, match="divisible"):
        LassoSession.fit(np.zeros((4, 9)), groups=2)
    with pytest.raises(ValueError, match="groups must be"):
        LassoSession.fit(np.zeros((4, 8)), groups=0)   # not silently m=1
    with pytest.raises(TypeError):
        LassoSession(np.zeros((4, 8)))         # fit() is the constructor
    # the group engine only implements {edpp, strong, none}: anything else
    # would silently run group-EDPP under the wrong rule name
    with pytest.raises(ValueError, match="group sessions support"):
        LassoSession.fit(np.ones((4, 8)), groups=2,
                         config=PathConfig(rule="gap"))
    gsess = LassoSession.fit(np.ones((4, 8)), groups=2)
    with pytest.raises(ValueError, match="group sessions support"):
        gsess.path(np.ones(4), [0.1], config=PathConfig(rule="dpp"))


def test_group_sessions_solve_the_group_lasso():
    # `fista` on a group session is block FISTA, the same path as the
    # default; `cd` solves the plain Lasso and is refused
    X, Y = _problem(b=3)
    grids = _grids(X, Y, num=4)
    runs = {}
    for strategy in (None, "fista"):
        cfg = PathConfig(solve=SolveSpec(strategy=strategy, tol=1e-6))
        sess = LassoSession.fit(X, groups=4, config=cfg)
        runs[strategy] = sess.path(Y, grids)
    for attr in ("betas", "masks"):
        np.testing.assert_array_equal(getattr(runs[None], attr),
                                      getattr(runs["fista"], attr))
    with pytest.raises(ValueError, match="plain Lasso"):
        LassoSession.fit(X, groups=4,
                         config=PathConfig(solve=SolveSpec(strategy="cd")))


def test_custom_registered_solver_passes_validation():
    from repro.core import SOLVERS, register_solver
    register_solver("fista_alias", SOLVERS["fista"])
    try:
        cfg = PathConfig(solver="fista_alias")
        assert cfg.solve.strategy == "fista_alias"
    finally:
        SOLVERS.pop("fista_alias", None)


# ---------------------------------------------------------------------------
# hybrid safe+strong screening (ScreenSpec.strong)
# ---------------------------------------------------------------------------

def test_hybrid_strong_tightens_screening_and_stays_exact():
    X, Y = _problem(seed=11)
    y = Y[0]
    tol = 1e-10
    grid = _grids(X, Y[:1])[0]
    sess = LassoSession.fit(X)
    safe = sess.path(y, grid, config=PathConfig(rule="edpp",
                                                solver_tol=tol)).squeeze()
    hybrid_cfg = PathConfig(screen=ScreenSpec(rule="edpp", strong=True),
                            solve=SolveSpec(tol=tol))
    assert hybrid_cfg.hybrid_strong
    hyb = sess.path(y, grid, config=hybrid_cfg).squeeze()
    # at least as tight everywhere, exact after the KKT backstop
    for k in range(K):
        assert hyb.stats[k].n_discarded >= safe.stats[k].n_discarded
    assert np.abs(hyb.betas - safe.betas).max() <= 2 * beta_err_tol(y, tol)
    # the extra strong pass is visible in the telemetry (2 passes/screen)
    assert all(s.x_passes == 2 for s in hyb.stats if s.screen_time_s > 0)
    assert all(s.x_passes == 1 for s in safe.stats if s.screen_time_s > 0)


# ---------------------------------------------------------------------------
# mesh dispatch (single virtual device: placement + per-shard backends)
# ---------------------------------------------------------------------------

def test_mesh_session_matches_unsharded_masks():
    import jax
    X, Y = _problem()
    y = Y[0]
    mesh = jax.make_mesh((1,), ("model",))
    grid = _grids(X, Y[:1])[0]
    sess_m = LassoSession.fit(X, mesh=mesh)
    # the screen backend is the per-shard dispatcher around the default tile
    assert sess_m.backend_name.startswith("shard:")
    res_m = sess_m.path(y, grid)
    res = LassoSession.fit(X).path(y, grid)
    np.testing.assert_array_equal(res_m.masks, res.masks)
    assert res_m.stats[1].screen_backend.startswith("shard:")


def test_mesh_session_honours_explicit_backend():
    """ISSUE 7 satellite: fit(mesh=..., backend="interpret") must resolve
    the named tile under the per-shard dispatcher, not silently downgrade
    to jnp, and the resolved names must land in the per-step stats."""
    import jax
    X, Y = _problem()
    y = Y[0]
    mesh = jax.make_mesh((1,), ("model",))
    grid = _grids(X, Y[:1])[0]
    cfg = PathConfig(backend="interpret", solver_backend="interpret")
    sess_m = LassoSession.fit(X, mesh=mesh, config=cfg)
    assert sess_m.backend_name == "shard:interpret"
    res_m = sess_m.path(y, grid)
    assert res_m.stats[1].screen_backend == "shard:interpret"
    live = [s for s in res_m.stats if s.bucket]
    assert live and all(s.solver_backend == "shard:interpret" for s in live)
    res = LassoSession.fit(X, config=cfg).path(y, grid)
    np.testing.assert_array_equal(res_m.masks, res.masks)


def test_group_mesh_pins_jnp_and_raises_otherwise():
    import jax
    X, _ = _problem()
    mesh = jax.make_mesh((1,), ("model",))
    sess = LassoSession.fit(X, groups=4, mesh=mesh)
    assert sess.backend_name == "jnp"   # group GSPMD partial support
    with pytest.raises(ValueError, match="jnp backend"):
        LassoSession.fit(X, groups=4, mesh=mesh,
                         config=PathConfig(backend="pallas"))


# ---------------------------------------------------------------------------
# grid endpoints: the λ = λ_max last-bit contract (regression, hi_frac=0.95)
# ---------------------------------------------------------------------------

def test_grid_endpoint_contract_pins_hi_frac():
    """The exactness contract (docs/api.md#exactness-contract): bitwise
    mask parity between batched and single drivers is claimed for grid
    points strictly inside (0, λ_max) — pinned here via hi_frac=0.95. At
    λ ≥ λ_max the step is trivial either way (β = 0, everything
    discarded), but its live/dead classification may flip on the last bit
    of λ_max between the batched and single kernel reductions, so the
    endpoint itself is NOT part of the bitwise claim."""
    X, Y = _problem(seed=7)
    sess = LassoSession.fit(X)
    # (a) single vs batched λ_max agree to working-precision rounding, not
    # necessarily bitwise: one comes from a (1, p) reduction, the other
    # from a (B, p) one (f32 on the kernel backends — hence the 1e-6 scale)
    from repro.core import ScreeningEngine
    lm_single = float(ScreeningEngine(X, jnp.asarray(Y[:1])).lam_max[0])
    lm_batched = float(ScreeningEngine(X, jnp.asarray(Y)).lam_max[0])
    np.testing.assert_allclose(lm_single, lm_batched, rtol=1e-6)
    # (b) interior grids (hi_frac = 0.95): full bitwise parity
    grids = _grids(X, Y, hi_frac=0.95)
    assert grids.max() < 0.96 * lm_batched
    res_b = sess.path(Y, grids)
    for b in range(B):
        res_1 = sess.path(Y[b], grids[b]).squeeze()
        np.testing.assert_array_equal(res_b.masks[b], res_1.masks)
    # (c) at and above λ_max both layouts degenerate identically: β = 0,
    # everything discarded — the endpoint is trivial, just not bitwise-
    # classified the same way in every reduction order. The (p,) and
    # (B, p) reductions may disagree on λ_max's last couple of ULPs, so
    # "above" means above BOTH (a grid built from one λ_max can land a
    # hair inside the other driver's live region).
    lm_hi = max(lm_single, lm_batched)
    hi = np.array([[1.5 * lm_hi, lm_hi * (1 + 1e-12)]])
    res_hi = sess.path(Y[:1], np.repeat(hi, 1, axis=0))
    assert np.all(res_hi.betas == 0.0)
    assert res_hi.masks.all()


# ---------------------------------------------------------------------------
# byte-exact replay: reset_solver_cache + end-to-end bf16 gap parity
# ---------------------------------------------------------------------------

def test_reset_solver_cache_gives_bitwise_replay():
    """The warm-started Lipschitz cache makes solves a function of session
    HISTORY (each solve refreshes the eigenvector its bucket warm-starts
    from), so identical ``path`` calls can drift in the last float.
    ``reset_solver_cache`` restores a deterministic cold start — two calls
    from a reset cache must agree bit-for-bit, which is the property the
    benches' precision A/Bs lean on (docs/solvers.md)."""
    X, Y = _problem(seed=19)
    y = Y[0]
    grid = _grids(X, Y[:1], num=6)[0]
    cfg = PathConfig(rule="gap", solver_tol=1e-8)
    sess = LassoSession.fit(X)
    sess.path(y, grid, config=cfg)         # arbitrary history
    sess.reset_solver_cache()
    r1 = sess.path(y, grid, config=cfg).squeeze()
    sess.reset_solver_cache()
    r2 = sess.path(y, grid, config=cfg).squeeze()
    np.testing.assert_array_equal(np.asarray(r1.betas), np.asarray(r2.betas))
    np.testing.assert_array_equal(np.asarray(r1.masks), np.asarray(r2.masks))


@pytest.mark.parametrize("rule", ["gap", "gap_cut"])
def test_bf16_gap_path_masks_match_f32_end_to_end(rule):
    """Whole-path regression for the two-stage GAP fallback (exact sup
    recovery from the candidate gather + straddler re-test): with cache
    resets equalising solver history, the bf16 arm's masks must be
    bit-identical to f32 over a full sequential path — single AND batched.
    (The per-step kernel contract is covered adversarially in
    tests/test_kernels.py; this drives the engine's gather plumbing
    end-to-end, where the loose rescale-interval version banded hundreds
    of columns and history drift flipped threshold-straddling bits.)"""
    X, Y = _problem(seed=23)
    grids = _grids(X, Y, num=10)
    sess = LassoSession.fit(X)

    def arm(dtype):
        cfg = PathConfig(screen=ScreenSpec(rule=rule, screen_dtype=dtype),
                         solve=SolveSpec(tol=1e-8))
        sess.reset_solver_cache()
        single = sess.path(Y[0], grids[0], config=cfg).squeeze()
        sess.reset_solver_cache()
        batched = sess.path(Y, grids, config=cfg)
        return single, batched

    s32, b32 = arm("float32")
    s16, b16 = arm("bfloat16")
    np.testing.assert_array_equal(np.asarray(s32.masks),
                                  np.asarray(s16.masks))
    np.testing.assert_array_equal(np.asarray(b32.masks),
                                  np.asarray(b16.masks))
    # the bf16 arm really ran reduced precision + its narrow extra pass
    screened = [s for s in s16.stats if s.screen_time_s > 0]
    assert screened and all(
        s.screen_dtype_effective == "bfloat16" for s in screened)
    assert all(s.x_passes == 2 for s in screened)
