"""Incremental-dictionary subsystem tests (ISSUE 10).

The contract under test is ORACLE-REFIT EXACTNESS: after
``session.update(add=, drop=)`` the session must be indistinguishable —
bit for bit where the contract says bits, ``beta_err_tol`` where it says
tolerance — from a cold ``LassoSession.fit`` on the edited dictionary:

  * geometry carry: ``sumsq``/``col_norms``, the bf16 screen copy and its
    quantisation-error columns equal a cold fit's exactly;
  * live workspace carry: ``|Xᵀy|``, λ_max/argmax (index-aware
    tie-breaks), v₁ and the DOME halfspace direction equal a cold
    ``PathWorkspace``'s exactly, with full rescans ONLY when a query's
    argmax column content was dropped;
  * bitwise replay: ``update`` + ``reset_solver_cache()`` → ``path``
    masks bit-identical to the cold fit's and Δβ = 0 (the eig cache is
    the one cache that intentionally survives an update — warm Lipschitz
    starts are the speedup — so the replay recipe resets it);
  * cache accounting: eig-cache warm starts keep hitting across
    versions, ``reset_solver_cache`` forces the next solves cold, and
    ``PathStepStats.geometry_version`` stamps which dictionary each step
    ran against;
  * serving: ``DispatchRecord.version`` attributes each dispatched batch
    to the dictionary version it actually ran on, across an update
    landing mid-trace;
  * buffer ownership: the first update copies (references captured
    before it stay valid), later updates donate (the old buffers are
    deleted) — see the two-phase note in core/engine.py.

Edit cases cover every layout branch: pure recycle (balanced), add-only,
drop-only, mixed both directions, and argmax-dropped, on the jnp and
interpret backends; a subprocess checks 1×2 mesh parity.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    LassoSession,
    PathConfig,
    PathWorkspace,
    carry_mask,
    make_plan,
    update_workspace,
)
from repro.launch import serve_loop as sl

BACKENDS = ["jnp", "interpret"]

N, P, B = 32, 64, 4


def _tol(y, tol, kappa=25.0):
    # benchmarks/common.beta_err_tol without importing the bench package
    return kappa * float(tol) * float(np.linalg.norm(np.asarray(y)))


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(N, P)).astype(np.float32)
    X /= np.linalg.norm(X, axis=0, keepdims=True)
    y = rng.normal(size=N).astype(np.float32)
    y /= np.linalg.norm(y)
    Y = rng.normal(size=(B, N)).astype(np.float32)
    Y /= np.linalg.norm(Y, axis=1, keepdims=True)
    add = rng.normal(size=(N, 3)).astype(np.float32)
    add /= np.linalg.norm(add, axis=0, keepdims=True)
    add7 = rng.normal(size=(N, 7)).astype(np.float32)
    add7 /= np.linalg.norm(add7, axis=0, keepdims=True)
    return X, y, Y, add, add7


def edited_oracle(Xh, drop, add):
    """The recycle-layout oracle: adds overwrite the first dropped slots
    in place, residual drops compact, residual adds append."""
    d = (np.unique(np.asarray(drop, dtype=np.int64))
         if drop is not None else np.zeros(0, np.int64))
    a = (np.asarray(add, np.float32)
         if add is not None else np.zeros((Xh.shape[0], 0), np.float32))
    k = min(a.shape[1], d.size)
    Xp = Xh.copy()
    if k:
        Xp[:, d[:k]] = a[:, :k]
    keep = np.setdiff1d(np.arange(Xh.shape[1]), d[k:])
    return np.concatenate([Xp[:, keep], a[:, k:]], axis=1)


def _fit(X, cfg):
    sess = LassoSession.fit(X, config=cfg)
    sess.geometry.screen_copy(jnp.bfloat16)
    sess.geometry.screen_err(jnp.bfloat16)
    return sess


def _bitwise(a, b, what):
    assert np.array_equal(np.asarray(a), np.asarray(b)), what


CASES = {
    "pure-recycle": (lambda p: ([3, 17, 50], "add")),
    "add-only": (lambda p: (None, "add")),
    "drop-only": (lambda p: ([0, 9, p - 1], None)),
    "mixed-add-gt-drop": (lambda p: ([5, 40], "add7")),
    "mixed-drop-gt-add": (lambda p: ([2, 11, 23, 31, 44, 59], "add")),
}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", list(CASES))
def test_oracle_refit_exactness(problem, backend, case):
    """The acceptance contract, per edit case × backend: geometry and
    workspace carry bitwise, then update + reset_solver_cache replays the
    cold fit's path bit-for-bit with Δβ = 0."""
    X, y, Y, add3, add7 = problem
    drop, which = CASES[case](P)
    add = {None: None, "add": add3, "add7": add7}[which]
    cfg = PathConfig(backend=backend, solver_backend=backend,
                     solver_tol=1e-8)

    sess = _fit(X, cfg)
    geom = sess.geometry
    ws = PathWorkspace(None, y[None], geometry=geom)     # a batch of one
    wsb = PathWorkspace(None, Y, geometry=geom)
    rep = sess.update(add=add, drop=drop, workspaces=[ws, wsb])

    X_ed = edited_oracle(X, drop, add)
    _bitwise(sess.X, X_ed, "edited X deviates from the layout oracle")
    assert rep.version == sess.version == 1
    assert rep.p == X_ed.shape[1]
    assert rep.workspaces_updated == 2

    cold = _fit(X_ed, cfg)
    cg = cold.geometry
    _bitwise(geom.sumsq, cg.sumsq, "sumsq")
    _bitwise(geom.col_norms, cg.col_norms, "col_norms")
    _bitwise(geom.screen_copy(jnp.bfloat16), cg.screen_copy(jnp.bfloat16),
             "bf16 screen copy")
    _bitwise(geom.screen_err(jnp.bfloat16), cg.screen_err(jnp.bfloat16),
             "bf16 screen err")

    cws = PathWorkspace(None, y[None], geometry=cg)
    cwsb = PathWorkspace(None, Y, geometry=cg)
    for carried, fresh, tag in [(ws, cws, "single"), (wsb, cwsb, "batched")]:
        _bitwise(carried.abs_xty, fresh.abs_xty, f"{tag} |Xᵀy|")
        _bitwise(carried.istar, fresh.istar, f"{tag} argmax")
        _bitwise(carried.lam_max, fresh.lam_max, f"{tag} λ_max")
        _bitwise(carried.v1_at_lmax, fresh.v1_at_lmax, f"{tag} v1")
        _bitwise(carried.ghat, fresh.ghat, f"{tag} ghat")

    sess.reset_solver_cache()
    ru = sess.path(Y, num_lambdas=4, config=cfg)
    rc = cold.path(Y, num_lambdas=4, config=cfg)
    _bitwise(ru.masks, rc.masks, "post-update masks vs cold-refit oracle")
    db = float(np.abs(np.asarray(ru.betas) - np.asarray(rc.betas)).max())
    assert db == 0.0, f"bitwise replay drifted: max|Δβ|={db}"


@pytest.mark.parametrize("balanced", [True, False],
                         ids=["recycled", "drop-only"])
def test_argmax_dropped_rescans(problem, balanced):
    """Dropping a query's argmax column forces (exactly) that query's
    full candidate rescan; the result still matches a cold workspace."""
    X, y, Y, add3, _ = problem
    cfg = PathConfig(backend="jnp", solver_backend="jnp", solver_tol=1e-8)
    sess = _fit(X, cfg)
    ws = PathWorkspace(None, y[None], geometry=sess.geometry)
    istar = int(ws.istar[0])
    drop = [istar, (istar + 1) % P, (istar + 2) % P] if balanced \
        else [istar]
    rep = sess.update(add=add3 if balanced else None, drop=drop,
                      workspaces=[ws])
    assert rep.argmax_rescans >= 1
    X_ed = edited_oracle(X, drop, add3 if balanced else None)
    cws = PathWorkspace(None, y[None],
                        geometry=LassoSession.fit(X_ed, config=cfg).geometry)
    _bitwise(ws.abs_xty, cws.abs_xty, "|Xᵀy| after argmax drop")
    _bitwise(ws.istar, cws.istar, "argmax after argmax drop")
    _bitwise(ws.lam_max, cws.lam_max, "λ_max after argmax drop")


def test_balanced_update_skips_rescan(problem):
    """A balanced edit away from the argmax touches only the edited
    slots: no rescan, and λ_max survives by carry, not recompute."""
    X, y, _, add3, _ = problem
    cfg = PathConfig(backend="jnp", solver_backend="jnp")
    sess = _fit(X, cfg)
    ws = PathWorkspace(None, y[None], geometry=sess.geometry)
    istar = int(ws.istar[0])
    drop = sorted({(istar + j) % P for j in (1, 2, 3)})
    rep = sess.update(add=add3, drop=drop, workspaces=[ws])
    assert rep.argmax_rescans == 0


def test_sequential_updates_compound(problem):
    """Three stacked edits (the 2nd+ take the donated in-place patch
    path) still land bit-identically on a cold fit of the final X."""
    X, y, Y, add3, _ = problem
    rng = np.random.default_rng(11)
    cfg = PathConfig(backend="jnp", solver_backend="jnp", solver_tol=1e-8)
    sess = _fit(X, cfg)
    ws = PathWorkspace(None, Y, geometry=sess.geometry)
    X_ed = X
    for step in range(3):
        drop = np.sort(rng.choice(X_ed.shape[1], size=3, replace=False))
        add = rng.normal(size=(N, 3)).astype(np.float32)
        add /= np.linalg.norm(add, axis=0, keepdims=True)
        sess.update(add=add, drop=drop, workspaces=[ws])
        X_ed = edited_oracle(X_ed, drop, add)
    assert sess.version == 3
    _bitwise(sess.X, X_ed, "stacked edits deviate from the oracle")
    cold = _fit(X_ed, cfg)
    _bitwise(sess.geometry.screen_err(jnp.bfloat16),
             cold.geometry.screen_err(jnp.bfloat16), "stacked bf16 err")
    sess.reset_solver_cache()
    ru = sess.path(Y, num_lambdas=4, config=cfg)
    rc = cold.path(Y, num_lambdas=4, config=cfg)
    _bitwise(ru.masks, rc.masks, "stacked-edit masks")
    assert float(np.abs(np.asarray(ru.betas)
                        - np.asarray(rc.betas)).max()) == 0.0


def test_two_phase_buffer_ownership(problem):
    """First update copies — references captured at fit time stay valid;
    the second update donates the geometry's buffers (deleted arrays)."""
    X, y, _, add3, _ = problem
    cfg = PathConfig(backend="jnp", solver_backend="jnp")
    sess = _fit(X, cfg)
    x_fit = sess.geometry.X
    sess.update(add=add3, drop=[1, 2, 3])
    _bitwise(x_fit, X, "fit-time X must survive the first (copy) update")
    x_v1 = sess.geometry.X
    assert sess.geometry._owns_buffers
    sess.update(add=add3, drop=[4, 5, 6])
    assert x_v1.is_deleted(), \
        "second update should donate the geometry's buffers"
    _bitwise(sess.X, edited_oracle(edited_oracle(X, [1, 2, 3], add3),
                                   [4, 5, 6], add3), "post-donation X")


def test_path_stats_record_geometry_version(problem):
    X, y, _, add3, _ = problem
    cfg = PathConfig(backend="jnp", solver_backend="jnp")
    sess = _fit(X, cfg)
    r0 = sess.path(y, num_lambdas=3, config=cfg)
    assert all(s.geometry_version == 0 for s in r0.stats)
    sess.update(add=add3, drop=[7, 8, 9])
    r1 = sess.path(y, num_lambdas=3, config=cfg)
    assert all(s.geometry_version == 1 for s in r1.stats)


def test_eig_cache_warm_across_update(problem):
    """Warm Lipschitz starts keep hitting after an edit (the carry that
    makes updates cheap); reset_solver_cache forces the next path cold."""
    X, y, _, add3, _ = problem
    cfg = PathConfig(backend="jnp", solver_backend="jnp")
    sess = _fit(X, cfg)
    sess.path(y, num_lambdas=4, config=cfg)
    s0 = sess.eig_cache_stats
    assert s0["cold"] > 0
    sess.update(add=add3, drop=[3, 4, 5])
    sess.path(y, num_lambdas=4, config=cfg)
    s1 = sess.eig_cache_stats
    assert s1["warm"] > s0["warm"], \
        "post-update solves should warm-start from cached eigenvectors"
    sess.reset_solver_cache()
    sess.path(y, num_lambdas=4, config=cfg)
    s2 = sess.eig_cache_stats
    assert s2["cold"] > s1["cold"], \
        "reset_solver_cache should force cold power iterations"


def test_update_workspace_requires_updated_geometry(problem):
    X, y, _, add3, _ = problem
    sess = _fit(X, PathConfig(backend="jnp"))
    ws = PathWorkspace(None, y[None], geometry=sess.geometry)
    plan, X_add = make_plan(P, add=None, drop=[0, 1])  # p shrinks by 2
    with pytest.raises(ValueError, match="update the geometry first"):
        update_workspace(ws, plan, X_add)


def test_make_plan_validation():
    with pytest.raises(ValueError, match="add= and/or drop="):
        make_plan(10)
    with pytest.raises(ValueError, match="out of range"):
        make_plan(10, drop=[10])
    with pytest.raises(ValueError, match="integer"):
        make_plan(10, drop=[0.5])
    with pytest.raises(ValueError, match="empty dictionary"):
        make_plan(3, drop=[0, 1, 2])
    with pytest.raises(ValueError, match=r"\(n, p_add\)"):
        make_plan(10, add=np.zeros(4))
    plan, _ = make_plan(10, add=np.zeros((4, 3)), drop=[2, 7])
    assert plan.pure_recycle is False and plan.n_recycle == 2
    assert plan.n_append == 1 and plan.p_new == 11
    assert list(plan.recycle_idx) == [2, 7]
    assert list(plan.touched_new_idx) == [2, 7, 10]


def test_session_update_rejects_bad_add(problem):
    X, _, _, _, _ = problem
    sess = LassoSession.fit(X, config=PathConfig(backend="jnp"))
    with pytest.raises(ValueError, match=f"n={N}"):
        sess.update(add=np.zeros((N + 1, 2), np.float32), drop=[0, 1])


def test_session_update_rejects_groups():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(16, 24)).astype(np.float32)
    sess = LassoSession.fit(X, groups=4)
    with pytest.raises(NotImplementedError, match="plain-Lasso only"):
        sess.update(drop=[0])


def test_carry_mask_semantics():
    plan, _ = make_plan(10, add=np.zeros((4, 3)), drop=[2, 7])
    m = np.arange(10) % 2 == 0          # True = discarded
    cm = carry_mask(m, plan)
    assert cm.shape == (11,)
    assert not cm[2] and not cm[7]      # recycled slots: new content,
    assert not cm[10]                   # unscreened, like the appended tail
    assert cm[0] and not cm[1] and cm[4]
    ni = plan.new_index(np.array([0, 2, 5, 7, 9]))
    assert list(ni) == [0, -1, 5, -1, 9]
    # batched masks carry along the last axis
    cb = carry_mask(np.stack([m, ~m]), plan)
    assert cb.shape == (2, 11) and not cb[:, 2].any()


def test_carry_mask_exact_for_inactive_drops(problem):
    """Dropping columns that were screened out leaves the dual optimum —
    hence every survivor's screen decision — unchanged: the carried mask
    IS the cold-refit mask, bit for bit."""
    X, y, _, _, _ = problem
    cfg = PathConfig(backend="jnp", solver_backend="jnp", solver_tol=1e-8)
    sess = LassoSession.fit(X, config=cfg)
    lam_max = float(sess.path(y, num_lambdas=2, config=cfg).lambdas[0, 0])
    grid = np.array([0.9, 0.7, 0.5]) * lam_max
    masks = np.asarray(sess.path(y, lambdas=grid, config=cfg).masks)[0]
    always_out = np.flatnonzero(masks.all(axis=0))
    assert always_out.size >= 3, "problem too easy to screen — retune"
    drop = always_out[:3].tolist()
    plan, _ = make_plan(P, drop=drop)
    carried = carry_mask(masks, plan)
    cold = LassoSession.fit(edited_oracle(X, drop, None), config=cfg)
    _bitwise(carried,
             np.asarray(cold.path(y, lambdas=grid, config=cfg).masks)[0],
             "carried mask vs cold refit (inactive drops)")


def test_serve_loop_tickets_span_update(problem):
    """A dictionary update landing between dispatches: each
    DispatchRecord carries the version its batch actually ran against,
    and both tickets retire with finite results."""
    X, _, Y, add3, _ = problem
    sess = LassoSession.fit(
        X, config=PathConfig(backend="jnp", solver_backend="jnp"))
    ex = sl.SessionExecutor(sess, num_lambdas=4)
    arrivals = sl.ScriptedArrivals([(0.0, Y[0]), (5.0, Y[1])])
    versions = []

    def after(ticket):
        if not versions:        # first retirement → edit the dictionary
            sess.update(add=add3, drop=[0, 1, 2])
        versions.append(sess.version)

    loop = sl.ServeLoop(arrivals, ex,
                        policy=sl.ServePolicy(b_max=4, deadline_s=0.5,
                                              queue_cap=8),
                        clock=sl.VirtualClock(), on_complete=after)
    rep = loop.run()
    assert [r.version for r in rep.trace] == [0, 1]
    assert versions == [1, 1]
    assert all(t.error is None for t in rep.tickets)


MESH_PARITY_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import LassoSession, PathConfig, PathWorkspace

rng = np.random.default_rng(5)
n, p, B = 32, 64, 4
X = rng.normal(size=(n, p)).astype(np.float32)
X /= np.linalg.norm(X, axis=0, keepdims=True)
Y = rng.normal(size=(B, n)).astype(np.float32)
Y /= np.linalg.norm(Y, axis=1, keepdims=True)
add = rng.normal(size=(n, 4)).astype(np.float32)
add /= np.linalg.norm(add, axis=0, keepdims=True)
drop = [3, 17, 40, 55]                      # balanced: p stays 64 (÷2)

cfg = PathConfig(backend="jnp", solver_backend="jnp", solver_tol=1e-8)
mesh = jax.make_mesh((1, 2), ("query", "feature"))
sess_m = LassoSession.fit(X, mesh=mesh, config=cfg)
sess_m.update(add=add, drop=drop)

X_ed = X.copy(); X_ed[:, drop] = add        # pure recycle
assert np.array_equal(np.asarray(sess_m.X), X_ed), "mesh edited X"
cold = LassoSession.fit(X_ed, config=cfg)
sess_m.reset_solver_cache()
rm = sess_m.path(Y, num_lambdas=4, config=cfg)
rc = cold.path(Y, num_lambdas=4, config=cfg)
assert np.array_equal(np.asarray(rm.masks), np.asarray(rc.masks)), \
    "mesh post-update masks diverged from the unsharded cold refit"
berr = float(np.abs(np.asarray(rm.betas) - np.asarray(rc.betas)).max())
tol = 25.0 * 1e-8 * float(np.linalg.norm(Y[0]))
assert berr <= tol, (berr, tol)

# shard-divisibility guard: an edit leaving p % fsize != 0 must refuse
try:
    sess_m.update(drop=[0])
except ValueError as e:
    assert "divisible" in str(e), e
else:
    raise AssertionError("odd p on a 1x2 mesh should have been rejected")
print("MESH_UPDATE_PARITY_OK")
"""


@pytest.mark.slow
def test_mesh_update_parity(subproc):
    """ISSUE 10 acceptance: update on a 1×2 ('query', 'feature') mesh
    matches the unsharded cold refit bit-for-bit on masks, β within
    tolerance, and rejects shard-indivisible edits."""
    out = subproc(MESH_PARITY_CODE, devices=2)
    assert "MESH_UPDATE_PARITY_OK" in out


# ---------------------------------------------------------------------------
# satellite: bf16 Gram build for the cd strategy
# ---------------------------------------------------------------------------

def test_cd_bf16_gram_records_effective_dtype(problem):
    """solve_dtype='bfloat16' with strategy='cd' streams the Gram build
    off the bf16 dictionary copy (no fall-back warning) and records the
    effective dtype, while masks and β stay on the f32 contract."""
    import warnings

    X, y, Y, _, _ = problem
    kw = dict(backend="jnp", solver_backend="jnp", solver_tol=1e-8,
              solver="cd")
    cfg32 = PathConfig(**kw)
    cfg16 = PathConfig(solve_dtype="bfloat16", **kw)
    sess = _fit(X, PathConfig(**kw))
    r32 = sess.path(y, num_lambdas=4, config=cfg32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # the old path warned here
        r16 = sess.path(y, num_lambdas=4, config=cfg16)
        rb16 = sess.path(Y, num_lambdas=4, config=cfg16)
    live = [s for s in r16.stats if s.solve_dtype_effective is not None]
    assert live and any(s.solve_dtype_effective == "bfloat16" for s in live)
    _bitwise(r16.masks, r32.masks, "cd bf16 masks vs f32")
    tol = _tol(y, 1e-8)
    assert float(np.abs(np.asarray(r16.betas)
                        - np.asarray(r32.betas)).max()) <= tol
    rb32 = sess.path(Y, num_lambdas=4, config=cfg32)
    _bitwise(rb16.masks, rb32.masks, "batched cd bf16 masks vs f32")
    assert float(np.abs(np.asarray(rb16.betas)
                        - np.asarray(rb32.betas)).max()) <= max(
        _tol(Y[b], 1e-8) for b in range(B))
