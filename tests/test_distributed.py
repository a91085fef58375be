"""Distributed lasso (shard_map) correctness on 8 virtual devices.

Subprocess-based: jax pins the device count at first init, and the main
pytest process must stay at 1 device for the smoke tests (assignment brief).
"""

import pytest

CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as D
from repro.core import lambda_max, edpp_mask, DualState, fista

mesh = D.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
N, p = 64, 512
X = rng.standard_normal((N, p)).astype(np.float32)
bt = np.zeros(p); nz = rng.choice(p, 12, replace=False)
bt[nz] = rng.uniform(-1, 1, 12)
y = (X @ bt + 0.1 * rng.standard_normal(N)).astype(np.float32)

Xd, yd = D.shard_problem(mesh, X, y)
lmax_d, matvec_d, screen_d, sup_d = D.make_dist_ops(mesh)
lm = float(lmax_d(Xd, yd))
lm_ref = float(lambda_max(jnp.asarray(X), jnp.asarray(y)))
assert abs(lm - lm_ref) < 1e-3

corr = X.T @ y; istar = np.argmax(np.abs(corr))
v1max = jnp.asarray(np.sign(corr[istar]) * X[:, istar])
beta0d = jax.device_put(jnp.zeros(p, jnp.float32), D.beta_sharding(mesh))
mask, scores = D.dist_edpp_screen(mesh, Xd, yd, 0.5 * lm, lm, beta0d, lm, v1max)
st = DualState.at_lambda_max(jnp.asarray(X), jnp.asarray(y))
ref_mask = edpp_mask(jnp.asarray(X), jnp.asarray(y), 0.5 * lm, st)
np.testing.assert_array_equal(np.asarray(mask), np.asarray(ref_mask))

L = D.dist_power_iteration(mesh, Xd) * 1.05
ref = fista(jnp.asarray(X), jnp.asarray(y), 0.3 * lm,
            max_iter=4000, tol=1e-10).beta
for mode, tol in [("none", 5e-5), ("chunked", 5e-5)]:
    b = D.dist_fista(mesh, Xd, yd, 0.3 * lm, beta0d, L, iters=500,
                     overlap=mode)
    err = float(np.abs(np.asarray(b) - np.asarray(ref)).max())
    assert err < tol, (mode, err)
print("DIST_OK")
"""

MULTIPOD_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as D
from repro.core import lambda_max
mesh = D.make_mesh((2, 2, 2), ("pod", "data", "model"))
rng = np.random.default_rng(1)
N, p = 32, 256
X = rng.standard_normal((N, p)).astype(np.float32)
y = rng.standard_normal(N).astype(np.float32)
Xd, yd = D.shard_problem(mesh, X, y)
lmax_d, *_ = D.make_dist_ops(mesh)
assert abs(float(lmax_d(Xd, yd))
           - float(lambda_max(jnp.asarray(X), jnp.asarray(y)))) < 1e-3
print("POD_OK")
"""


@pytest.mark.slow
def test_distributed_matches_local(subproc):
    out = subproc(CODE, devices=8)
    assert "DIST_OK" in out


@pytest.mark.slow
def test_multipod_mesh(subproc):
    out = subproc(MULTIPOD_CODE, devices=8)
    assert "POD_OK" in out


BATCHED_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as D
from repro.core import lambda_max, edpp_mask, make_dual_state, fista

mesh = D.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(2)
N, p, B = 48, 512, 4
X = rng.standard_normal((N, p)).astype(np.float32)
Y = np.stack([
    (X[:, rng.choice(p, 8, replace=False)] @ rng.uniform(-1, 1, 8)
     + 0.1 * rng.standard_normal(N)).astype(np.float32)
    for _ in range(B)])
Xd, _ = D.shard_problem(mesh, X, Y[0])
Yd = jax.device_put(jnp.asarray(Y), D.replicated(mesh))

corr = Y @ X                                # (B, p)
istar = np.argmax(np.abs(corr), axis=-1)
lmax = np.abs(corr)[np.arange(B), istar]
v1max = jnp.asarray(np.sign(corr[np.arange(B), istar])[:, None]
                    * X[:, istar].T)
col_norms = jax.device_put(jnp.linalg.norm(jnp.asarray(X), axis=0),
                           D.beta_sharding(mesh))
beta0 = jax.device_put(jnp.zeros((B, p), jnp.float32),
                       jax.sharding.NamedSharding(
                           mesh, jax.sharding.PartitionSpec(
                               None, D.feature_axes(mesh))))

lam_prev = jnp.asarray(lmax, jnp.float32)
lam_next = 0.5 * lam_prev
mask, scores = D.dist_edpp_screen_batched(
    mesh, Xd, Yd, lam_next, lam_prev, beta0, jnp.asarray(lmax), v1max,
    col_norms)
# per-query parity vs the single-query jnp oracle
for b in range(B):
    st = make_dual_state(jnp.asarray(X), jnp.asarray(Y[b]),
                         jnp.zeros(p), float(lam_prev[b]), float(lmax[b]))
    ref = edpp_mask(jnp.asarray(X), jnp.asarray(Y[b]), float(lam_next[b]), st)
    np.testing.assert_array_equal(np.asarray(mask[b]), np.asarray(ref))

# batched distributed FISTA vs per-query single-chip solves
L = 1.05 * float(np.linalg.norm(X, 2) ** 2)
lam = jnp.asarray(0.3 * lmax, jnp.float32)
beta_b = D.dist_fista_batched(mesh, Xd, Yd, lam, beta0, L, iters=600)
for b in range(B):
    ref = fista(jnp.asarray(X), jnp.asarray(Y[b]), float(lam[b]),
                max_iter=4000, tol=1e-10).beta
    err = float(np.abs(np.asarray(beta_b[b]) - np.asarray(ref)).max())
    assert err < 1e-4, (b, err)
print("BATCH_DIST_OK")
"""


@pytest.mark.slow
def test_distributed_batched_matches_per_query(subproc):
    """Batched multi-query screen+solve on the mesh: one (B, N) psum per
    step, per-query results identical to the single-query references."""
    out = subproc(BATCHED_CODE, devices=8)
    assert "BATCH_DIST_OK" in out


SHARD_PARITY_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as D
from repro.core.session import LassoSession, PathConfig

def beta_err_tol(y, solver_tol, kappa=25.0):
    return kappa * float(np.sqrt(solver_tol * 0.5 * np.dot(y, y)))

rng = np.random.default_rng(11)
n, p, B = 48, 256, 4
X = rng.standard_normal((n, p)).astype(np.float32)
Y = np.stack([
    (X[:, rng.choice(p, 8, replace=False)] @ rng.uniform(-1, 1, 8)
     + 0.1 * rng.standard_normal(n)).astype(np.float32)
    for _ in range(B)])
tol = 1e-8
grids = np.stack([
    np.linspace(0.95, 0.1, 8) * float(np.max(np.abs(X.T @ Y[b])))
    for b in range(B)])                     # hi_frac=0.95: inside (0, λmax)

for tile in ("jnp", "interpret"):
    cfg = PathConfig(backend=tile, solver_backend=tile, solver_tol=tol)
    ref = LassoSession.fit(X, config=cfg)
    r0 = ref.path(Y, grids)
    r0_single = ref.path(Y[0], grids[0])
    for q, f in [(1, 1), (1, 2), (2, 2), (1, 8)]:
        mesh = D.make_mesh((q, f), ("query", "feature"))
        sess = LassoSession.fit(X, mesh=mesh, config=cfg)
        assert sess.backend_name == f"shard:{tile}", sess.backend_name
        r = sess.path(Y, grids)
        assert np.array_equal(np.asarray(r.masks), np.asarray(r0.masks)), \
            (tile, q, f, "batched masks diverged")
        berr = float(np.max(np.abs(np.asarray(r.betas)
                                   - np.asarray(r0.betas))))
        assert berr <= beta_err_tol(Y[0], tol), (tile, q, f, berr)
        r1 = sess.path(Y[0], grids[0])       # single-query driver too
        assert np.array_equal(np.asarray(r1.masks),
                              np.asarray(r0_single.masks)), \
            (tile, q, f, "single masks diverged")
        assert r.stats[1].screen_backend == f"shard:{tile}"
    print(f"SHARD_PARITY_{tile}_OK")
"""


@pytest.mark.slow
def test_sharded_session_mask_parity_sweep(subproc):
    """ISSUE 7 acceptance: the session on every tested mesh shape —
    {1×1, 1×2, 2×2, 1×8} over ('query', 'feature') — produces masks
    bit-identical to the unsharded engine and β within the solver-tol
    bound, with the per-shard tile dispatcher resolved from the configured
    backend (jnp AND interpret tiles)."""
    out = subproc(SHARD_PARITY_CODE, devices=8)
    assert "SHARD_PARITY_jnp_OK" in out
    assert "SHARD_PARITY_interpret_OK" in out


BF16_CUT_PARITY_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as D
from repro.core.session import LassoSession, PathConfig

rng = np.random.default_rng(13)
n, p, B = 48, 256, 4
X = rng.standard_normal((n, p)).astype(np.float32)
Y = np.stack([
    (X[:, rng.choice(p, 8, replace=False)] @ rng.uniform(-1, 1, 8)
     + 0.1 * rng.standard_normal(n)).astype(np.float32)
    for _ in range(B)])
grids = np.stack([
    np.linspace(0.95, 0.1, 8) * float(np.max(np.abs(X.T @ Y[b])))
    for b in range(B)])

for tile in ("jnp", "interpret"):
    kw = dict(backend=tile, solver_backend=tile, solver_tol=1e-8)
    r0 = LassoSession.fit(X, config=PathConfig(**kw)).path(Y, grids)
    cfg16 = PathConfig(screen_dtype="bfloat16", **kw)
    cfg_gap16 = PathConfig(rule="gap", screen_dtype="bfloat16", **kw)
    cfg_cut = PathConfig(rule="gap_cut", **kw)
    r_gap = LassoSession.fit(X, config=PathConfig(rule="gap", **kw)).path(
        Y, grids)
    r_cut0 = LassoSession.fit(X, config=cfg_cut).path(Y, grids)
    for q, f in [(1, 2), (2, 2), (1, 8)]:
        mesh = D.make_mesh((q, f), ("query", "feature"))
        # bf16 screen copy on the mesh: the narrow f32 fallback re-gathers
        # sharded columns, masks must equal the f32 UNSHARDED session's
        r16 = LassoSession.fit(X, mesh=mesh, config=cfg16).path(Y, grids)
        assert np.array_equal(np.asarray(r16.masks), np.asarray(r0.masks)), \
            (tile, q, f, "bf16 mesh masks diverged from f32 unsharded")
        # bf16 GAP adds the exact-sup candidate gather before the margin
        # combine — both narrow gathers must shard-map cleanly too
        rg16 = LassoSession.fit(X, mesh=mesh, config=cfg_gap16).path(Y, grids)
        assert np.array_equal(np.asarray(rg16.masks),
                              np.asarray(r_gap.masks)), \
            (tile, q, f, "bf16 gap mesh masks diverged from f32 unsharded")
        # gap_cut on the mesh: bit-identical to unsharded gap_cut AND a
        # discard superset of plain gap (ball ∩ half-space ⊆ ball)
        r_cut = LassoSession.fit(X, mesh=mesh, config=cfg_cut).path(Y, grids)
        assert np.array_equal(np.asarray(r_cut.masks),
                              np.asarray(r_cut0.masks)), \
            (tile, q, f, "gap_cut mesh masks diverged")
        mg, mc = np.asarray(r_gap.masks), np.asarray(r_cut.masks)
        assert np.all(mc | ~mg), (tile, q, f, "cut lost a gap discard")
    print(f"BF16_CUT_PARITY_{tile}_OK")
"""


@pytest.mark.slow
def test_sharded_bf16_and_cut_mask_parity(subproc):
    """Mixed-precision + half-space cuts on the mesh: bfloat16 screen
    copies keep masks bit-identical to the unsharded f32 session on every
    tested mesh shape, and gap_cut masks are shard-invariant and a
    superset of gap's (jnp AND interpret tiles)."""
    out = subproc(BF16_CUT_PARITY_CODE, devices=8)
    assert "BF16_CUT_PARITY_jnp_OK" in out
    assert "BF16_CUT_PARITY_interpret_OK" in out


SOLVE_DTYPE_PARITY_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as D
from repro.core.session import LassoSession, PathConfig

def beta_err_tol(y, solver_tol, kappa=25.0):
    return kappa * float(np.sqrt(solver_tol * 0.5 * np.dot(y, y)))

rng = np.random.default_rng(17)
n, p, B = 48, 256, 4
X = rng.standard_normal((n, p)).astype(np.float32)
Y = np.stack([
    (X[:, rng.choice(p, 8, replace=False)] @ rng.uniform(-1, 1, 8)
     + 0.1 * rng.standard_normal(n)).astype(np.float32)
    for _ in range(B)])
tol = 1e-6
grids = np.stack([
    np.linspace(0.95, 0.1, 8) * float(np.max(np.abs(X.T @ Y[b])))
    for b in range(B)])

kw = dict(backend="jnp", solver_backend="jnp", solver_tol=tol)
r0 = LassoSession.fit(X, config=PathConfig(**kw)).path(Y, grids)
r0_single = LassoSession.fit(X, config=PathConfig(**kw)).path(Y[0], grids[0])
cfg16 = PathConfig(solve_dtype="bfloat16", **kw)
for q, f in [(1, 2), (2, 2), (1, 8)]:
    mesh = D.make_mesh((q, f), ("query", "feature"))
    sess = LassoSession.fit(X, mesh=mesh, config=cfg16)
    r = sess.path(Y, grids)
    # the gap certificates stream f32 X, so the bf16 iteration stream must
    # land inside the same tol ball: post-KKT masks bit-identical to the
    # f32 UNSHARDED session, β within the solver-tol bound
    assert np.array_equal(np.asarray(r.masks), np.asarray(r0.masks)), \
        (q, f, "bf16-solve mesh masks diverged from f32 unsharded")
    berr = float(np.max(np.abs(np.asarray(r.betas) - np.asarray(r0.betas))))
    assert berr <= beta_err_tol(Y[0], tol), (q, f, berr)
    r1 = sess.path(Y[0], grids[0])
    assert np.array_equal(np.asarray(r1.masks),
                          np.asarray(r0_single.masks)), \
        (q, f, "bf16-solve single masks diverged")
    # telemetry: solves ran the bf16 stream, screens stayed f32
    st = [s for s in r.stats if s.solver_iters > 0]
    assert st and all(s.solve_dtype_effective == "bfloat16" for s in st), \
        (q, f, [s.solve_dtype_effective for s in r.stats])
    assert sum(s.solver_lo_iters for s in st) > 0, (q, f, "no lo iters")
    assert all(s.screen_dtype_effective == "float32" for s in r.stats)
print("SOLVE_DTYPE_PARITY_OK")
"""


@pytest.mark.slow
def test_sharded_solve_dtype_bf16_parity(subproc):
    """ISSUE 9 acceptance on the mesh: solve_dtype="bfloat16" sessions on
    {1×2, 2×2, 1×8} meshes keep post-KKT masks bit-identical to the
    unsharded f32 session and β within the solver-tol bound — the bf16
    iteration stream is re-gathered per shard while every gap certificate
    streams the f32 shards."""
    out = subproc(SOLVE_DTYPE_PARITY_CODE, devices=8)
    assert "SOLVE_DTYPE_PARITY_OK" in out


KERNELS_UNDER_SHARD_MAP_CODE = r"""
import numpy as np, jax
from repro.core import distributed as D
from repro.core.session import LassoSession, PathConfig
from repro.kernels import ops

calls = []

def spy(op, fn):
    def call(*a, **k):
        calls.append((op, jax.sharding.get_abstract_mesh().manual_axes))
        return fn(*a, **k)
    return call

tile = ops.BACKENDS["interpret"]
spied = tile._replace(**{op: spy(op, getattr(tile, op)) for op in (
    "matvec", "fused_scores", "fista_step", "cd_gram_sweep")})
rng = np.random.default_rng(3)
n, p, B = 32, 256, 4
X = rng.standard_normal((n, p)).astype(np.float32)
Y = np.stack([(X[:, :6] @ rng.uniform(-1, 1, 6)).astype(np.float32)
              for _ in range(B)])
mesh = D.make_mesh((2, 2), ("query", "feature"))
for solver in ("fista", "cd"):
    cfg = PathConfig(backend=spied, solver_backend=spied, solver=solver)
    sess = LassoSession.fit(X, mesh=mesh, config=cfg)
    res = sess.path(Y, num_lambdas=4, lo_frac=0.3, hi_frac=0.95)
    live = [s for s in res.stats if s.bucket]
    assert live and all(s.solver_backend == "shard:interpret"
                        for s in live), [s.solver_backend for s in live]
    sess.path(Y[0], num_lambdas=4, lo_frac=0.3, hi_frac=0.95)
used = {op for op, _ in calls}
assert used == {"matvec", "fused_scores", "fista_step", "cd_gram_sweep"}, used
outside = sorted({op for op, axes in calls
                  if set(axes) != {"query", "feature"}})
assert not outside, f"kernels traced outside shard_map: {outside}"
print("KERNELS_UNDER_SHARD_MAP_OK")
"""


def test_mesh_session_runs_every_kernel_under_shard_map(subproc):
    """A compiled Pallas (Mosaic) kernel in a program that spans several
    devices must sit inside a shard_map over every mesh axis: the compiler
    cannot partition it, even on replicated operands. Interpret mode has
    no such limit, so this pins it on the CPU: on a 2×2 mesh every tile
    kernel a session calls — screens and the reduced solves' fista/cd
    steps — is traced with all mesh axes manual."""
    out = subproc(KERNELS_UNDER_SHARD_MAP_CODE, devices=4)
    assert "KERNELS_UNDER_SHARD_MAP_OK" in out
