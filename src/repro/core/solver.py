"""SolverEngine: fused, device-resident λ-path solvers behind a registry.

Symmetric to :class:`repro.core.engine.ScreeningEngine`: the paper's rules
are solver-agnostic (§1, §4.1.2), so the solver layer is its own engine —
strategies (``fista`` | ``cd`` | ``group_fista``) dispatched through the
``SOLVERS`` registry, each running a **device-resident**
``lax.while_loop`` whose inner iterations go through the fused kernels of
:mod:`repro.kernels.solver_step` via the same ``kernels.ops.BACKENDS``
registry the screens use (pallas | interpret | jnp).

Key design points
-----------------
* **Gap-check cadence.** The duality-gap stopping test costs two extra
  passes over X and, in a host-driven loop, a device→host sync. Strategies
  check it every ``gap_check_cadence`` inner iterations (Fercoq et al.
  2015 show the gap certificate is cheap *because* it is amortised); the
  count of checks actually run is returned in ``SolveResult.gap_checks``
  and surfaced per λ-step in ``PathStepStats``.
* **Gram crossover.** For ``cd`` on a reduced buffer with bucket ≤ n
  columns (the paper's n ≪ p regime after screening), the engine builds
  G = XᵀX / c = Xᵀy once per solve (one pass over the bucket) and sweeps
  the VMEM-resident Gram system (``cd_gram_sweep`` kernel) — zero HBM
  passes over X per coordinate.
  Crossover: ``bucket ≤ min(n, GRAM_BUCKET_MAX)``; a sweep is then O(b²)
  against the matvec sweep's O(n·b). ``gram_step_frac`` in the path stats
  records how often this fires.
* **Lipschitz caching.** FISTA's step needs ‖X_r‖₂². The engine caches the
  top eigenpair per bucket size and warm-starts power iteration from the
  cached eigenvector on reuse (the kept set drifts slowly along the path),
  so repeated path solves don't re-estimate from scratch.
* **Batched twins.** ``fista``, ``cd`` and ``group_fista`` each have a
  batched strategy (``BATCHED_SOLVERS``): B queries on one union bucket,
  per-query λ and validity masks, converged queries frozen in place.
* **Backend selection**: explicit ``backend=`` → ``REPRO_SOLVER_BACKEND``
  env var → ``INTERPRET=1`` (CI) → ``pallas`` on TPU → ``jnp``. Screen-only
  backends registered via :func:`repro.core.engine.register_backend` keep
  working — missing solver ops fall back to the pure-jnp oracles.

The pure-jnp reference solvers remain the semantics oracle:
tests/test_solver_engine.py checks every strategy × backend against them
to solver tolerance on lasso and group-lasso paths.
"""

from __future__ import annotations

import functools
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from . import tracing
from .group_lasso import (member_major_gap_from_residual,
                          member_major_soft_threshold, to_group_major,
                          to_member_major)
from .lasso import gap_from_residual, soft_threshold, top_eigenpair


class SolveResult(NamedTuple):
    """Result of one reduced solve. Batched solves return the same tuple
    with a leading batch axis on beta (B, b) and per-query gap / iters /
    converged (B,) — gap_checks stays scalar (checks are shared: one fused
    gap pass evaluates all B certificates)."""

    beta: jax.Array
    gap: jax.Array        # final duality gap
    iters: jax.Array      # inner iterations (epochs/sweeps for cd) run
    converged: jax.Array
    gap_checks: jax.Array = jnp.asarray(0)  # duality-gap evaluations run


# Back-compat aliases (the old per-solver result types).
FistaResult = SolveResult
GroupFistaResult = SolveResult


# ---------------------------------------------------------------------------
# Backend resolution (same policy shape as engine.default_backend, separate
# env knob so solver and screening backends can be A/B'd independently)
# ---------------------------------------------------------------------------

def default_solver_backend() -> str:
    return ops.default_backend_name("REPRO_SOLVER_BACKEND")


def resolve_solver_backend(
        name: str | ops.ScreenBackend | None = None) -> ops.ScreenBackend:
    if isinstance(name, ops.ScreenBackend):
        return name
    name = name or default_solver_backend()
    try:
        return ops.BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown solver backend {name!r}; "
            f"available: {tuple(ops.BACKENDS)}") from None


def _fista_step_op(backend: ops.ScreenBackend) -> Callable:
    return backend.fista_step or ops.BACKENDS["jnp"].fista_step


def _cd_gram_op(backend: ops.ScreenBackend) -> Callable:
    return backend.cd_gram_sweep or ops.BACKENDS["jnp"].cd_gram_sweep


# ---------------------------------------------------------------------------
# Strategy bodies: jitted, device-resident while_loops. The gap check runs
# every `cadence` inner iterations; everything between checks stays on
# device (no shapes or values cross to host until the final result).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend", "max_iter", "cadence"))
@jax.named_scope("solve")
def _fista_solve(backend, X, y, lam, beta0, lipschitz, tol,
                 max_iter: int, cadence: int) -> SolveResult:
    """FISTA with the fused gradient+prox+momentum kernel per iteration.

    Per inner step: one forward fit Xz (n-vector) + one fused
    ``fista_step`` pass over X's columns. ``tol`` is a *relative* gap
    tolerance: stop when gap ≤ tol·½‖y‖². Zero columns are fixed points,
    so padded buffers from the path driver pass through.
    """
    dtype = X.dtype
    step_op = _fista_step_op(backend)
    L = jnp.maximum(lipschitz, 1e-12)
    step = 1.0 / L
    scale = 0.5 * jnp.sum(jnp.square(y)) + 1e-30

    def gap_of(beta):
        r = y - X @ beta
        return gap_from_residual(r, X.T @ r, beta, lam, y)

    def one_step(carry, _):
        beta, z, t = carry
        rz = X @ z - y
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        mom = (t - 1.0) / t_new
        beta_new, z_new = step_op(X, rz, z, beta, step, lam, mom)
        return (beta_new.astype(dtype), z_new.astype(dtype), t_new), None

    def cond(state):
        _, _, _, k, gap, _ = state
        return jnp.logical_and(k < max_iter, gap > tol * scale)

    def body(state):
        beta, z, t, k, _, checks = state
        (beta, z, t), _ = jax.lax.scan(one_step, (beta, z, t), None,
                                       length=cadence)
        return beta, z, t, k + cadence, gap_of(beta), checks + 1

    t0 = jnp.asarray(1.0, dtype=dtype)
    state = (beta0, beta0, t0, jnp.asarray(0), gap_of(beta0),
             jnp.asarray(1))
    beta, _, _, k, gap, checks = jax.lax.while_loop(cond, body, state)
    return SolveResult(beta, gap, k, gap <= tol * scale, checks)


@functools.partial(jax.jit, static_argnames=("backend", "max_iter", "cadence"))
@jax.named_scope("solve")
def _fista_solve_lo(backend, X, X_lo, y, lam, beta0, lipschitz, tol,
                    max_iter: int, cadence: int, err_max,
                    cn_max) -> SolveResult:
    """Certified low-precision FISTA phase: the same fused iteration as
    :func:`_fista_solve` but the 2·cadence iteration matvecs between gap
    checks stream the bf16 copy ``X_lo`` of the bucket. β/z and every
    accumulation stay f32 (``fista_step`` out-dtypes follow z; the kernels
    cast X tiles up before the dot), so the only iteration error is the
    bf16 storage rounding of X — bounded per column by
    :func:`ops.bf16_column_err`.

    The duality-gap CERTIFICATE streams the f32 ``X`` (2 passes per check,
    cadence-amortised like every gap check), so a stop at ``gap ≤
    tol·scale`` is TRUE convergence — exactness never rests on bf16 data.
    The phase hands over to the f32 polish early only when the exact gap
    sits under ``BF16_SOLVE_SLACK ×`` the certified progress floor
    (:func:`ops.bf16_gap_budget` — below it a bf16 gradient cannot
    certifiably improve the gap) AND the measured gap has stopped decaying
    by ``BF16_SOLVE_PROGRESS`` per check: the worst-case budget alone must
    not evict a stream that is still measurably converging, and a stall
    alone (FISTA momentum ripples) must not either.
    """
    dtype = beta0.dtype               # β/z stay f32 over the bf16 stream
    step_op = _fista_step_op(backend)
    L = jnp.maximum(lipschitz, 1e-12)
    step = 1.0 / L
    scale = 0.5 * jnp.sum(jnp.square(y)) + 1e-30

    def gap_budget(beta):
        r = y - X @ beta              # exact certificate: f32 stream
        gap = gap_from_residual(r, X.T @ r, beta, lam, y)
        budget = ops.bf16_gap_budget(jnp.linalg.norm(r),
                                     jnp.sum(jnp.abs(beta)),
                                     err_max, cn_max)
        return gap, budget

    def one_step(carry, _):
        beta, z, t = carry
        rz = X_lo @ z - y
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        mom = (t - 1.0) / t_new
        beta_new, z_new = step_op(X_lo, rz, z, beta, step, lam, mom)
        return (beta_new.astype(dtype), z_new.astype(dtype), t_new), None

    def stop(gap, budget, prev_gap):
        return ops.bf16_certified_stop(gap, budget, prev_gap, tol * scale)

    def cond(state):
        _, _, _, k, _, _, done, _ = state
        return jnp.logical_and(k < max_iter, jnp.logical_not(done))

    def body(state):
        beta, z, t, k, prev_gap, _, _, checks = state
        (beta, z, t), _ = jax.lax.scan(one_step, (beta, z, t), None,
                                       length=cadence)
        gap, budget = gap_budget(beta)
        done = stop(gap, budget, prev_gap)
        return beta, z, t, k + cadence, gap, budget, done, checks + 1

    t0 = jnp.asarray(1.0, dtype=dtype)
    gap0, budget0 = gap_budget(beta0)
    state = (beta0, beta0, t0, jnp.asarray(0), gap0, budget0,
             stop(gap0, budget0, jnp.asarray(jnp.inf)), jnp.asarray(1))
    beta, _, _, k, gap, _, _, checks = jax.lax.while_loop(cond, body, state)
    return SolveResult(beta, gap, k, gap <= tol * scale, checks)


@functools.partial(jax.jit, static_argnames=("max_epochs", "cadence"))
@jax.named_scope("solve")
def _cd_solve(X, y, lam, beta0, tol, max_epochs: int,
              cadence: int) -> SolveResult:
    """Cyclic coordinate descent on matvecs (residual maintained).

    Per coordinate:  β_j ← S(x_jᵀr + ‖x_j‖²β_j, λ) / ‖x_j‖²; zero-norm
    (padded) columns are skipped via a `where`. The duality gap is checked
    every ``cadence`` epochs. Inherently sequential column access — no
    kernel; the Gram variant (``_cd_gram_solve``) is the fused path.
    """
    p = X.shape[1]
    sqnorms = jnp.sum(jnp.square(X), axis=0)
    scale = 0.5 * jnp.sum(jnp.square(y)) + 1e-30

    def gap_of(beta):
        # recompute r = y − Xβ fresh: the carried residual accumulates
        # p·eps rounding drift per epoch, which at tight tol could fake
        # convergence (the stopping certificate must not drift)
        r = y - X @ beta
        return gap_from_residual(r, X.T @ r, beta, lam, y)

    def coord(j, carry):
        beta, r = carry
        xj = X[:, j]
        bj = beta[j]
        nj = sqnorms[j]
        rho = xj @ r + nj * bj
        bj_new = jnp.where(nj > 0,
                           soft_threshold(rho, lam) / jnp.maximum(nj, 1e-30),
                           0.0)
        r = r + xj * (bj - bj_new)
        return beta.at[j].set(bj_new), r

    def cond(state):
        _, _, k, gap, _ = state
        return jnp.logical_and(k < max_epochs, gap > tol * scale)

    def body(state):
        beta, r, k, _, checks = state

        def epoch(_, carry):
            return jax.lax.fori_loop(0, p, coord, carry)

        beta, r = jax.lax.fori_loop(0, cadence, epoch, (beta, r))
        return beta, r, k + cadence, gap_of(beta), checks + 1

    r0 = y - X @ beta0
    state = (beta0, r0, jnp.asarray(0), gap_of(beta0), jnp.asarray(1))
    beta, _, k, gap, checks = jax.lax.while_loop(cond, body, state)
    return SolveResult(beta, gap, k, gap <= tol * scale, checks)


@functools.partial(jax.jit, static_argnames=("backend", "max_epochs",
                                             "cadence"))
@jax.named_scope("solve")
def _cd_gram_solve(backend, X, y, lam, beta0, tol, max_epochs: int,
                   cadence: int) -> SolveResult:
    """Coordinate descent over the cached Gram system (n ≪ p regime).

    G = XᵀX and c = Xᵀy are built once (one pass over X); each sweep then
    runs through the backend's VMEM-resident ``cd_gram_sweep`` kernel with
    zero HBM traffic over X. The gap check recomputes the residual
    directly from X (cadence-amortised, avoids the ‖y‖²−2cᵀβ+βᵀGβ
    cancellation at tight tolerances).
    """
    acc = jnp.promote_types(X.dtype, jnp.float32)
    Xa = X.astype(acc)
    G = Xa.T @ Xa
    c = Xa.T @ y.astype(acc)
    sweep_op = _cd_gram_op(backend)
    scale = 0.5 * jnp.sum(jnp.square(y)) + 1e-30

    def gap_of(beta):
        r = y - X @ beta
        return gap_from_residual(r, X.T @ r, beta, lam, y)

    def cond(state):
        _, k, gap, _ = state
        return jnp.logical_and(k < max_epochs, gap > tol * scale)

    def body(state):
        beta, k, _, checks = state
        beta = sweep_op(G, c, beta.astype(acc), lam,
                        sweeps=cadence).astype(X.dtype)
        return beta, k + cadence, gap_of(beta), checks + 1

    state = (beta0, jnp.asarray(0), gap_of(beta0), jnp.asarray(1))
    beta, k, gap, checks = jax.lax.while_loop(cond, body, state)
    return SolveResult(beta, gap, k, gap <= tol * scale, checks)


# ---------------------------------------------------------------------------
# Batched strategy bodies: B queries against one reduced buffer Xr. The
# while_loop carries per-query convergence masks — a converged query's
# (β, z) become FIXED POINTS (further batched iterations are identity on
# them), its iteration counter stops, and the loop exits when every query
# has converged. ``valid`` (B, b) ∈ {0, 1} pins the columns each query
# screened out (the buffer holds the UNION of survivors across the batch),
# so every query solves exactly its own reduced problem.
# ---------------------------------------------------------------------------

def _gap_from_residual_batched(r, dot, beta, lam, y):
    """Per-query duality gaps (B,) from batched residuals r (B, n) and
    correlations dot (B, b) — same arithmetic as lasso.gap_from_residual
    per row, one fused evaluation for the batch."""
    corr = jnp.max(jnp.abs(dot), axis=-1)                     # (B,)
    s = jnp.minimum(1.0, lam / (corr + 1e-30))
    return (0.5 * jnp.sum(jnp.square(r), axis=-1)
            + lam * jnp.sum(jnp.abs(beta), axis=-1)
            - 0.5 * jnp.sum(jnp.square(y), axis=-1)
            + 0.5 * jnp.sum(jnp.square(s[:, None] * r - y), axis=-1))


@functools.partial(jax.jit, static_argnames=("backend", "max_iter", "cadence"))
@jax.named_scope("solve")
def _fista_solve_batched(backend, X, Y, lam, beta0, valid, lipschitz, tol,
                         max_iter: int, cadence: int) -> SolveResult:
    """Batched FISTA: B queries share every pass over X (forward fits and
    the fused ``fista_step`` gradient+prox+momentum kernel both carry the
    batch axis), per-query λ, per-query convergence freezing."""
    dtype = X.dtype
    step_op = _fista_step_op(backend)
    L = jnp.maximum(lipschitz, 1e-12)                 # shared: same buffer
    step = 1.0 / L
    scale = 0.5 * jnp.sum(jnp.square(Y), axis=-1) + 1e-30     # (B,)

    def gap_of(beta):
        r = Y - beta @ X.T
        return _gap_from_residual_batched(r, r @ X, beta, lam, Y)

    def body(state):
        beta, z, t, k, _, conv, iters, checks = state
        frozen = conv[:, None]

        def one_step(carry, _):
            beta, z, t = carry
            rz = z @ X.T - Y
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            mom = (t - 1.0) / t_new
            beta_new, z_new = step_op(X, rz, z, beta, step, lam, mom)
            beta_new = (beta_new * valid).astype(dtype)
            z_new = (z_new * valid).astype(dtype)
            # converged queries are fixed points of further iterations
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


@functools.partial(jax.jit, static_argnames=("backend", "max_iter", "cadence"))
@jax.named_scope("solve")
def _fista_solve_lo_batched(backend, X, X_lo, Y, lam, beta0, valid,
                            lipschitz, tol, max_iter: int, cadence: int,
                            err_max, cn_max) -> SolveResult:
    """Batched twin of :func:`_fista_solve_lo`: B queries share every pass
    over the bf16 bucket copy (iterations) and the f32 bucket (exact gap
    certificates), each with its OWN certified progress floor (per-query
    ‖r‖, ‖β‖₁) and stall test — a query freezes as soon as it truly
    converges or its bf16 stream provably can't improve it, exactly like
    batched f32 convergence freezing."""
    dtype = beta0.dtype
    step_op = _fista_step_op(backend)
    L = jnp.maximum(lipschitz, 1e-12)
    step = 1.0 / L
    scale = 0.5 * jnp.sum(jnp.square(Y), axis=-1) + 1e-30     # (B,)

    def gap_budget(beta):
        r = Y - beta @ X.T            # exact certificate: f32 stream
        gap = _gap_from_residual_batched(r, r @ X, beta, lam, Y)
        budget = ops.bf16_gap_budget(jnp.linalg.norm(r, axis=-1),
                                     jnp.sum(jnp.abs(beta), axis=-1),
                                     err_max, cn_max)
        return gap, budget

    def stop(gap, budget, prev_gap):
        return ops.bf16_certified_stop(gap, budget, prev_gap, tol * scale)

    def body(state):
        beta, z, t, k, prev_gap, conv, iters, checks = state
        frozen = conv[:, None]

        def one_step(carry, _):
            beta, z, t = carry
            rz = z @ X_lo.T - Y
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            mom = (t - 1.0) / t_new
            beta_new, z_new = step_op(X_lo, rz, z, beta, step, lam, mom)
            beta_new = (beta_new * valid).astype(dtype)
            z_new = (z_new * valid).astype(dtype)
            beta_new = jnp.where(frozen, beta, beta_new)
            z_new = jnp.where(frozen, z, z_new)
            return (beta_new, z_new, t_new), None

        (beta, z, t), _ = jax.lax.scan(one_step, (beta, z, t), None,
                                       length=cadence)
        iters = iters + jnp.where(conv, 0, cadence)
        gap, budget = gap_budget(beta)
        conv = jnp.logical_or(conv, stop(gap, budget, prev_gap))
        return beta, z, t, k + cadence, gap, conv, iters, checks + 1

    def cond(state):
        _, _, _, k, _, conv, _, _ = state
        return jnp.logical_and(k < max_iter, jnp.any(~conv))

    t0 = jnp.asarray(1.0, dtype=dtype)
    gap0, budget0 = gap_budget(beta0)
    conv0 = stop(gap0, budget0, jnp.full_like(gap0, jnp.inf))
    iters0 = jnp.zeros(Y.shape[:1], jnp.int32)
    state = (beta0, beta0, t0, jnp.asarray(0), gap0, conv0, iters0,
             jnp.asarray(1))
    beta, _, _, _, gap, conv, iters, checks = jax.lax.while_loop(
        cond, body, state)
    return SolveResult(beta, gap, iters, gap <= tol * scale, checks)


@functools.partial(jax.jit, static_argnames=("max_epochs", "cadence"))
@jax.named_scope("solve")
def _cd_solve_batched(X, Y, lam, beta0, valid, tol, max_epochs: int,
                      cadence: int) -> SolveResult:
    """Batched cyclic CD on matvecs: each coordinate update touches x_j
    once for ALL B residual rows; convergence freezing at epoch-block
    granularity (frozen queries' updates are discarded)."""
    p = X.shape[1]
    sqnorms = jnp.sum(jnp.square(X), axis=0)
    scale = 0.5 * jnp.sum(jnp.square(Y), axis=-1) + 1e-30

    def gap_of(beta):
        r = Y - beta @ X.T
        return _gap_from_residual_batched(r, r @ X, beta, lam, Y)

    def coord(j, carry):
        beta, r = carry
        xj = X[:, j]
        bj = beta[:, j]
        nj = sqnorms[j]
        rho = r @ xj + nj * bj                            # (B,)
        bj_new = jnp.where(
            nj > 0, soft_threshold(rho, lam) / jnp.maximum(nj, 1e-30), 0.0
        ) * valid[:, j]
        r = r + xj[None, :] * (bj - bj_new)[:, None]
        return beta.at[:, j].set(bj_new), r

    def body(state):
        beta, r, k, _, conv, iters, checks = state

        def epoch(_, carry):
            return jax.lax.fori_loop(0, p, coord, carry)

        beta_new, r_new = jax.lax.fori_loop(0, cadence, epoch, (beta, r))
        frozen = conv[:, None]
        beta_new = jnp.where(frozen, beta, beta_new)
        r_new = jnp.where(frozen, r, r_new)
        iters = iters + jnp.where(conv, 0, cadence)
        gap = gap_of(beta_new)
        conv = jnp.logical_or(conv, gap <= tol * scale)
        return beta_new, r_new, k + cadence, gap, conv, iters, checks + 1

    def cond(state):
        _, _, k, _, conv, _, _ = state
        return jnp.logical_and(k < max_epochs, jnp.any(~conv))

    r0 = Y - beta0 @ X.T
    gap0 = gap_of(beta0)
    conv0 = gap0 <= tol * scale
    iters0 = jnp.zeros(Y.shape[:1], jnp.int32)
    state = (beta0, r0, jnp.asarray(0), gap0, conv0, iters0, jnp.asarray(1))
    beta, _, _, gap, conv, iters, checks = jax.lax.while_loop(
        cond, body, state)
    return SolveResult(beta, gap, iters, conv, checks)


@functools.partial(jax.jit, static_argnames=("backend", "max_epochs",
                                             "cadence"))
@jax.named_scope("solve")
def _cd_gram_solve_batched(backend, X, Y, lam, beta0, valid, tol,
                           max_epochs: int, cadence: int) -> SolveResult:
    """Batched Gram CD: ONE shared G = XᵀX (the dictionary Gram of the
    union bucket, built with a single pass over X) serves all B coordinate
    systems; per-query c = Xᵀy_b, λ_b and validity masks ride through the
    batched ``cd_gram_sweep`` kernel."""
    acc = jnp.promote_types(X.dtype, jnp.float32)
    Xa = X.astype(acc)
    G = Xa.T @ Xa
    C = Y.astype(acc) @ Xa                                    # (B, b)
    sweep_op = _cd_gram_op(backend)
    scale = 0.5 * jnp.sum(jnp.square(Y), axis=-1) + 1e-30

    def gap_of(beta):
        r = Y - beta @ X.T
        return _gap_from_residual_batched(r, r @ X, beta, lam, Y)

    def body(state):
        beta, k, _, conv, iters, checks = state
        beta_new = sweep_op(G, C, beta.astype(acc), lam, sweeps=cadence,
                            valid=valid).astype(X.dtype)
        beta_new = jnp.where(conv[:, None], beta, beta_new)
        iters = iters + jnp.where(conv, 0, cadence)
        gap = gap_of(beta_new)
        conv = jnp.logical_or(conv, gap <= tol * scale)
        return beta_new, k + cadence, gap, conv, iters, checks + 1

    def cond(state):
        _, k, _, conv, _, _ = state
        return jnp.logical_and(k < max_epochs, jnp.any(~conv))

    gap0 = gap_of(beta0)
    conv0 = gap0 <= tol * scale
    iters0 = jnp.zeros(Y.shape[:1], jnp.int32)
    state = (beta0, jnp.asarray(0), gap0, conv0, iters0, jnp.asarray(1))
    beta, _, gap, conv, iters, checks = jax.lax.while_loop(cond, body, state)
    return SolveResult(beta, gap, iters, conv, checks)


@functools.partial(jax.jit, static_argnames=("backend", "max_epochs",
                                             "cadence"))
@jax.named_scope("solve")
def _cd_gram_solve_lo(backend, X, X_lo, y, lam, beta0, tol, max_epochs: int,
                      cadence: int, err_max, cn_max) -> SolveResult:
    """Gram CD with the G build streamed off the bf16 dictionary copy:
    G̃ = X̃ᵀX̃ and c̃ = X̃ᵀy accumulate in f32 from the 2-byte elements —
    the ONE HBM pass over the bucket this solver path takes, so the whole
    data movement of the build runs at half width. Sweeps then run in VMEM
    on G̃ exactly as in :func:`_cd_gram_solve`.

    The duality-gap CERTIFICATE recomputes the residual from the f32 ``X``
    (2 passes per check, cadence-amortised), so a stop at ``gap ≤
    tol·scale`` is TRUE convergence. The perturbed sweep gradient is
    ``G̃β − c̃ = X̃ᵀ(X̃β − y)`` — exactly the doubly-perturbed matvec
    :func:`ops.bf16_gap_budget` bounds for the FISTA lo phase — so the
    same certified stall/floor handover applies; on handover
    ``_cd_gram_solve`` rebuilds the exact G and polishes."""
    acc = jnp.promote_types(X.dtype, jnp.float32)
    Xl = X_lo.astype(acc)
    G = Xl.T @ Xl
    c = Xl.T @ y.astype(acc)
    sweep_op = _cd_gram_op(backend)
    scale = 0.5 * jnp.sum(jnp.square(y)) + 1e-30

    def gap_budget(beta):
        r = y - X @ beta              # exact certificate: f32 stream
        gap = gap_from_residual(r, X.T @ r, beta, lam, y)
        budget = ops.bf16_gap_budget(jnp.linalg.norm(r),
                                     jnp.sum(jnp.abs(beta)),
                                     err_max, cn_max)
        return gap, budget

    def cond(state):
        _, k, _, done, _ = state
        return jnp.logical_and(k < max_epochs, jnp.logical_not(done))

    def body(state):
        beta, k, prev_gap, _, checks = state
        beta = sweep_op(G, c, beta.astype(acc), lam,
                        sweeps=cadence).astype(X.dtype)
        gap, budget = gap_budget(beta)
        done = ops.bf16_certified_stop(gap, budget, prev_gap, tol * scale)
        return beta, k + cadence, gap, done, checks + 1

    gap0, budget0 = gap_budget(beta0)
    done0 = ops.bf16_certified_stop(gap0, budget0, jnp.asarray(jnp.inf),
                                    tol * scale)
    state = (beta0, jnp.asarray(0), gap0, done0, jnp.asarray(1))
    beta, k, gap, _, checks = jax.lax.while_loop(cond, body, state)
    return SolveResult(beta, gap, k, gap <= tol * scale, checks)


@functools.partial(jax.jit, static_argnames=("backend", "max_epochs",
                                             "cadence"))
@jax.named_scope("solve")
def _cd_gram_solve_lo_batched(backend, X, X_lo, Y, lam, beta0, valid, tol,
                              max_epochs: int, cadence: int, err_max,
                              cn_max) -> SolveResult:
    """Batched twin of :func:`_cd_gram_solve_lo`: ONE bf16-streamed
    G̃ = X̃ᵀX̃ serves all B coordinate systems, per-query c̃ = X̃ᵀy_b rides
    the batched sweep kernel, and each query carries its OWN certified
    stall/floor test against the exact f32 gap certificate (a query
    freezes as soon as it truly converges or its bf16 Gram provably can't
    improve it)."""
    acc = jnp.promote_types(X.dtype, jnp.float32)
    Xl = X_lo.astype(acc)
    G = Xl.T @ Xl
    C = Y.astype(acc) @ Xl                                    # (B, b)
    sweep_op = _cd_gram_op(backend)
    scale = 0.5 * jnp.sum(jnp.square(Y), axis=-1) + 1e-30

    def gap_budget(beta):
        r = Y - beta @ X.T            # exact certificate: f32 stream
        gap = _gap_from_residual_batched(r, r @ X, beta, lam, Y)
        budget = ops.bf16_gap_budget(jnp.linalg.norm(r, axis=-1),
                                     jnp.sum(jnp.abs(beta), axis=-1),
                                     err_max, cn_max)
        return gap, budget

    def body(state):
        beta, k, prev_gap, conv, iters, checks = state
        beta_new = sweep_op(G, C, beta.astype(acc), lam, sweeps=cadence,
                            valid=valid).astype(X.dtype)
        beta_new = jnp.where(conv[:, None], beta, beta_new)
        iters = iters + jnp.where(conv, 0, cadence)
        gap, budget = gap_budget(beta_new)
        conv = jnp.logical_or(
            conv, ops.bf16_certified_stop(gap, budget, prev_gap,
                                          tol * scale))
        return beta_new, k + cadence, gap, conv, iters, checks + 1

    def cond(state):
        _, k, _, conv, _, _ = state
        return jnp.logical_and(k < max_epochs, jnp.any(~conv))

    gap0, budget0 = gap_budget(beta0)
    conv0 = ops.bf16_certified_stop(gap0, budget0,
                                    jnp.full_like(gap0, jnp.inf),
                                    tol * scale)
    iters0 = jnp.zeros(Y.shape[:1], jnp.int32)
    state = (beta0, jnp.asarray(0), gap0, conv0, iters0, jnp.asarray(1))
    beta, _, gap, conv, iters, checks = jax.lax.while_loop(cond, body, state)
    return SolveResult(beta, gap, iters, gap <= tol * scale, checks)


@functools.partial(jax.jit, static_argnames=("m", "max_iter", "cadence"))
@jax.named_scope("solve")
def _group_fista_solve(X, y, lam, m: int, beta0, lipschitz, tol,
                       max_iter: int, cadence: int) -> SolveResult:
    """Block-FISTA for the group Lasso (pure-jnp body on every backend —
    the block soft-threshold has no fused kernel yet). Zero-padded group
    blocks are fixed points, so group buckets pass through.

    The iteration runs on the member-major view of the bucket
    (:func:`repro.core.group_lasso.to_member_major`): X's columns and the
    iterate are reordered once on entry and β once on exit, so callers
    see the group-major layout."""
    dtype = X.dtype
    Xt = to_member_major(X.T, m, axis=0)       # (b, n): rows member-major
    L = jnp.maximum(lipschitz, 1e-12)
    step = 1.0 / L
    scale = 0.5 * jnp.sum(jnp.square(y)) + 1e-30

    def gap_of(beta):
        r = y - beta @ Xt
        return member_major_gap_from_residual(r, Xt @ r, beta, lam, m, y)

    def one_step(carry, _):
        beta, z, t = carry
        g = Xt @ (z @ Xt - y)
        beta_new = member_major_soft_threshold(z - step * g, step * lam, m)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_new = beta_new + ((t - 1.0) / t_new) * (beta_new - beta)
        return (beta_new, z_new, t_new), None

    def cond(state):
        _, _, _, k, gap, _ = state
        return jnp.logical_and(k < max_iter, gap > tol * scale)

    def body(state):
        beta, z, t, k, _, checks = state
        (beta, z, t), _ = jax.lax.scan(one_step, (beta, z, t), None,
                                       length=cadence)
        return beta, z, t, k + cadence, gap_of(beta), checks + 1

    beta0 = to_member_major(beta0, m)
    t0 = jnp.asarray(1.0, dtype=dtype)
    state = (beta0, beta0, t0, jnp.asarray(0), gap_of(beta0), jnp.asarray(1))
    beta, _, _, k, gap, checks = jax.lax.while_loop(cond, body, state)
    return SolveResult(to_group_major(beta, m), gap, k, gap <= tol * scale,
                       checks)


@functools.partial(jax.jit, static_argnames=("m", "max_iter", "cadence"))
@jax.named_scope("solve")
def _group_fista_solve_batched(X, Y, lam, m: int, beta0, valid, lipschitz,
                               tol, max_iter: int,
                               cadence: int) -> SolveResult:
    """Batched block-FISTA: B queries share every pass over the bucket X,
    each with its own λ, its own kept groups (``valid`` (B, b), constant
    over each group's m columns) and its own convergence freezing, as in
    :func:`_fista_solve_batched`. Like :func:`_group_fista_solve` it
    iterates on the member-major view: X, ``beta0`` and ``valid`` are
    reordered on entry, β on exit."""
    dtype = X.dtype
    Xt = to_member_major(X.T, m, axis=0)       # (b, n): rows member-major
    valid = to_member_major(valid, m)
    L = jnp.maximum(lipschitz, 1e-12)                 # shared: same buffer
    step = 1.0 / L
    scale = 0.5 * jnp.sum(jnp.square(Y), axis=-1) + 1e-30     # (B,)

    def gap_of(beta):
        r = Y - beta @ Xt
        return member_major_gap_from_residual(r, r @ Xt.T, beta, lam, m, Y)

    def body(state):
        beta, z, t, k, _, conv, iters, checks = state
        frozen = conv[:, None]

        def one_step(carry, _):
            beta, z, t = carry
            g = (z @ Xt - Y) @ Xt.T
            beta_new = (member_major_soft_threshold(z - step * g,
                                                    step * lam, m)
                        * valid).astype(dtype)
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            z_new = (beta_new + ((t - 1.0) / t_new) * (beta_new - beta))
            z_new = (z_new * valid).astype(dtype)
            # converged queries are fixed points of further iterations
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

    beta0 = to_member_major(beta0, m)
    t0 = jnp.asarray(1.0, dtype=dtype)
    gap0 = gap_of(beta0)
    conv0 = gap0 <= tol * scale
    iters0 = jnp.zeros(Y.shape[:1], jnp.int32)
    state = (beta0, beta0, t0, jnp.asarray(0), gap0, conv0, iters0,
             jnp.asarray(1))
    beta, _, _, _, gap, conv, iters, checks = jax.lax.while_loop(
        cond, body, state)
    return SolveResult(to_group_major(beta, m), gap, iters, conv, checks)


# ---------------------------------------------------------------------------
# Strategies + registry. A strategy is `(engine, Xr, lam, beta0, m) ->
# (SolveResult, info)` with info = {"gram": bool} telemetry (+ "lo_iters" /
# "lo_checks" / "hi_iters" from the mixed-precision fista two-phase,
# "lo_passes" / "x_passes" pass-accounting overrides from the
# mixed-precision Gram-CD two-phase, and "member_major" from the group
# solver when it iterated on the member-major bucket).
# ---------------------------------------------------------------------------

_BF16_SOLVE_WARNED: set[str] = set()


def _note_solve_f32_fallback(strategy: str) -> None:
    """One-time warning per strategy: solve_dtype='bfloat16' was requested
    but this strategy has no certified low-precision phase (the fista
    iteration stream and the cd Gram build are the implemented ones), so
    solves run f32."""
    if strategy in _BF16_SOLVE_WARNED:
        return
    _BF16_SOLVE_WARNED.add(strategy)
    warnings.warn(
        f"solve_dtype='bfloat16' has no certified low-precision phase for "
        f"solver strategy {strategy!r}; solving in float32 instead (results "
        f"unchanged, no byte saving — see docs/solvers.md#mixed-precision-"
        f"solves)", RuntimeWarning, stacklevel=4)


def _fista_strategy(eng: "SolverEngine", Xr, lam, beta0, m: int):
    L = eng.lipschitz(Xr)                 # shared by both phases
    lo = eng._take_lo()
    lo_it = lo_ck = 0
    if lo is not None:
        # Phase 1: certified bf16 iterations while the gap certificate is
        # provably slack (see _fista_solve_lo). β stays f32 throughout.
        X_lo, err_max, cn_max = lo
        with tracing.span("solve.iterate"):
            res_lo = _fista_solve_lo(eng.backend, Xr, X_lo, eng.y, lam,
                                     beta0.astype(jnp.float32), L, eng.tol,
                                     eng.max_iter, eng.gap_check_cadence,
                                     err_max, cn_max)
        lo_it = int(tracing.fetch(res_lo.iters))
        lo_ck = int(tracing.fetch(res_lo.gap_checks))
        if bool(tracing.fetch(res_lo.converged)):
            # The lo-phase gap certificate streams f32 X, so convergence
            # declared there IS convergence at the original tol — no
            # polish pass needed.
            return (SolveResult(res_lo.beta.astype(Xr.dtype), res_lo.gap,
                                res_lo.iters, res_lo.converged,
                                res_lo.gap_checks),
                    {"gram": False, "lo_iters": lo_it, "lo_checks": lo_ck})
        beta0 = res_lo.beta.astype(Xr.dtype)
    # Phase 2 (or the whole solve in f32): polish at the original tol.
    with tracing.span("solve.iterate"):
        res = _fista_solve(eng.backend, Xr, eng.y, lam, beta0, L, eng.tol,
                           eng.max_iter, eng.gap_check_cadence)
    if lo is not None:
        res = SolveResult(res.beta, res.gap, res.iters + lo_it,
                          res.converged, res.gap_checks + lo_ck)
    return res, {"gram": False, "lo_iters": lo_it, "lo_checks": lo_ck}


def _cd_strategy(eng: "SolverEngine", Xr, lam, beta0, m: int):
    n, b = Xr.shape
    max_epochs = eng.max_iter // 10 + 1
    lo = eng._take_lo()
    if b <= min(n, ops.GRAM_BUCKET_MAX):
        if lo is None:
            with tracing.span("solve.iterate"):
                res = _cd_gram_solve(eng.backend, Xr, eng.y, lam, beta0,
                                     eng.tol, max_epochs,
                                     eng.gap_check_cadence)
            return res, {"gram": True}
        # Phase 1: build G̃ off the bf16 copy (half-width bucket pass) and
        # sweep under the f32 gap certificate (see _cd_gram_solve_lo).
        X_lo, err_max, cn_max = lo
        with tracing.span("solve.iterate"):
            res_lo = _cd_gram_solve_lo(eng.backend, Xr, X_lo, eng.y, lam,
                                       beta0, eng.tol, max_epochs,
                                       eng.gap_check_cadence, err_max,
                                       cn_max)
        lo_it = int(tracing.fetch(res_lo.iters))
        lo_ck = int(tracing.fetch(res_lo.gap_checks))
        if bool(tracing.fetch(res_lo.converged)):
            # the certificate streamed f32 X — convergence in the
            # bf16-built Gram phase is convergence at the original tol
            return res_lo, {
                "gram": True, "lo_iters": lo_it, "lo_checks": lo_ck,
                "lo_passes": 1.0,
                "x_passes": 1.0 + lo_it * (b / max(n, 1)) + 2.0 * lo_ck}
        # Phase 2: rebuild the exact G (one f32 pass) and polish.
        with tracing.span("solve.iterate"):
            res = _cd_gram_solve(eng.backend, Xr, eng.y, lam, res_lo.beta,
                                 eng.tol, max_epochs, eng.gap_check_cadence)
        hi_it = int(tracing.fetch(res.iters))
        hi_ck = int(tracing.fetch(res.gap_checks))
        res = SolveResult(res.beta, res.gap, res.iters + lo_it,
                          res.converged, res.gap_checks + lo_ck)
        return res, {
            "gram": True, "lo_iters": lo_it, "lo_checks": lo_ck,
            "lo_passes": 1.0,
            "x_passes": (2.0 + (lo_it + hi_it) * (b / max(n, 1))
                         + 2.0 * (lo_ck + hi_ck))}
    if lo is not None:
        # buckets past the Gram crossover run matvec CD, which has no
        # certified bf16 stream — this solve streams f32. A bucket-size
        # crossover is not a config error, so telemetry only, no warning.
        eng.last_effective_dtype = "float32"
    with tracing.span("solve.iterate"):
        res = _cd_solve(Xr, eng.y, lam, beta0, eng.tol, max_epochs,
                        eng.gap_check_cadence)
    return res, {"gram": False}


def _group_fista_strategy(eng: "SolverEngine", Xr, lam, beta0, m: int):
    L = eng.lipschitz(Xr)
    with tracing.span("solve.iterate"):
        res = _group_fista_solve(Xr, eng.y, lam, m, beta0, L, eng.tol,
                                 eng.max_iter, eng.gap_check_cadence)
    return res, {"gram": False, "member_major": m > 1}


def _fista_strategy_batched(eng: "SolverEngine", Xr, lam, beta0, valid,
                            m: int):
    L = eng.lipschitz(Xr)
    lo = eng._take_lo()
    lo_it = lo_ck = 0
    res_lo = None
    if lo is not None:
        X_lo, err_max, cn_max = lo
        with tracing.span("solve.iterate"):
            res_lo = _fista_solve_lo_batched(
                eng.backend, Xr, X_lo, eng.y, lam, beta0.astype(jnp.float32),
                valid, L, eng.tol, eng.max_iter, eng.gap_check_cadence,
                err_max, cn_max)
        lo_it = int(tracing.fetch(jnp.max(res_lo.iters)))
        lo_ck = int(tracing.fetch(res_lo.gap_checks))
        if bool(tracing.fetch(jnp.all(res_lo.converged))):
            # every query converged against the f32 gap certificate inside
            # the lo phase — the batch needs no polish pass
            return (SolveResult(res_lo.beta.astype(Xr.dtype), res_lo.gap,
                                res_lo.iters, res_lo.converged,
                                res_lo.gap_checks),
                    {"gram": False, "lo_iters": lo_it, "lo_checks": lo_ck,
                     "hi_iters": 0})
        beta0 = res_lo.beta.astype(Xr.dtype)
    with tracing.span("solve.iterate"):
        res = _fista_solve_batched(eng.backend, Xr, eng.y, lam, beta0, valid,
                                   L, eng.tol, eng.max_iter,
                                   eng.gap_check_cadence)
    hi_it = int(tracing.fetch(jnp.max(res.iters)))
    if res_lo is not None:
        res = SolveResult(res.beta, res.gap, res.iters + res_lo.iters,
                          res.converged, res.gap_checks + lo_ck)
    return res, {"gram": False, "lo_iters": lo_it, "lo_checks": lo_ck,
                 "hi_iters": hi_it}


def _group_fista_strategy_batched(eng: "SolverEngine", Xr, lam, beta0, valid,
                                  m: int):
    L = eng.lipschitz(Xr)
    with tracing.span("solve.iterate"):
        res = _group_fista_solve_batched(Xr, eng.y, lam, m, beta0, valid, L,
                                         eng.tol, eng.max_iter,
                                         eng.gap_check_cadence)
    return res, {"gram": False, "member_major": m > 1}


def _cd_strategy_batched(eng: "SolverEngine", Xr, lam, beta0, valid, m: int):
    n, b = Xr.shape
    max_epochs = eng.max_iter // 10 + 1
    lo = eng._take_lo()
    if b <= min(n, ops.GRAM_BUCKET_MAX):
        if lo is None:
            with tracing.span("solve.iterate"):
                res = _cd_gram_solve_batched(eng.backend, Xr, eng.y, lam,
                                             beta0, valid, eng.tol,
                                             max_epochs,
                                             eng.gap_check_cadence)
            return res, {"gram": True}
        X_lo, err_max, cn_max = lo
        with tracing.span("solve.iterate"):
            res_lo = _cd_gram_solve_lo_batched(eng.backend, Xr, X_lo, eng.y,
                                               lam, beta0, valid, eng.tol,
                                               max_epochs,
                                               eng.gap_check_cadence,
                                               err_max, cn_max)
        lo_it = int(tracing.fetch(jnp.max(res_lo.iters)))
        lo_ck = int(tracing.fetch(res_lo.gap_checks))
        if bool(tracing.fetch(jnp.all(res_lo.converged))):
            # every query converged against the f32 gap certificate on the
            # bf16-built Gram — no exact rebuild needed
            return res_lo, {
                "gram": True, "lo_iters": lo_it, "lo_checks": lo_ck,
                "lo_passes": 1.0,
                "x_passes": 1.0 + lo_it * (b / max(n, 1)) + 2.0 * lo_ck}
        with tracing.span("solve.iterate"):
            res = _cd_gram_solve_batched(eng.backend, Xr, eng.y, lam,
                                         res_lo.beta, valid, eng.tol,
                                         max_epochs, eng.gap_check_cadence)
        hi_it = int(tracing.fetch(jnp.max(res.iters)))
        hi_ck = int(tracing.fetch(res.gap_checks))
        res = SolveResult(res.beta, res.gap, res.iters + res_lo.iters,
                          res.converged, res.gap_checks + lo_ck)
        return res, {
            "gram": True, "lo_iters": lo_it, "lo_checks": lo_ck,
            "lo_passes": 1.0,
            "x_passes": (2.0 + (lo_it + hi_it) * (b / max(n, 1))
                         + 2.0 * (lo_ck + hi_ck))}
    if lo is not None:
        # matvec CD past the Gram crossover: no certified bf16 stream —
        # f32 solve, telemetry only (bucket size is data, not config).
        eng.last_effective_dtype = "float32"
    with tracing.span("solve.iterate"):
        res = _cd_solve_batched(Xr, eng.y, lam, beta0, valid, eng.tol,
                                max_epochs, eng.gap_check_cadence)
    return res, {"gram": False}


SOLVERS: dict[str, Callable] = {
    "fista": _fista_strategy,
    "cd": _cd_strategy,
    "group_fista": _group_fista_strategy,
}

# Batched twins: `(engine, Xr, lam (B,), beta0 (B, b), valid (B, b), m) ->
# (SolveResult, info)`. Strategies without an entry fall back to a
# per-query Python loop in SolverEngine.solve_batched.
BATCHED_SOLVERS: dict[str, Callable] = {
    "fista": _fista_strategy_batched,
    "cd": _cd_strategy_batched,
    "group_fista": _group_fista_strategy_batched,
}


def register_solver(name: str, strategy: Callable,
                    batched: Callable | None = None) -> None:
    """Add a solver strategy: `(engine, Xr, lam, beta0, m) -> (SolveResult,
    {"gram": bool})`. Select it with ``PathConfig(solver=name)``. Pass
    ``batched`` to serve multi-query paths natively (see BATCHED_SOLVERS);
    without it, batched solves loop the single-query strategy per query."""
    SOLVERS[name] = strategy
    if batched is not None:
        BATCHED_SOLVERS[name] = batched
    else:
        BATCHED_SOLVERS.pop(name, None)


def available_solvers() -> tuple[str, ...]:
    return tuple(SOLVERS)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class SolverEngine:
    """One entry point for every reduced solve on a λ-path.

    Usage (what the path driver does)::

        eng = SolverEngine(y, solver="fista", backend=cfg.solver_backend,
                           tol=cfg.solver_tol, max_iter=cfg.max_iter,
                           gap_check_cadence=cfg.gap_check_cadence)
        for lam in grid:
            ... screen -> gather bucket Xr, warm start beta0 ...
            res = eng.solve(Xr, lam, beta0)

    ``last_gap_checks`` / ``last_used_gram`` expose per-solve telemetry for
    ``PathStepStats``; ``total_gap_checks`` accumulates across the path.
    """

    def __init__(self, y, *, solver: str = "fista",
                 backend: str | ops.ScreenBackend | None = None,
                 tol: float = 1e-8, max_iter: int = 5000,
                 gap_check_cadence: int = 10,
                 solve_dtype: str = "float32",
                 power_iters: int = 50, warm_power_iters: int = 16,
                 seed: int = 0, eig_cache: dict | None = None,
                 eig_stats: dict | None = None):
        if solver not in SOLVERS:
            raise ValueError(f"unknown solver {solver!r}; "
                             f"available: {available_solvers()}")
        if solve_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"unknown solve_dtype {solve_dtype!r}; "
                             "expected 'float32' or 'bfloat16'")
        self.y = jnp.asarray(y)
        self.solver = solver
        self.backend = resolve_solver_backend(backend)
        self.tol = tol
        self.max_iter = max_iter
        self.gap_check_cadence = max(1, int(gap_check_cadence))
        self.solve_dtype = solve_dtype
        self.power_iters = power_iters
        self.warm_power_iters = warm_power_iters
        self.seed = seed
        # ``eig_cache`` lets a LassoSession share the per-bucket Lipschitz
        # warm starts across many engines (one per query batch): the kept
        # sets drift slowly between queries of the same dictionary, so the
        # cached eigenvector stays an excellent start.
        self._eig_cache: dict[int, jax.Array] = (
            eig_cache if eig_cache is not None else {})
        # warm/cold power-iteration accounting; share a dict (like
        # eig_cache) to accumulate across the engines a session builds —
        # the update-path tests use it to prove eigenvectors carry across
        # dictionary versions.
        self._eig_stats: dict[str, int] = (
            eig_stats if eig_stats is not None else {"warm": 0, "cold": 0})
        self.n_solves = 0
        self.gram_solves = 0
        self.total_gap_checks = 0
        self.last_gap_checks = 0
        self.last_used_gram = False
        self.last_member_major = False  # last solve ran member-major
        self.last_x_passes = 0.0   # HBM passes over the reduced buffer
        # Mixed-precision solve telemetry (solve_dtype="bfloat16"):
        self.last_lo_iters = 0             # bf16-phase iterations last solve
        self.last_effective_dtype = "float32"  # stream dtype actually used
        self.last_solve_bytes = 0.0        # HBM bytes the last solve streamed
        self.total_solve_bytes = 0.0
        self._lo = None                    # staged (X_lo, err_max, cn_max)

    @property
    def backend_name(self) -> str:
        return self.backend.name

    def lipschitz(self, Xr) -> jax.Array:
        """1.05·‖X_r‖₂², warm-started per bucket size.

        The kept set drifts slowly along the path, so the previous
        eigenvector for the same bucket is an excellent start: a handful
        of iterations replaces the full cold estimate. A bucket change
        (new static shape) re-estimates cold.
        """
        bucket = Xr.shape[1]
        v_prev = self._eig_cache.get(bucket)
        with tracing.span("solve.lipschitz"):
            if v_prev is None:
                self._eig_stats["cold"] = self._eig_stats.get("cold", 0) + 1
                eig, v = top_eigenpair(Xr, iters=self.power_iters,
                                       seed=self.seed)
            else:
                self._eig_stats["warm"] = self._eig_stats.get("warm", 0) + 1
                eig, v = top_eigenpair(Xr, iters=self.warm_power_iters,
                                       v0=v_prev)
            self._eig_cache[bucket] = v
            return 1.05 * eig

    # -- mixed-precision lo-phase staging -------------------------------
    # The strategy signature is fixed at (eng, Xr, lam, beta0, m), so the
    # bf16 buffers for a solve are STAGED on the engine by solve()/
    # solve_batched() and consumed exactly once by the fista/cd strategies
    # via _take_lo(). Strategies without a certified lo phase never see
    # them (_stage_lo only arms fista + cd and warns once otherwise).

    def _stage_lo(self, Xr, lo) -> None:
        """Arm the bf16 phase for the next strategy dispatch. ``lo`` is the
        caller-provided ``(X_lo, col_err, col_norms)`` triple (the path
        driver gathers it from the geometry's cached bf16 copy — one cache
        for screens and solves); None builds it from Xr on the fly."""
        self._lo = None
        self.last_effective_dtype = "float32"
        if self.solve_dtype != "bfloat16":
            return
        if self.solver not in ("fista", "cd"):
            _note_solve_f32_fallback(self.solver)
            return
        if lo is None:
            X_lo = jnp.asarray(Xr, jnp.bfloat16)
            col_err = ops.bf16_column_err(Xr, X_lo)
            col_norms = jnp.linalg.norm(jnp.asarray(Xr, jnp.float32), axis=0)
            lo = (X_lo, col_err, col_norms)
        X_lo, col_err, col_norms = lo
        # scalar worst-case bounds over the bucket (padding columns are
        # zero in both copies, so their err/norm of 0 can't raise the max)
        self._lo = (jnp.asarray(X_lo), jnp.max(jnp.asarray(col_err)),
                    jnp.max(jnp.asarray(col_norms)))
        self.last_effective_dtype = "bfloat16"

    def _take_lo(self):
        lo, self._lo = self._lo, None
        return lo

    def solve(self, Xr, lam, beta0=None, m: int = 1, lo=None) -> SolveResult:
        """Solve the reduced problem on the bucket buffer Xr (zero-padded
        columns are fixed points). Returns the SolveResult; telemetry in
        ``last_gap_checks`` / ``last_used_gram`` / ``last_solve_bytes``.

        ``lo``: optional ``(X_lo, col_err, col_norms)`` bf16 bucket triple
        for ``solve_dtype="bfloat16"`` (gathered from the geometry cache by
        the path driver); ignored for f32 engines, built from Xr when the
        engine is bf16 and the caller didn't pass one."""
        Xr = jnp.asarray(Xr)
        if beta0 is None:
            beta0 = jnp.zeros((Xr.shape[1],), dtype=Xr.dtype)
        self._stage_lo(Xr, lo)
        res, info = SOLVERS[self.solver](self, Xr, lam, beta0, m)
        self.n_solves += 1
        self.last_used_gram = bool(info.get("gram", False))
        self.last_member_major = bool(info.get("member_major", False))
        self.gram_solves += int(self.last_used_gram)
        self.last_gap_checks = int(tracing.fetch(res.gap_checks))
        self.total_gap_checks += self.last_gap_checks
        # Data-movement telemetry in passes over the *reduced* buffer:
        # FISTA reads Xr twice per iteration (fit + fused gradient), CD
        # streams the columns once per epoch, Gram CD reads Xr once to
        # build G (sweeps then cost b/n of a pass each); every gap check
        # adds two passes (residual + correlations).
        it, ck = int(tracing.fetch(res.iters)), self.last_gap_checks
        n, b = Xr.shape
        if "x_passes" in info:
            # mixed-precision Gram CD computes its own total (two G
            # builds on handover, VMEM sweeps, f32 certificate passes)
            self.last_x_passes = float(info["x_passes"])
        elif self.last_used_gram:
            self.last_x_passes = 1.0 + it * (b / max(n, 1)) + 2.0 * ck
        elif self.solver == "cd":
            self.last_x_passes = float(it) + 2.0 * ck
        else:
            self.last_x_passes = 2.0 * it + 2.0 * ck
        # Byte accounting: the bf16-phase ITERATION passes (2 per FISTA
        # iter; ONE G-build pass for Gram CD, reported via "lo_passes")
        # moved 2-byte elements; every gap check — bf16 phase included —
        # and every f32-phase pass moved 4-byte elements. it/ck above
        # already include the lo phase (the strategies sum both phases).
        lo_it = int(info.get("lo_iters", 0))
        lo_passes = float(info.get("lo_passes", 2.0 * lo_it))
        self.last_lo_iters = lo_it
        self.last_solve_bytes = (
            (self.last_x_passes - lo_passes) * n * b * 4.0
            + lo_passes * n * b * 2.0)
        self.total_solve_bytes += self.last_solve_bytes
        return res

    def solve_batched(self, Xr, lam, beta0=None, valid=None,
                      m: int = 1, lo=None) -> SolveResult:
        """Solve B reduced problems that share the bucket buffer Xr.

        The engine must have been built with y of shape (B, n); ``lam`` is
        the per-query λ (B,), ``valid`` (B, b) ∈ {0, 1} masks the columns
        each query kept (the buffer holds the union of survivors across
        the batch — see the batched path driver). Every pass over Xr
        serves all B queries; converged queries freeze in place (their β
        is untouched by further batched iterations). ``last_x_passes``
        counts buffer passes per *batch* — divide by B for the amortised
        per-query cost.
        """
        Xr = jnp.asarray(Xr)
        if self.y.ndim != 2:
            raise ValueError("solve_batched needs a batched engine "
                             "(construct SolverEngine with y of shape (B, n))")
        bsz = self.y.shape[0]
        lam = jnp.asarray(lam, Xr.dtype)
        if beta0 is None:
            beta0 = jnp.zeros((bsz, Xr.shape[1]), dtype=Xr.dtype)
        if valid is None:
            valid = jnp.ones((bsz, Xr.shape[1]), dtype=Xr.dtype)
        n, b = Xr.shape

        def _passes(it: int, ck: int, gram: bool) -> float:
            # same per-solve formulas as solve(): Gram builds G once then
            # sweeps in VMEM; matvec CD streams once per epoch; FISTA
            # reads the buffer twice per iteration; each gap check adds 2.
            if gram:
                return 1.0 + it * (b / max(n, 1)) + 2.0 * ck
            if self.solver == "cd":
                return float(it) + 2.0 * ck
            return 2.0 * it + 2.0 * ck

        self._stage_lo(Xr, lo)
        strategy = BATCHED_SOLVERS.get(self.solver)
        if strategy is not None:
            res, info = strategy(self, Xr, lam, beta0, valid, m)
            self.last_gap_checks = int(tracing.fetch(res.gap_checks))
            # Shared-pass accounting: one buffer pass serves the whole
            # batch, and each phase's loop runs until ITS last query
            # converges — the bf16 phase contributes 2·max(lo_iters)
            # iteration passes at 2 bytes/elt plus 2·lo_checks f32
            # certificate passes, the f32 polish max(hi_iters) at 4.
            lo_it = int(info.get("lo_iters", 0))
            lo_passes = float(info.get("lo_passes", 2.0 * lo_it))
            if "x_passes" in info:
                # mixed-precision Gram CD reports its own total (see
                # solve(): builds + VMEM sweeps + certificate passes)
                self.last_x_passes = float(info["x_passes"])
            else:
                lo_ck = int(info.get("lo_checks", 0))
                hi_it = int(info.get(
                    "hi_iters", int(tracing.fetch(jnp.max(res.iters)))))
                hi_ck = self.last_gap_checks - lo_ck
                self.last_x_passes = (
                    _passes(hi_it, hi_ck, bool(info.get("gram", False)))
                    + lo_passes + 2.0 * lo_ck)
            self.last_lo_iters = lo_it
            self.last_solve_bytes = (
                (self.last_x_passes - lo_passes) * n * b * 4.0
                + lo_passes * n * b * 2.0)
        else:
            # per-query fallback: loops the single-query strategy (custom
            # registered solvers without a batched twin stay usable)
            parts, checks, gram, passes = [], 0, False, 0.0
            y_full = self.y
            try:
                for qb in range(bsz):
                    self.y = y_full[qb]
                    # zero the columns this query screened out: they become
                    # solver fixed points, so the single-query strategy
                    # solves exactly the query's OWN reduced problem (gap /
                    # converged describe the returned β, matching the
                    # native batched strategies' `valid` pinning)
                    Xq = Xr * valid[qb][None, :]
                    # the per-bucket Lipschitz cache must not leak between
                    # differently-masked buffers: a cached eigenvector
                    # supported only on another query's columns lies in
                    # Xq's null space and warm power iteration would
                    # return eig ≈ 0 (divergent step). Cold-start each
                    # query instead.
                    self._eig_cache.pop(Xq.shape[1], None)
                    r, info_b = SOLVERS[self.solver](
                        self, Xq, lam[qb], beta0[qb] * valid[qb], m)
                    parts.append(r)
                    checks += int(tracing.fetch(r.gap_checks))
                    gram_b = bool(info_b.get("gram", False))
                    gram = gram or gram_b
                    # passes here are per-query, NOT shared: sum them
                    passes += _passes(int(tracing.fetch(r.iters)),
                                      int(tracing.fetch(r.gap_checks)),
                                      gram_b)
            finally:
                self.y = y_full
            res = SolveResult(
                beta=jnp.stack([r.beta for r in parts]),
                gap=jnp.stack([r.gap for r in parts]),
                iters=jnp.stack([jnp.asarray(r.iters) for r in parts]),
                converged=jnp.stack([jnp.asarray(r.converged)
                                     for r in parts]),
                gap_checks=jnp.asarray(checks),
            )
            info = {"gram": gram}
            self.last_gap_checks = checks
            self.last_x_passes = passes
            self.last_lo_iters = 0
            self.last_solve_bytes = passes * n * b * 4.0
        self.n_solves += 1
        self.last_used_gram = bool(info.get("gram", False))
        self.last_member_major = bool(info.get("member_major", False))
        self.gram_solves += int(self.last_used_gram)
        self.total_gap_checks += self.last_gap_checks
        self.total_solve_bytes += self.last_solve_bytes
        return res


# ---------------------------------------------------------------------------
# Back-compat entry points (the old core.lasso / core.group_lasso solvers).
# Same signatures and semantics; now thin wrappers over the strategies.
# ---------------------------------------------------------------------------

def _as_beta0(beta0, p, dtype):
    if beta0 is None:
        return jnp.zeros((p,), dtype=dtype)
    return jnp.asarray(beta0, dtype)


def fista(X, y, lam, beta0=None, *, max_iter: int = 2000, tol: float = 1e-8,
          check_every: int = 10, lipschitz=None,
          backend=None) -> SolveResult:
    """FISTA for the Lasso with duality-gap stopping (see `_fista_solve`)."""
    X = jnp.asarray(X)
    if lipschitz is None:
        lipschitz = top_eigenpair(X)[0] * 1.05
    return _fista_solve(resolve_solver_backend(backend), X, jnp.asarray(y),
                        lam, _as_beta0(beta0, X.shape[1], X.dtype),
                        lipschitz, tol, max_iter, max(1, check_every))


def cd(X, y, lam, beta0=None, *, max_epochs: int = 200, tol: float = 1e-10,
       check_every: int = 1) -> SolveResult:
    """Cyclic coordinate descent with residual updates (see `_cd_solve`)."""
    X = jnp.asarray(X)
    return _cd_solve(X, jnp.asarray(y), lam,
                     _as_beta0(beta0, X.shape[1], X.dtype), tol, max_epochs,
                     max(1, check_every))


def group_fista(X, y, lam, m: int, beta0=None, *, max_iter: int = 2000,
                tol: float = 1e-8, check_every: int = 10,
                lipschitz=None) -> SolveResult:
    """Accelerated proximal gradient for the group Lasso."""
    X = jnp.asarray(X)
    if lipschitz is None:
        lipschitz = top_eigenpair(X)[0] * 1.05
    return _group_fista_solve(X, jnp.asarray(y), lam, m,
                              _as_beta0(beta0, X.shape[1], X.dtype),
                              lipschitz, tol, max_iter, max(1, check_every))
