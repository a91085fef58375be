"""Lasso objective/dual geometry helpers shared by every solver strategy.

The actual solvers (FISTA, coordinate descent, their Gram variants and the
group-Lasso block FISTA) live in :mod:`repro.core.solver` as strategies
dispatched by the :class:`~repro.core.solver.SolverEngine`; the public
``fista`` / ``cd`` entry points are re-exported from there. This module owns
the math they share:

Primal:  P(β)  = ½‖y − Xβ‖² + λ‖β‖₁                      (paper eq. 1)
Dual:    D(θ)  = ½‖y‖² − λ²/2 ‖θ − y/λ‖²  s.t. |x_iᵀθ|≤1  (paper eq. 2)
Duality gap is the stopping criterion; a feasible dual point is obtained by
scaling the residual into the polytope F.

``power_iteration`` / ``top_eigenpair`` estimate the Lipschitz constant
‖X‖₂² on matvecs (never forming the p×p Gram). The seed/key/dtype plumbing
is explicit and a pre-computed eigenvector can be passed as ``v0`` so
repeated path solves warm-start the estimate instead of re-running the full
iteration per bucket — the SolverEngine caches (eig, v) per bucket size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def soft_threshold(u: jax.Array, thresh) -> jax.Array:
    """Elementwise soft-thresholding operator S(u, t) = sign(u)·max(|u|−t, 0)."""
    return jnp.sign(u) * jnp.maximum(jnp.abs(u) - thresh, 0.0)


@functools.partial(jax.jit, static_argnames="iters")
@jax.named_scope("solve")
def _power_iterate(X: jax.Array, v0: jax.Array, iters: int):
    v = v0 / (jnp.linalg.norm(v0) + 1e-30)

    def body(_, v):
        w = X.T @ (X @ v)
        return w / (jnp.linalg.norm(w) + 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v)
    return jnp.sum(jnp.square(X @ v)), v


def top_eigenpair(X: jax.Array, iters: int = 50, *, v0=None, key=None,
                  seed: int = 0, dtype=None) -> tuple[jax.Array, jax.Array]:
    """(λ_max(XᵀX), eigenvector) via power iteration on matvecs.

    Never forms the p×p Gram matrix, so it is safe for p ≫ N. Pass ``v0``
    (e.g. the eigenvector from a previous, similar X) to warm-start: a few
    iterations then suffice where a cold start needs ~50.
    """
    dtype = X.dtype if dtype is None else dtype
    if v0 is None:
        if key is None:
            key = jax.random.PRNGKey(seed)
        v0 = jax.random.normal(key, (X.shape[1],), dtype=dtype)
    return _power_iterate(X, jnp.asarray(v0, dtype), iters)


def power_iteration(X: jax.Array, iters: int = 50, seed: int = 0, *,
                    v0=None, key=None, dtype=None) -> jax.Array:
    """Largest eigenvalue of XᵀX (= ‖X‖₂²); see :func:`top_eigenpair`."""
    return top_eigenpair(X, iters, v0=v0, key=key, seed=seed, dtype=dtype)[0]


def primal_objective(X, y, beta, lam):
    r = y - X @ beta
    return 0.5 * jnp.sum(jnp.square(r)) + lam * jnp.sum(jnp.abs(beta))


def dual_objective(y, theta, lam):
    return 0.5 * jnp.sum(jnp.square(y)) - 0.5 * lam**2 * jnp.sum(
        jnp.square(theta - y / lam)
    )


def feasible_dual_point(X, y, beta, lam):
    """Scale the residual into the dual polytope F = {θ : ‖Xᵀθ‖∞ ≤ 1}.

    θ̃ = s·r/λ with s = min(1, λ/‖Xᵀr‖∞). At the optimum r/λ = θ* and s = 1.
    """
    r = y - X @ beta
    corr = jnp.max(jnp.abs(X.T @ r))
    s = jnp.minimum(1.0, lam / (corr + 1e-30))
    return s * r / lam


def gap_from_residual(r, dot, beta, lam, y):
    """Duality gap from a precomputed residual r = y − Xβ and dot = Xᵀr.

    Identical arithmetic to :func:`duality_gap` with the two X passes
    hoisted out — the solver strategies' cadence-amortised gap check, and
    the Gram CD path's zero-extra-pass check (its dot comes from c − Gβ).
    """
    corr = jnp.max(jnp.abs(dot))
    s = jnp.minimum(1.0, lam / (corr + 1e-30))
    return (0.5 * jnp.sum(jnp.square(r)) + lam * jnp.sum(jnp.abs(beta))
            - 0.5 * jnp.sum(jnp.square(y))
            + 0.5 * jnp.sum(jnp.square(s * r - y)))


def duality_gap(X, y, beta, lam):
    """The certificate P(β) − D(θ̃), both X passes at full f32 precision
    (TPU's default f32 matmul is one bf16 pass)."""
    hi = jax.lax.Precision.HIGHEST
    r = y - jnp.matmul(X, beta, precision=hi)
    return gap_from_residual(r, jnp.matmul(X.T, r, precision=hi), beta, lam,
                             y)
