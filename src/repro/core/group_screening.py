"""Group-Lasso screening: group-EDPP (paper §3, Corollary 21) + group strong.

The paper's group-EDPP is, to its knowledge, the first *exact* (safe)
screening rule for the group Lasso. Same three-step recipe as the Lasso:
estimate θ*(λ) in a ball (Theorem 19, via the ray Lemma 18 + firm
nonexpansiveness), take the sup of ‖X_gᵀθ‖ over the ball (Theorem 20), test
against √n_g.

Equal contiguous groups of size ``m`` (the paper's §4.2 layout). The
per-step pieces the engine and the path driver use (``group_v2_perp``,
``group_kkt_violations``) also take a (B, n) batch of queries with
per-query λ (B,), row by row the single-query arithmetic.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

EPS_DEFAULT = 1e-6


class GroupDualState(NamedTuple):
    theta: jax.Array      # θ*(λ₀) via KKT eq. (52)
    lam: jax.Array
    v1: jax.Array         # v̄₁ of eq. (59)


def _group_view(X: jax.Array, m: int) -> jax.Array:
    """(N, p) → (G, N, m) group-major view of the design matrix."""
    n = X.shape[0]
    return jnp.moveaxis(X.reshape(n, -1, m), 1, 0)


def group_spectral_norms(X: jax.Array, m: int) -> jax.Array:
    """Exact ‖X_g‖₂ per group: top singular value via eigh of the m×m Gram.

    Theorem 20 uses the *operator* norm of each X_g (its proof bounds
    ‖X_gᵀ(θ*−o)‖ ≤ ‖X_g‖₂‖θ*−o‖); the Frobenius norm would also be safe but
    strictly looser. m is small, so the m×m eigh is cheap and batched.
    """
    Xg = _group_view(X, m)                       # (G, N, m)
    grams = jnp.einsum("gnm,gnk->gmk", Xg, Xg)   # (G, m, m)
    eig = jnp.linalg.eigvalsh(grams)[..., -1]
    return jnp.sqrt(jnp.maximum(eig, 0.0))


def group_state_at_lambda_max(X: jax.Array, y: jax.Array, m: int) -> GroupDualState:
    """β* = 0, θ* = y/λ̄_max (eq. 57); v̄₁ = X*X*ᵀy (eq. 59, Lemma 18)."""
    corr = (X.T @ y).reshape(-1, m)                       # (G, m)
    gnorms = jnp.linalg.norm(corr, axis=1) / jnp.sqrt(float(m))
    gstar = jnp.argmax(gnorms)
    lmax = gnorms[gstar]
    Xg = _group_view(X, m)                                # (G, N, m)
    Xstar = Xg[gstar]                                     # (N, m)
    v1 = Xstar @ (Xstar.T @ y)
    return GroupDualState(theta=y / lmax, lam=lmax, v1=v1)


def group_state_from_solution(X, y, beta, lam) -> GroupDualState:
    lam = jnp.asarray(lam, dtype=X.dtype)
    theta = (y - X @ beta) / lam
    return GroupDualState(theta=theta, lam=lam, v1=y / lam - theta)


def make_group_dual_state(X, y, beta, lam, lam_max_val, m: int) -> GroupDualState:
    smax = group_state_at_lambda_max(X, y, m)
    sseq = group_state_from_solution(X, y, beta, lam)
    at_max = lam >= lam_max_val * (1.0 - 1e-12)
    return GroupDualState(
        theta=jnp.where(at_max, smax.theta, sseq.theta),
        lam=jnp.where(at_max, smax.lam, sseq.lam),
        v1=jnp.where(at_max, smax.v1, sseq.v1),
    )


def group_v2_perp(y, lam_next, state: GroupDualState) -> jax.Array:
    v1 = state.v1
    if y.ndim == 2:                                       # (B, n) batch
        v2 = y / jnp.asarray(lam_next)[:, None] - state.theta
        denom = jnp.sum(jnp.square(v1), axis=-1) + 1e-30
        return v2 - (jnp.sum(v1 * v2, axis=-1) / denom)[:, None] * v1
    v2 = y / lam_next - state.theta                       # eq. (68)
    denom = jnp.sum(jnp.square(v1)) + 1e-30
    return v2 - (jnp.dot(v1, v2) / denom) * v1            # eq. (69)


def group_edpp_mask(
    X, y, lam_next, state: GroupDualState, m: int,
    spec_norms: jax.Array | None = None, eps: float = EPS_DEFAULT,
):
    """Group-EDPP (Corollary 21): discard group g iff

        ‖X_gᵀ(θ*(λ₀) + ½v̄₂⊥)‖₂ < √n_g − ½‖v̄₂⊥‖₂·‖X_g‖₂.

    Returns bool[G]. ``spec_norms`` may be precomputed once per path.
    """
    vp = group_v2_perp(y, lam_next, state)
    centre = state.theta + 0.5 * vp
    rho = 0.5 * jnp.linalg.norm(vp)
    if spec_norms is None:
        spec_norms = group_spectral_norms(X, m)
    scores = jnp.linalg.norm((X.T @ centre).reshape(-1, m), axis=1)
    return scores < jnp.sqrt(float(m)) - rho * spec_norms - eps


def group_strong_mask(X, y, lam_next, state: GroupDualState, m: int,
                      eps: float = EPS_DEFAULT):
    """Group strong rule (Tibshirani et al. 2012), heuristic:
    discard g iff ‖X_gᵀ(y − Xβ*(λ₀))‖ < √n_g(2λ − λ₀). Needs a KKT check."""
    resid = state.theta * state.lam
    scores = jnp.linalg.norm((X.T @ resid).reshape(-1, m), axis=1)
    return scores < jnp.sqrt(float(m)) * (2.0 * lam_next - state.lam) - eps


def group_kkt_violations(X, y, beta, lam, discarded_groups, m: int,
                         tol: float = 1e-4, fitted=None):
    """Discarded groups violating ‖X_gᵀr‖ ≤ λ√n_g (KKT eq. 53).
    ``fitted`` (= Xβ) skips the full X·β pass — see kkt_violations.
    Batched: y/fitted (B, n), beta (B, p), lam (B,), discarded (B, G)."""
    if y.ndim == 2:
        r = y - (beta @ X.T if fitted is None else fitted)
        dot = (r @ X).reshape(r.shape[0], -1, m)
        lam = jnp.asarray(lam)[:, None]
    else:
        r = y - (X @ beta if fitted is None else fitted)
        dot = (X.T @ r).reshape(-1, m)
    scores = jnp.linalg.norm(dot, axis=-1)
    viol = scores > lam * jnp.sqrt(float(m)) * (1.0 + tol)
    return jnp.logical_and(viol, discarded_groups)


GROUP_RULES = {
    "edpp": group_edpp_mask,
    "strong": group_strong_mask,
}


@functools.partial(jax.jit, static_argnames=("rule", "m"))
def group_screen(X, y, lam_next, state: GroupDualState, m: int,
                 rule: str = "edpp", spec_norms=None, eps: float = EPS_DEFAULT):
    if rule == "edpp":
        return group_edpp_mask(X, y, lam_next, state, m, spec_norms, eps)
    return group_strong_mask(X, y, lam_next, state, m, eps)
