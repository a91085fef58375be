"""Pure-numpy float64 Lasso / group-Lasso oracles for the test suite.

Deliberately independent of JAX (and of the JAX_ENABLE_X64 flag), so the
safety property tests compare the JAX implementation against solutions of
certified precision.
"""

from __future__ import annotations

import numpy as np


def soft(u, t):
    return np.sign(u) * np.maximum(np.abs(u) - t, 0.0)


def _rel_gap(X, y, beta, lam):
    """Relative duality gap (P − D)/(½‖y‖²) of β, with the residual
    scaled into the dual polytope."""
    r = y - X @ beta
    corr = np.abs(X.T @ r).max()
    s = min(1.0, lam / (corr + 1e-300))
    theta = s * r / lam
    primal = 0.5 * r @ r + lam * np.abs(beta).sum()
    dual = 0.5 * y @ y - 0.5 * lam**2 * ((theta - y / lam) ** 2).sum()
    return (primal - dual) / (0.5 * y @ y + 1e-300)


def _support_solve(X, y, beta, lam):
    """The Lasso's KKT equations on β's support and signs, solved exactly:
    β_A = (X_AᵀX_A)⁻¹(X_Aᵀy − λ·sign(β_A)), zero elsewhere. None when the
    system is singular or the solution flips a sign (the support or the
    signs are not yet the optimum's)."""
    A = np.flatnonzero(beta)
    if A.size == 0 or A.size > X.shape[0]:
        return None
    XA, sA = X[:, A], np.sign(beta[A])
    try:
        bA = np.linalg.solve(XA.T @ XA, XA.T @ y - lam * sA)
    except np.linalg.LinAlgError:
        return None
    if np.any(np.sign(bA) != sA):
        return None
    out = np.zeros_like(beta)
    out[A] = bA
    return out


def cd_lasso(X, y, lam, max_epochs=5000, tol=1e-13):
    """Cyclic coordinate descent to (relative) duality gap ``tol``.

    CD alone crawls where the support is as large as n and X_A is ill
    conditioned (n = 8, p = 53, λ = 0.055·λ_max stalls at a gap of 1e-5
    after 5000 epochs). After each epoch that misses ``tol``, the KKT
    equations on the iterate's support and signs are solved exactly; the
    solution is taken only when its own duality gap certifies ``tol``."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    n, p = X.shape
    beta = np.zeros(p)
    r = y.copy()
    sq = np.einsum("ij,ij->j", X, X)
    for _ in range(max_epochs):
        for j in range(p):
            if sq[j] == 0:
                continue
            bj = beta[j]
            rho = X[:, j] @ r + sq[j] * bj
            bn = soft(rho, lam) / sq[j]
            if bn != bj:
                r += X[:, j] * (bj - bn)
                beta[j] = bn
        if _rel_gap(X, y, beta, lam) <= tol:
            break
        exact = _support_solve(X, y, beta, lam)
        if exact is not None and _rel_gap(X, y, exact, lam) <= tol:
            return exact
    return beta


def group_soft(u, t, m):
    ug = u.reshape(-1, m)
    nrm = np.linalg.norm(ug, axis=1, keepdims=True)
    scale = np.maximum(0.0, 1.0 - t * np.sqrt(m) / (nrm + 1e-300))
    return (scale * ug).reshape(-1)


def fista_group(X, y, lam, m, max_iter=20000, tol=1e-13):
    """Block-FISTA group Lasso to (relative) duality gap ``tol``."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    p = X.shape[1]
    L = np.linalg.norm(X, 2) ** 2 * 1.01
    step = 1.0 / L
    beta = np.zeros(p)
    z = beta.copy()
    t = 1.0
    scale = 0.5 * y @ y + 1e-300
    for it in range(max_iter):
        g = X.T @ (X @ z - y)
        beta_new = group_soft(z - step * g, step * lam, m)
        t_new = 0.5 * (1 + np.sqrt(1 + 4 * t * t))
        z = beta_new + ((t - 1) / t_new) * (beta_new - beta)
        beta, t = beta_new, t_new
        if it % 50 == 0:
            r = y - X @ beta
            corr = np.linalg.norm((X.T @ r).reshape(-1, m), axis=1)
            ratio = (corr / np.sqrt(m)).max()
            s = min(1.0, lam / (ratio + 1e-300))
            theta = s * r / lam
            gnorms = np.linalg.norm(beta.reshape(-1, m), axis=1)
            primal = 0.5 * r @ r + lam * np.sqrt(m) * gnorms.sum()
            dual = (0.5 * y @ y
                    - 0.5 * lam**2 * ((theta - y / lam) ** 2).sum())
            if primal - dual <= tol * scale:
                break
    return beta
