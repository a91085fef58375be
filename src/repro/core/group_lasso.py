"""Group Lasso (paper eq. 50) objective/dual helpers.

    inf_β ½‖y − Σ_g X_g β_g‖² + λ Σ_g √n_g ‖β_g‖₂

We use the equal-group-size contiguous layout (n_g = p/G for all g), the
layout of the paper's own §4.2 experiments; groups live on the last axis as
``β.reshape(G, m)``. The dual (eq. 51) and KKT system (eqs. 52-53) mirror the
Lasso exactly, with the polytope replaced by an intersection of ellipsoids —
which is all the EDPP machinery needs (still closed + convex, Lemma 18).

The block-FISTA solver itself is the ``group_fista`` strategy in
:mod:`repro.core.solver` (re-exported here for compatibility); this module
owns the math it shares with the screening layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lasso import power_iteration  # noqa: F401  (compat re-export)


def group_soft_threshold(u: jax.Array, thresh, m: int) -> jax.Array:
    """Block soft-threshold: β_g = max(0, 1 − t√m/‖u_g‖)·u_g (groups of m)."""
    ug = u.reshape(-1, m)
    norms = jnp.linalg.norm(ug, axis=1, keepdims=True)
    scale = jnp.maximum(0.0, 1.0 - thresh * jnp.sqrt(float(m)) / (norms + 1e-30))
    return (scale * ug).reshape(-1)


# Member-major layout. A bucket of G groups of m columns is stored group-
# major, column g·m + i for member i of group g. Member-major stores that
# column at i·G + g: member i of every group is one contiguous run of G
# columns, so on a TPU the groups lie along the 128 lanes and each group's
# norm is a sum of m lane-dense slabs, where the group-major (…, G, m) view
# fills m of 128 lanes. The block solver works in this order inside its
# jitted body and converts at its boundary.

def to_member_major(a: jax.Array, m: int, axis: int = -1) -> jax.Array:
    """Reorder ``axis`` (G·m entries) from group-major to member-major:
    entry g·m + i moves to i·G + g."""
    axis %= a.ndim
    split = a.shape[:axis] + (a.shape[axis] // m, m) + a.shape[axis + 1:]
    return jnp.swapaxes(a.reshape(split), axis, axis + 1).reshape(a.shape)


def to_group_major(a: jax.Array, m: int, axis: int = -1) -> jax.Array:
    """Inverse of :func:`to_member_major`: the same reordering with the
    roles of G and m exchanged."""
    return to_member_major(a, a.shape[axis] // m, axis)


def member_major_norms(v: jax.Array, m: int) -> jax.Array:
    """Group norms (…, G) of a member-major (…, G·m) array: the square
    root of a sum of m slabs of G columns."""
    G = v.shape[-1] // m
    slabs = jnp.square(v).reshape(v.shape[:-1] + (m, G))
    return jnp.sqrt(jnp.sum(slabs, axis=-2))


def member_major_soft_threshold(u: jax.Array, thresh, m: int) -> jax.Array:
    """:func:`group_soft_threshold` on a member-major (…, G·m) array, with
    ``thresh`` scalar or one per leading row."""
    thresh = jnp.asarray(thresh)[..., None]
    scale = jnp.maximum(
        0.0, 1.0 - thresh * jnp.sqrt(float(m))
        / (member_major_norms(u, m) + 1e-30))
    G = u.shape[-1] // m
    return (u.reshape(u.shape[:-1] + (m, G))
            * scale[..., None, :]).reshape(u.shape)


def member_major_gap_from_residual(r, dot, beta, lam, m: int, y):
    """:func:`group_gap_from_residual` with ``dot`` = Xᵀr and ``beta``
    member-major; leading axes of ``r``, ``dot``, ``beta``, ``y`` and
    ``lam`` are batch axes (one gap per row)."""
    ratio = jnp.max(member_major_norms(dot, m), axis=-1) / jnp.sqrt(float(m))
    s = jnp.minimum(1.0, lam / (ratio + 1e-30))
    gnorms = member_major_norms(beta, m)
    return (0.5 * jnp.sum(jnp.square(r), axis=-1)
            + lam * jnp.sqrt(float(m)) * jnp.sum(gnorms, axis=-1)
            - 0.5 * jnp.sum(jnp.square(y), axis=-1)
            + 0.5 * jnp.sum(jnp.square(s[..., None] * r - y), axis=-1))


def group_lambda_max(X: jax.Array, y: jax.Array, m: int) -> jax.Array:
    """λ̄_max = max_g ‖X_gᵀy‖/√n_g (eq. 55)."""
    corr = (X.T @ y).reshape(-1, m)
    return jnp.max(jnp.linalg.norm(corr, axis=1)) / jnp.sqrt(float(m))


def group_primal(X, y, beta, lam, m: int):
    r = y - X @ beta
    gnorms = jnp.linalg.norm(beta.reshape(-1, m), axis=1)
    return 0.5 * jnp.sum(jnp.square(r)) + lam * jnp.sqrt(float(m)) * jnp.sum(gnorms)


def group_gap_from_residual(r, dot, beta, lam, m: int, y):
    """Group duality gap from precomputed r = y − Xβ and dot = Xᵀr.

    The dual point is r/λ scaled into F̄ = {θ: ‖X_gᵀθ‖ ≤ √n_g ∀g}; same
    hoisted-passes trick as :func:`repro.core.lasso.gap_from_residual`.
    """
    gcorr = jnp.linalg.norm(dot.reshape(-1, m), axis=1)
    ratio = jnp.max(gcorr) / jnp.sqrt(float(m))
    s = jnp.minimum(1.0, lam / (ratio + 1e-30))
    gnorms = jnp.linalg.norm(beta.reshape(-1, m), axis=1)
    return (0.5 * jnp.sum(jnp.square(r))
            + lam * jnp.sqrt(float(m)) * jnp.sum(gnorms)
            - 0.5 * jnp.sum(jnp.square(y))
            + 0.5 * jnp.sum(jnp.square(s * r - y)))


def group_duality_gap(X, y, beta, lam, m: int):
    """Gap with the dual point r/λ scaled into F̄ = {θ: ‖X_gᵀθ‖ ≤ √n_g ∀g}."""
    r = y - X @ beta
    return group_gap_from_residual(r, X.T @ r, beta, lam, m, y)
