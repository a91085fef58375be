"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; the kernels must match them to float tolerance
across the shape/dtype sweep in tests/test_kernels.py.

Batch axis
----------
Every query-side op is **batch-polymorphic**: the query operand (``centre``
for the screens, ``r``/``z``/``beta`` for the solver steps) may carry a
leading batch axis B, in which case the per-query parameters (``rho``,
``step``, ``lam``, ``mom``) may each be a scalar (shared) or a ``(B,)``
vector, and the outputs grow the same leading axis. X is never batched —
one fitted dictionary serves all B queries, which is the whole point: a
batched call reads X from HBM **once** for the entire batch. Rank-1 inputs
take the exact pre-batch code paths, so single-query results are
bit-identical to the unbatched implementation.

Mixed precision
---------------
Every op accepts bf16 X with f32 accumulation (``_acc_dtype``): scores
may then deviate from the f32 pass by at most ``‖c‖·e_j`` per column,
where ``e_j`` is the measured quantisation error bound of
``repro.kernels.ops.bf16_column_err``. The engine's margin fallback
(docs/kernels.md) re-tests threshold-adjacent columns in f32 so the
final masks stay bit-identical; these oracles make no such promise on
their own — they are exact only for the dtype they are given.

The solver steps accept bf16 X the same way: the SolverEngine's
mixed-precision mode iterates through a bf16 copy while its duality-gap
certificates recompute with f32 X, so solver exactness also never rests
on these oracles' low-precision outputs
(docs/solvers.md#mixed-precision-solves).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


#: Precision of every dot here and in the kernels. TPU's default f32 matmul
#: is one bf16 pass (~2⁻⁹ relative error): a screen decides at 1 − 1e-6,
#: and FISTA stalls above a 1e-6 gap on a gradient that coarse.
HIGHEST = jax.lax.Precision.HIGHEST


def _matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _acc_dtype(X: jax.Array):
    """Accumulation dtype: f32 for f32/bf16 inputs (the kernels' contract),
    but NEVER downcast — f64 inputs (jax_enable_x64 callers) stay f64."""
    return jnp.promote_types(X.dtype, jnp.float32)


def _per_query(s, batch: int, dtype) -> jax.Array:
    """Broadcast a scalar-or-(B,) per-query parameter to (B,) in dtype."""
    return jnp.broadcast_to(jnp.asarray(s, dtype), (batch,))


def edpp_screen_ref(X: jax.Array, centre: jax.Array, rho) -> tuple[jax.Array, jax.Array]:
    """Fused screening pass (EDPP/DPP family, Theorem 16 LHS+RHS combined).

    Returns (scores, sumsq) with
        scores[j] = |x_jᵀ·centre| + rho·‖x_j‖₂
        sumsq[j]  = ‖x_j‖₂²
    Discard feature j iff scores[j] < 1 − eps. Batched: centre (B, n) and
    rho scalar-or-(B,) give scores (B, p); sumsq stays (p,) (it is a
    property of the dictionary, not the query).
    """
    acc = _acc_dtype(X)
    Xa = X.astype(acc)
    ca = centre.astype(acc)
    sumsq = jnp.sum(jnp.square(Xa), axis=0)
    if ca.ndim == 2:
        dot = _matmul(ca, Xa)                         # (B, p)
        rho_b = _per_query(rho, ca.shape[0], acc)
        scores = jnp.abs(dot) + rho_b[:, None] * jnp.sqrt(sumsq)
        return scores, sumsq
    dot = _matmul(Xa.T, ca)
    scores = jnp.abs(dot) + jnp.asarray(rho, acc) * jnp.sqrt(sumsq)
    return scores, sumsq


def screen_matvec_ref(X: jax.Array, centre: jax.Array) -> jax.Array:
    """Plain screening matvec: dot[j] = x_jᵀ·centre (norms cached by caller).
    Batched: centre (B, n) → dot (B, p), one logical pass over X for all B."""
    acc = _acc_dtype(X)
    if centre.ndim == 2:
        return _matmul(centre.astype(acc), X.astype(acc))
    return _matmul(X.astype(acc).T, centre.astype(acc))


def group_screen_ref(X: jax.Array, centre: jax.Array, m: int) -> jax.Array:
    """Group screening scores (Corollary 21 LHS): per contiguous group of m,

        gscores[g] = ‖X_gᵀ·centre‖₂

    Batched: centre (B, n) → gscores (B, G), one logical pass over X.
    """
    dot = screen_matvec_ref(X, centre)
    return jnp.linalg.norm(dot.reshape(*dot.shape[:-1], -1, m), axis=-1)


def prox_step_ref(z: jax.Array, g: jax.Array, beta_old: jax.Array,
                  step, lam, mom) -> tuple[jax.Array, jax.Array]:
    """Fused FISTA inner update (one HBM pass over 3 p-vectors):

        u        = z − step·g
        beta_new = sign(u)·max(|u| − step·lam, 0)
        z_new    = beta_new + mom·(beta_new − beta_old)

    Batched: z/g/beta_old (B, p) with step/lam/mom scalar-or-(B,).
    """
    if z.ndim == 2:
        acc = z.dtype
        step = _per_query(step, z.shape[0], acc)[:, None]
        lam = _per_query(lam, z.shape[0], acc)[:, None]
        mom = _per_query(mom, z.shape[0], acc)[:, None]
    u = z - step * g
    t = step * lam
    beta_new = jnp.sign(u) * jnp.maximum(jnp.abs(u) - t, 0.0)
    z_new = beta_new + mom * (beta_new - beta_old)
    return beta_new, z_new


def fista_step_ref(X: jax.Array, r: jax.Array, z: jax.Array,
                   beta_old: jax.Array, step, lam, mom
                   ) -> tuple[jax.Array, jax.Array]:
    """Fused FISTA iteration tail: gradient matvec + prox + momentum.

    Given the residual r = Xz − y (the n-sized forward fit is the caller's
    one other pass over X), this is ONE streaming pass over X's columns:

        g[j]     = x_jᵀ·r
        u        = z − step·g
        beta_new = S(u, step·lam)
        z_new    = beta_new + mom·(beta_new − beta_old)

    Unfused, g round-trips to HBM as a p-vector and the prox re-reads
    (z, g, beta_old); fused, the gradient block never leaves VMEM.
    Batched: r (B, n), z/beta_old (B, p), step/lam/mom scalar-or-(B,) —
    the B gradients come out of the same single pass over X's columns.
    """
    acc = _acc_dtype(X)
    if r.ndim == 2:
        g = _matmul(r.astype(acc), X.astype(acc))     # (B, p)
    else:
        g = _matmul(X.astype(acc).T, r.astype(acc))
        step = jnp.asarray(step, acc)
        lam = jnp.asarray(lam, acc)
        mom = jnp.asarray(mom, acc)
    return prox_step_ref(z.astype(acc), g, beta_old.astype(acc),
                         step, lam, mom)


def cd_gram_sweep_ref(G: jax.Array, c: jax.Array, beta: jax.Array, lam,
                      sweeps: int = 1, valid: jax.Array | None = None
                      ) -> jax.Array:
    """``sweeps`` cyclic coordinate-descent sweeps over the Gram system.

    G = XᵀX and c = Xᵀy are precomputed by the caller (one pass over the
    reduced bucket per solve); each coordinate update is then O(p) on the
    Gram row with the correlation vector q = Gβ maintained incrementally:

        ρ_j  = c_j − q_j + G_jj·β_j
        β_j' = S(ρ_j, λ) / G_jj            (0 where G_jj = 0: padded cols)
        q   += G_:,j·(β_j' − β_j)

    No pass over X at all — the n ≪ p regime's win once G is resident.
    Batched: G stays (p, p) (shared dictionary Gram), c/beta grow to
    (B, p), lam is scalar-or-(B,), and ``valid`` (B, p) ∈ {0, 1} pins each
    query's screened-out columns at 0 so every query solves *its own*
    reduced problem on the shared union bucket.
    """
    p = G.shape[0]
    if beta.ndim == 2:
        lam_b = _per_query(lam, beta.shape[0], beta.dtype)
        q = _matmul(beta, G)                          # (B, p); G symmetric

        def coord_b(i, carry):
            beta, q = carry
            j = i % p
            gjj = G[j, j]
            rho = c[:, j] - q[:, j] + gjj * beta[:, j]
            bn = jnp.where(
                gjj > 0,
                jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam_b, 0.0)
                / jnp.maximum(gjj, 1e-30),
                0.0,
            )
            if valid is not None:
                bn = bn * valid[:, j]
            q = q + G[:, j][None, :] * (bn - beta[:, j])[:, None]
            return beta.at[:, j].set(bn), q

        beta, _ = jax.lax.fori_loop(0, sweeps * p, coord_b, (beta, q))
        return beta

    q = _matmul(G, beta)

    def coord(i, carry):
        beta, q = carry
        j = i % p
        gjj = G[j, j]
        rho = c[j] - q[j] + gjj * beta[j]
        bn = jnp.where(
            gjj > 0,
            jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0)
            / jnp.maximum(gjj, 1e-30),
            0.0,
        )
        q = q + G[:, j] * (bn - beta[j])
        return beta.at[j].set(bn), q

    beta, _ = jax.lax.fori_loop(0, sweeps * p, coord, (beta, q))
    return beta
