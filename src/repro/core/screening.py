"""Safe (and heuristic-baseline) screening rules for the Lasso.

Implements the paper's full family plus every baseline it compares against:

  * DPP (Theorem 3 / Corollaries 4-5)
  * Improvement 1 — projections of rays (Theorems 7 & 11)
  * Improvement 2 — firm nonexpansiveness (Theorems 13 & 14)
  * EDPP (Theorems 15 & 16, Corollary 17)           ← the paper's main rule
  * SAFE / ST1 (eq. 15, El Ghaoui et al.)
  * sequential SAFE (sphere at y/λ with radius from the previous dual point)
  * GAP-safe sphere (Fercoq, Gramfort & Salmon 2015, Theorem 2)
  * strong rule (Tibshirani et al. 2012) — *heuristic*, requires KKT check
  * DOME (Xiang et al.) — basic rule only, exact sup over the dome region

Every rule is expressed as a *discard mask* computation: ``mask[i] == True``
means feature ``i`` is guaranteed (safe rules) or presumed (strong rule) to
satisfy ``β*_i(λ) = 0`` and can be removed from the problem.

Sphere geometry
---------------
Every ball-based rule above is the *same* test with a different ball: for a
sphere B(centre, ρ) that provably contains θ*(λ),

    discard i  ⟺  sup_{θ∈B} |x_iᵀθ| = |x_iᵀ·centre| + ρ‖x_i‖ < 1.

Each rule therefore exposes a ``<rule>_sphere`` constructor returning a
:class:`SphereTest` ``(centre, rho)`` alongside its mask function; the mask
functions are the pure-jnp oracles, and :mod:`repro.core.engine` evaluates
the identical test through the fused Pallas kernel (one HBM pass over X).

All rules share the sequential interface ``rule(X, y, lam_next, state)`` where
``state`` is a :class:`DualState` built from the solution at the previous
(larger) λ on the grid; the *basic* variants are the special case
``state = DualState.at_lambda_max(X, y)`` (paper Remark 3).

Batch axis
----------
The polytope F and the column norms depend on X only — every query-side
quantity (y, θ, v₁, λ, ρ) batches trivially. All sphere constructors and
mask oracles therefore accept a **leading batch axis B** on the query
operands: ``y``/``theta``/``v1`` as (B, n), ``lam``/``rho``/``beta_l1`` as
(B,), producing (B, p) masks — B response vectors screened against one
fitted dictionary in a single pass over X. Rank-1 inputs take the exact
pre-batch code paths, so single-query masks are unchanged bit-for-bit.

Strict inequalities are evaluated with a safety margin ``eps``: we only ever
*shrink* the discard set, preserving safety under floating point (DESIGN §9.4).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

EPS_DEFAULT = 1e-6


class DualState(NamedTuple):
    """Everything the sequential rules need about the previous grid point.

    theta:    θ*(λ₀) = (y − Xβ*(λ₀))/λ₀, the exact dual optimum (KKT eq. 3)
    lam:      λ₀
    v1:       ray direction of Theorem 7 / eq. (17)
    at_lmax:  whether λ₀ == λ_max (selects the v₁ branch of eq. 17)
    beta_l1:  ‖β*(λ₀)‖₁ — needed only by the GAP-safe sphere's duality gap
    """

    theta: jax.Array
    lam: jax.Array
    v1: jax.Array
    at_lmax: jax.Array
    beta_l1: jax.Array | float = 0.0

    @staticmethod
    def at_lambda_max(X: jax.Array, y: jax.Array) -> "DualState":
        """State at λ₀ = λ_max where β* = 0 and θ* = y/λ_max (eq. 9)."""
        corr = X.T @ y
        istar = jnp.argmax(jnp.abs(corr))
        lmax = jnp.abs(corr)[istar]
        xstar = X[:, istar]
        v1 = jnp.sign(corr[istar]) * xstar          # eq. (17), λ₀ = λ_max
        return DualState(
            theta=y / lmax,
            lam=lmax,
            v1=v1,
            at_lmax=jnp.asarray(True),
            beta_l1=jnp.zeros((), dtype=X.dtype),
        )

    @staticmethod
    def from_solution(
        X: jax.Array, y: jax.Array, beta: jax.Array, lam, lam_max=None
    ) -> "DualState":
        """State from the primal solution β*(λ₀) via KKT eq. (3)."""
        lam = jnp.asarray(lam, dtype=X.dtype)
        theta = (y - X @ beta) / lam
        v1 = y / lam - theta                         # eq. (17), λ₀ < λ_max
        at_lmax = jnp.asarray(False)
        if lam_max is not None:
            at_lmax = jnp.asarray(lam >= lam_max)
        return DualState(theta=theta, lam=lam, v1=v1, at_lmax=at_lmax,
                         beta_l1=jnp.sum(jnp.abs(beta)))


def lambda_max(X: jax.Array, y: jax.Array) -> jax.Array:
    """λ_max = max_i |x_iᵀy| (eq. 7): smallest λ with β*(λ) = 0."""
    return jnp.max(jnp.abs(X.T @ y))


def make_dual_state(X, y, beta, lam, lam_max_val) -> DualState:
    """Sequential-state constructor that is branch-correct at λ₀ == λ_max.

    jit-friendly: selects the eq. (17) branch with ``where`` so a single
    compiled program serves the whole λ-grid.
    """
    smax = DualState.at_lambda_max(X, y)
    sseq = DualState.from_solution(X, y, beta, lam)
    at_max = lam >= lam_max_val * (1.0 - 1e-12)
    return DualState(
        theta=jnp.where(at_max, smax.theta, sseq.theta),
        lam=jnp.where(at_max, smax.lam, sseq.lam),
        v1=jnp.where(at_max, smax.v1, sseq.v1),
        at_lmax=jnp.asarray(at_max),
        beta_l1=jnp.where(at_max, 0.0, sseq.beta_l1),
    )


# ---------------------------------------------------------------------------
# EDPP geometry (Theorems 7 & 15)
# ---------------------------------------------------------------------------

def _is_batched(y) -> bool:
    """Leading batch axis on the query operand (y or θ is (B, n))."""
    return jnp.ndim(y) == 2


def _col(s) -> jax.Array:
    """Per-query scalar(s) → broadcastable column: (B,) → (B, 1), () → (1,)."""
    return jnp.asarray(s)[..., None]


def v2_perp(y: jax.Array, lam_next, state: DualState) -> jax.Array:
    """v₂⊥(λ, λ₀) of eq. (19): component of v₂ orthogonal to the ray v₁."""
    v1 = state.v1
    if _is_batched(y):
        v2 = y / _col(lam_next) - state.theta        # eq. (18), (B, n)
        denom = jnp.sum(jnp.square(v1), axis=-1) + 1e-30
        return v2 - _col(jnp.sum(v1 * v2, axis=-1) / denom) * v1
    v2 = y / lam_next - state.theta                  # eq. (18)
    denom = jnp.sum(jnp.square(v1)) + 1e-30
    return v2 - (jnp.dot(v1, v2) / denom) * v1


# ---------------------------------------------------------------------------
# Sphere geometry: every ball rule as an explicit (centre, ρ) pair
# ---------------------------------------------------------------------------

class SphereTest(NamedTuple):
    """A safe sphere B(centre, rho) ∋ θ*(λ): discard i iff
    |x_iᵀ·centre| + rho·‖x_i‖ < 1 (up to the eps safety margin).

    Batched: centre (B, n) and rho (B,) hold B per-query spheres — the B
    tests still share one streaming pass over X (see core.engine).
    """

    centre: jax.Array
    rho: jax.Array


def dpp_sphere(y, lam_next, state: DualState) -> SphereTest:
    """DPP (Theorem 3): B(θ*(λ₀), |1/λ − 1/λ₀|·‖y‖)."""
    rho = jnp.abs(1.0 / jnp.asarray(lam_next) - 1.0 / state.lam) \
        * jnp.linalg.norm(y, axis=-1)
    return SphereTest(centre=state.theta, rho=rho)


def imp1_sphere(y, lam_next, state: DualState) -> SphereTest:
    """Improvement 1 (Theorem 11): B(θ*(λ₀), ‖v₂⊥‖)."""
    vp = v2_perp(y, lam_next, state)
    return SphereTest(centre=state.theta, rho=jnp.linalg.norm(vp, axis=-1))


def imp2_sphere(y, lam_next, state: DualState) -> SphereTest:
    """Improvement 2 (Theorem 14): half-radius ball at shifted centre."""
    d = 0.5 * (1.0 / jnp.asarray(lam_next) - 1.0 / state.lam)
    if _is_batched(y):
        return SphereTest(centre=state.theta + _col(d) * y,
                          rho=jnp.abs(d) * jnp.linalg.norm(y, axis=-1))
    return SphereTest(centre=state.theta + d * y,
                      rho=jnp.abs(d) * jnp.linalg.norm(y))


def edpp_sphere(y, lam_next, state: DualState) -> SphereTest:
    """EDPP (Theorem 16 / Corollary 17): B(θ*(λ₀) + ½v₂⊥, ½‖v₂⊥‖)."""
    vp = v2_perp(y, lam_next, state)
    return SphereTest(centre=state.theta + 0.5 * vp,
                      rho=0.5 * jnp.linalg.norm(vp, axis=-1))


def seq_safe_sphere(y, lam_next, state: DualState) -> SphereTest:
    """Sequential SAFE: B(y/λ, ‖y/λ − θ*(λ₀)‖).

    θ*(λ₀) ∈ F and θ*(λ) = P_F(y/λ) give ‖θ*(λ) − y/λ‖ ≤ ‖θ*(λ₀) − y/λ‖ —
    the recursive-SAFE construction (El Ghaoui et al.) instantiated with the
    previous exact dual point.
    """
    centre = y / _col(lam_next) if _is_batched(y) else y / lam_next
    return SphereTest(centre=centre,
                      rho=jnp.linalg.norm(centre - state.theta, axis=-1))


def safe_sphere(y, lam_next, lam_max_val) -> SphereTest:
    """Basic SAFE / ST1 (eq. 15) normalised to the unit test: dividing
    |x_iᵀy| < λ − ‖x_i‖‖y‖(λ_max − λ)/λ_max through by λ gives the sphere
    B(y/λ, ‖y‖(λ_max − λ)/(λ_max·λ))."""
    rho = jnp.linalg.norm(y, axis=-1) * (lam_max_val - lam_next) / (
        lam_max_val * lam_next)
    centre = y / _col(lam_next) if _is_batched(y) else y / lam_next
    return SphereTest(centre=centre, rho=rho)


def gap_sphere(y, lam_next, state: DualState, sup_corr=None) -> SphereTest:
    """GAP-safe sphere (Fercoq, Gramfort & Salmon 2015, Theorem 2).

    λ²-strong concavity of the dual gives, for ANY primal-dual feasible pair
    (β₀, θ_c):  ‖θ*(λ) − θ_c‖ ≤ √(2·G_λ(β₀, θ_c))/λ with G the duality gap
    at λ. We instantiate it with the previous grid point's (β₀, θ₀) — unlike
    the DPP family this stays safe even when β₀ is an *inexact* solve.

    ``sup_corr`` = ‖Xᵀθ₀‖∞ rescales θ₀ into the feasible polytope under
    floating point (θ_c = θ₀/max(1, sup_corr)); pass the value cached from
    the screening matvec, or None to trust θ₀'s feasibility.
    """
    if _is_batched(y):
        s = (jnp.ones(y.shape[:1], y.dtype) if sup_corr is None
             else jnp.maximum(1.0, sup_corr))
        centre = state.theta / _col(s)
        resid = state.theta * _col(state.lam)        # y − Xβ*(λ₀)
        lam_next = jnp.asarray(lam_next)
        primal = 0.5 * jnp.sum(jnp.square(resid), axis=-1) \
            + lam_next * state.beta_l1
        dual = 0.5 * jnp.sum(jnp.square(y), axis=-1) \
            - 0.5 * lam_next * lam_next * jnp.sum(
                jnp.square(centre - y / _col(lam_next)), axis=-1)
        gap = jnp.maximum(primal - dual, 0.0)
        return SphereTest(centre=centre, rho=jnp.sqrt(2.0 * gap) / lam_next)
    s = 1.0 if sup_corr is None else jnp.maximum(1.0, sup_corr)
    centre = state.theta / s
    resid = state.theta * state.lam                  # y − Xβ*(λ₀)
    primal = 0.5 * jnp.sum(jnp.square(resid)) + lam_next * state.beta_l1
    dual = 0.5 * jnp.sum(jnp.square(y)) - 0.5 * lam_next * lam_next * (
        jnp.sum(jnp.square(centre - y / lam_next)))
    gap = jnp.maximum(primal - dual, 0.0)
    return SphereTest(centre=centre, rho=jnp.sqrt(2.0 * gap) / lam_next)


SPHERE_RULES = {
    "dpp": dpp_sphere,
    "imp1": imp1_sphere,
    "imp2": imp2_sphere,
    "edpp": edpp_sphere,
    "seq_safe": seq_safe_sphere,
    "gap": gap_sphere,
}


@functools.partial(jax.jit, static_argnames=("rule",))
@jax.named_scope("screen")
def make_sphere(rule: str, y, lam_next, state: DualState) -> SphereTest:
    """Jitted dispatch over the sequential sphere constructors."""
    return SPHERE_RULES[rule](y, lam_next, state)


def sphere_mask(X, test: SphereTest, eps: float = EPS_DEFAULT):
    """Pure-jnp oracle for a SphereTest: the fused-score form
    |x_iᵀc| + ρ‖x_i‖ < 1 − eps, bit-matching kernels/ref.edpp_screen_ref.
    Batched tests (centre (B, n), rho (B,)) give a (B, p) mask."""
    if _is_batched(test.centre):
        scores = jnp.abs(test.centre @ X) \
            + _col(test.rho) * jnp.linalg.norm(X, axis=0)
        return scores < 1.0 - _col(jnp.asarray(eps))
    scores = jnp.abs(X.T @ test.centre) + test.rho * jnp.linalg.norm(X, axis=0)
    return scores < 1.0 - eps


# ---------------------------------------------------------------------------
# Discard-mask rules. All return bool[p]: True = discard (β*_i(λ_next) = 0).
# These are the pure-jnp oracles the engine is validated against.
# ---------------------------------------------------------------------------

def dpp_mask(X, y, lam_next, state: DualState, eps: float = EPS_DEFAULT):
    """DPP (Theorem 3): ball B(θ*(λ₀), |1/λ − 1/λ₀|·‖y‖)."""
    return sphere_mask(X, dpp_sphere(y, lam_next, state), eps)


def imp1_mask(X, y, lam_next, state: DualState, eps: float = EPS_DEFAULT):
    """Improvement 1 (Theorem 11): ball B(θ*(λ₀), ‖v₂⊥‖)."""
    return sphere_mask(X, imp1_sphere(y, lam_next, state), eps)


def imp2_mask(X, y, lam_next, state: DualState, eps: float = EPS_DEFAULT):
    """Improvement 2 (Theorem 14): half-radius ball at shifted centre."""
    return sphere_mask(X, imp2_sphere(y, lam_next, state), eps)


def edpp_mask(X, y, lam_next, state: DualState, eps: float = EPS_DEFAULT):
    """EDPP (Theorem 16 / Corollary 17) — the paper's main rule.

    Discard i iff  |x_iᵀ(θ*(λ₀) + ½v₂⊥)| < 1 − ½‖v₂⊥‖·‖x_i‖.
    """
    return sphere_mask(X, edpp_sphere(y, lam_next, state), eps)


def safe_mask(X, y, lam_next, lam_max_val, eps: float = EPS_DEFAULT):
    """Basic SAFE / ST1 (eq. 15): |x_iᵀy| < λ − ‖x_i‖‖y‖(λ_max − λ)/λ_max,
    evaluated in the unit-normalised sphere form (see safe_sphere). eq. 15's
    eps margin lives at λ scale, so it is eps/λ after normalisation."""
    return sphere_mask(X, safe_sphere(y, lam_next, lam_max_val),
                       eps / lam_next)


def seq_safe_mask(X, y, lam_next, state: DualState, eps: float = EPS_DEFAULT):
    """Sequential SAFE: sphere centred at y/λ with data-driven radius."""
    return sphere_mask(X, seq_safe_sphere(y, lam_next, state), eps)


def gap_mask(X, y, lam_next, state: DualState, eps: float = EPS_DEFAULT):
    """GAP-safe sphere rule (see gap_sphere). One matvec Xᵀθ₀ serves both
    the feasibility rescale ‖Xᵀθ₀‖∞ and the scores — the engine fuses this
    into a single HBM pass; this oracle mirrors the arithmetic exactly."""
    if _is_batched(y):
        dot = state.theta @ X                        # (B, p)
        sup_corr = jnp.max(jnp.abs(dot), axis=-1)
        test = gap_sphere(y, lam_next, state, sup_corr=sup_corr)
        s = jnp.maximum(1.0, sup_corr)
        scores = jnp.abs(dot) / _col(s) \
            + _col(test.rho) * jnp.linalg.norm(X, axis=0)
        return scores < 1.0 - eps
    dot = X.T @ state.theta
    sup_corr = jnp.max(jnp.abs(dot))
    test = gap_sphere(y, lam_next, state, sup_corr=sup_corr)
    s = jnp.maximum(1.0, sup_corr)
    scores = jnp.abs(dot) / s + test.rho * jnp.linalg.norm(X, axis=0)
    return scores < 1.0 - eps


def strong_mask(X, y, lam_next, state: DualState, eps: float = EPS_DEFAULT):
    """Sequential strong rule (Tibshirani et al. 2012). *Heuristic*:

    discard i iff |x_iᵀ(y − Xβ*(λ₀))| < 2λ − λ₀.
    May discard active features — callers MUST run the KKT violation loop
    (see path.py). Basic variant: state at λ_max gives |x_iᵀy| < 2λ − λ_max.
    """
    if _is_batched(y):
        resid_corr = jnp.abs((state.theta * _col(state.lam)) @ X)
        return resid_corr < _col(
            2.0 * jnp.asarray(lam_next) - state.lam - eps)
    resid_corr = jnp.abs(X.T @ (state.theta * state.lam))
    return resid_corr < 2.0 * lam_next - state.lam - eps


def _sup_over_dome(a_scores, a_gdot, a_norms, c, rho, ghat, b):
    """sup_{θ ∈ B(c,ρ) ∩ {ĝᵀθ ≤ b}} aᵀθ for a batch of directions a.

    a_scores = aᵀc, a_gdot = aᵀĝ, a_norms = ‖a‖ (vectorised over features).
    Closed form: decompose a along ĝ; the cap constraint clips the sphere
    maximiser at t_b = (b − ĝᵀc)/ρ. Query-batched inputs (a_scores/a_gdot
    (B, p), c/ghat (B, n), rho/b (B,)) give (B, p) sups.
    """
    if _is_batched(c):
        t_b = jnp.clip(
            (b - jnp.sum(ghat * c, axis=-1)) / (rho + 1e-30), -1.0, 1.0)
        t_star = a_gdot / (a_norms + 1e-30)
        a_perp = jnp.sqrt(jnp.maximum(
            jnp.square(a_norms) - jnp.square(a_gdot), 0.0))
        unclipped = a_scores + _col(rho) * a_norms
        clipped = a_scores + _col(rho) * (
            a_gdot * _col(t_b)
            + a_perp * _col(jnp.sqrt(jnp.maximum(1.0 - t_b * t_b, 0.0))))
        return jnp.where(t_star <= _col(t_b), unclipped, clipped)
    t_b = jnp.clip((b - jnp.dot(ghat, c)) / (rho + 1e-30), -1.0, 1.0)
    t_star = a_gdot / (a_norms + 1e-30)          # unconstrained maximiser
    a_perp = jnp.sqrt(jnp.maximum(jnp.square(a_norms) - jnp.square(a_gdot), 0.0))
    unclipped = a_scores + rho * a_norms
    clipped = a_scores + rho * (
        a_gdot * t_b + a_perp * jnp.sqrt(jnp.maximum(1.0 - t_b * t_b, 0.0))
    )
    return jnp.where(t_star <= t_b, unclipped, clipped)


def dome_scores(scores_c, gdot, col_norms, c, rho, ghat, b):
    """max(sup ±x_iᵀθ) over the dome, from precomputed matvecs — shared by
    dome_mask and the engine (which streams the two matvecs through the
    fused kernel with cached column norms)."""
    sup_pos = _sup_over_dome(scores_c, gdot, col_norms, c, rho, ghat, b)
    sup_neg = _sup_over_dome(-scores_c, -gdot, col_norms, c, rho, ghat, b)
    return jnp.maximum(sup_pos, sup_neg)


def _cap_sup(g, t_b, a_norms):
    """h(g, t_b) = sup_{t ≤ t_b, within the ball} of the unit-ρ cap term of
    :func:`_sup_over_dome`, as a function of ONE dot g = aᵀĝ:

        h = ‖a‖                                    if g/‖a‖ ≤ t_b (unclipped)
            g·t_b + √(‖a‖²−g²)₊·√(1−t_b²)₊          otherwise   (clipped)

    Used by the interval bounds below; the exact combines keep using
    :func:`_sup_over_dome` itself.
    """
    perp = jnp.sqrt(jnp.maximum(jnp.square(a_norms) - jnp.square(g), 0.0))
    clipped = g * t_b + perp * jnp.sqrt(jnp.maximum(1.0 - t_b * t_b, 0.0))
    return jnp.where(g <= t_b * (a_norms + 1e-30), a_norms, clipped)


def dome_sup_bounds(s_lo, s_hi, g_lo, g_hi, a_norms, rho_lo, rho_hi,
                    tb_lo, tb_hi):
    """Interval bound on the dome sup s + ρ·h(g, t_b) given per-piece
    intervals on its inputs: s ∈ [s_lo, s_hi], g ∈ [g_lo, g_hi],
    ρ ∈ [rho_lo, rho_hi] (ρ ≥ 0), t_b ∈ [tb_lo, tb_hi]. Returns (lo, hi)
    with the exact sup guaranteed inside.

    h is piecewise in g — constant ‖a‖ on the unclipped regime, concave
    decreasing on the cap regime up to g = ‖a‖, then linear g·t_b beyond —
    so its max over [g_lo, g_hi] is attained at an endpoint, while its min
    needs the regime breakpoint g = ‖a‖ as a third candidate (for t_b > 0
    the clipped branch turns back upward there). h is non-decreasing in
    t_b (the cap only grows), so hi evaluates at tb_hi and lo at tb_lo.
    """
    if jnp.ndim(s_lo) == 2:
        rho_lo, rho_hi = _col(rho_lo), _col(rho_hi)
        tb_lo, tb_hi = _col(tb_lo), _col(tb_hi)
    g_brk = jnp.clip(a_norms, g_lo, g_hi)
    h_hi = jnp.maximum(_cap_sup(g_lo, tb_hi, a_norms),
                       _cap_sup(g_hi, tb_hi, a_norms))
    h_lo = jnp.minimum(
        jnp.minimum(_cap_sup(g_lo, tb_lo, a_norms),
                    _cap_sup(g_hi, tb_lo, a_norms)),
        _cap_sup(g_brk, tb_lo, a_norms))
    # ρ ≥ 0 but h may be negative: take both corners of ρ·h
    hi = s_hi + jnp.maximum(rho_lo * h_hi, rho_hi * h_hi)
    lo = s_lo + jnp.minimum(rho_lo * h_lo, rho_hi * h_lo)
    return lo, hi


def dome_score_bounds(s_lo, s_hi, g_lo, g_hi, a_norms, rho_lo, rho_hi,
                      tb_lo, tb_hi):
    """Interval bound on :func:`dome_scores` = max(sup over ±x_j): the +
    branch takes (s, g) straight, the − branch takes (−s, −g) with the
    interval endpoints swapped and negated. Exact max lies in [lo, hi]."""
    lo_p, hi_p = dome_sup_bounds(s_lo, s_hi, g_lo, g_hi, a_norms,
                                 rho_lo, rho_hi, tb_lo, tb_hi)
    lo_n, hi_n = dome_sup_bounds(-s_hi, -s_lo, -g_hi, -g_lo, a_norms,
                                 rho_lo, rho_hi, tb_lo, tb_hi)
    return jnp.maximum(lo_p, lo_n), jnp.maximum(hi_p, hi_n)


def dome_t_b(c, rho, ghat, b):
    """The clipped cap threshold t_b = clip((b − ĝᵀc)/ρ, −1, 1) of
    :func:`_sup_over_dome`, exposed for the mixed-precision interval
    screens (which need it as an explicit input interval)."""
    if _is_batched(c):
        return jnp.clip(
            (b - jnp.sum(ghat * c, axis=-1)) / (rho + 1e-30), -1.0, 1.0)
    return jnp.clip((b - jnp.dot(ghat, c)) / (rho + 1e-30), -1.0, 1.0)


def dome_mask(X, y, lam_next, lam_max_val, eps: float = EPS_DEFAULT):
    """DOME test (Xiang et al. [36, 35]) — basic rule only (no sequential
    version exists; paper §4.1).

    Safe region: B(y/λ, ‖y‖(1/λ − 1/λ_max)) ∩ {θ : ĝᵀθ ≤ 1/‖x*‖·(1/1)}
    where g = sign(x*ᵀy)x* and x* attains λ_max. Both constraints provably
    contain θ*(λ): the ball because y/λ_max ∈ F is no closer to y/λ than the
    projection θ*(λ); the halfspace because gᵀθ ≤ 1 on all of F. We evaluate
    the *exact* sup of ±x_iᵀθ over the dome (tighter than the sphere test).

    The paper notes DOME assumes unit-norm features and y; this closed form
    does not need that, but benchmarks normalise for parity (Fig. 2).
    Batched: y (B, n), lam_next/lam_max_val (B,) → (B, p) mask.
    """
    if _is_batched(y):
        corr = y @ X                                   # (B, p)
        istar = jnp.argmax(jnp.abs(corr), axis=-1)
        g = _col(jnp.sign(jnp.take_along_axis(
            corr, istar[:, None], axis=-1)[:, 0])) * X[:, istar].T
        gnorm = jnp.linalg.norm(g, axis=-1) + 1e-30
        ghat = g / _col(gnorm)
        b = 1.0 / gnorm
        c = y / _col(jnp.asarray(lam_next))
        rho = jnp.linalg.norm(y, axis=-1) * (
            1.0 / jnp.asarray(lam_next) - 1.0 / jnp.asarray(lam_max_val))
        scores_c = c @ X
        gdot = ghat @ X
        col_norms = jnp.linalg.norm(X, axis=0)
        dec = dome_scores(scores_c, gdot, col_norms, c, rho, ghat, b) \
            < 1.0 - eps
        # The sup at istar itself is identically 1: θ = y/λ_max attains both
        # the sphere boundary (‖y/λ − y/λ_max‖ = ρ) and the half-space
        # boundary (ĝᵀθ = b) with x_*ᵀθ = 1 — the test sits exactly ON the
        # discard threshold, so any negative f32 rounding would evict the
        # λ_max-attaining feature. Pin it kept (exact, not a tolerance).
        return dec & (jnp.arange(X.shape[1])[None, :] != istar[:, None])
    corr = X.T @ y
    istar = jnp.argmax(jnp.abs(corr))
    g = jnp.sign(corr[istar]) * X[:, istar]
    gnorm = jnp.linalg.norm(g) + 1e-30
    ghat = g / gnorm
    b = 1.0 / gnorm                                   # ĝᵀθ ≤ 1/‖g‖
    c = y / lam_next
    rho = jnp.linalg.norm(y) * (1.0 / lam_next - 1.0 / lam_max_val)

    scores_c = X.T @ c
    gdot = X.T @ ghat
    col_norms = jnp.linalg.norm(X, axis=0)
    dec = dome_scores(scores_c, gdot, col_norms, c, rho, ghat, b) < 1.0 - eps
    # sup at istar is identically 1 (see batched branch) — pin it kept.
    return dec.at[istar].set(False)


# ---------------------------------------------------------------------------
# Composable half-space cuts: sphere ∩ {θ : ĝᵀθ ≤ b}   (Tran et al. 2022)
# ---------------------------------------------------------------------------

class HalfSpaceCut(NamedTuple):
    """A dual cutting half-space {θ : ĝᵀθ ≤ b}, composable with any
    :class:`SphereTest`: the sup of ±x_jᵀθ over ball ∩ half-space has the
    same closed form as the DOME region (:func:`_sup_over_dome`), and its
    evaluation needs ONE extra dot per column (Xᵀĝ) — which the engine
    stacks into the same streaming pass as the sphere-centre dot.

    ghat: unit normal, (n,) or (B, n) for per-query cuts
    b:    offset, scalar or (B,)

    A cut that does not intersect the ball is harmless: ``t_b`` clips to 1
    and the sup reduces exactly to the plain sphere sup (never *larger*),
    so composing is always safe and never looser than the sphere alone.
    """

    ghat: jax.Array
    b: jax.Array


def cut_from_ray(v1) -> HalfSpaceCut:
    """The λ_max feasibility cut from the (cached) ray g = sign(x*ᵀy)·x*.

    Every θ ∈ F satisfies |x*ᵀθ| ≤ 1, so gᵀθ ≤ 1, i.e. ĝᵀθ ≤ 1/‖g‖ with
    ĝ = g/‖g‖ — a half-space containing θ*(λ) for EVERY λ, dual-feasibility
    made geometric. The engine has v₁ cached in its workspace, so this cut
    is free; the oracle recomputes it from Xᵀy (:func:`feasibility_cut`).
    Batched: v1 (B, n) → per-query cuts.
    """
    gnorm = jnp.linalg.norm(v1, axis=-1) + 1e-30
    if jnp.ndim(v1) == 2:
        return HalfSpaceCut(ghat=v1 / _col(gnorm), b=1.0 / gnorm)
    return HalfSpaceCut(ghat=v1 / gnorm, b=1.0 / gnorm)


def feasibility_cut(X, y) -> HalfSpaceCut:
    """The λ_max feasibility cut computed from scratch (pure-jnp oracle
    path): g = sign(x*ᵀy)·x* with x* the λ_max feature — the same
    construction :func:`dome_mask` uses for its half-space."""
    if _is_batched(y):
        corr = y @ X                                   # (B, p)
        istar = jnp.argmax(jnp.abs(corr), axis=-1)
        g = _col(jnp.sign(jnp.take_along_axis(
            corr, istar[:, None], axis=-1)[:, 0])) * X[:, istar].T
        return cut_from_ray(g)
    corr = X.T @ y
    istar = jnp.argmax(jnp.abs(corr))
    return cut_from_ray(jnp.sign(corr[istar]) * X[:, istar])


def halfspace_sup(scores_c, gdot, col_norms, test: SphereTest,
                  cut: HalfSpaceCut):
    """sup |x_jᵀθ| over B(centre, ρ) ∩ {ĝᵀθ ≤ b}, from precomputed dots
    scores_c = Xᵀ·centre and gdot = Xᵀĝ — exact closed form (the DOME sup
    with an arbitrary cut). Degenerate cuts (half-space contains the whole
    ball) reduce bit-exactly to the sphere sup |scores_c| + ρ‖x_j‖."""
    return dome_scores(scores_c, gdot, col_norms, test.centre, test.rho,
                       cut.ghat, cut.b)


def cut_mask(X, test: SphereTest, cut: HalfSpaceCut,
             eps: float = EPS_DEFAULT):
    """Pure-jnp oracle for sphere ∩ half-space: discard j iff the exact sup
    of |x_jᵀθ| over the intersection is < 1 − eps. Because the region is a
    subset of the sphere, the discard set is always a superset of
    ``sphere_mask(X, test, eps)``'s."""
    col_norms = jnp.linalg.norm(X, axis=0)
    if _is_batched(test.centre):
        scores_c = test.centre @ X
        gdot = cut.ghat @ X
    else:
        scores_c = X.T @ test.centre
        gdot = X.T @ cut.ghat
    return halfspace_sup(scores_c, gdot, col_norms, test, cut) < 1.0 - eps


def _make_cut_rule(base: str):
    """Discard-mask oracle for ``<base>_cut``: the base rule's safe sphere
    intersected with the λ_max feasibility cut. Signature matches RULES."""
    def mask(X, y, lam_next, state: DualState, eps: float = EPS_DEFAULT):
        cut = feasibility_cut(X, y)
        col_norms = jnp.linalg.norm(X, axis=0)
        if base == "gap":
            # mirror gap_mask: one dot serves the feasibility rescale AND
            # the centre scores (centre = θ₀/max(1, ‖Xᵀθ₀‖∞))
            if _is_batched(y):
                dot = state.theta @ X
                sup_corr = jnp.max(jnp.abs(dot), axis=-1)
                test = gap_sphere(y, lam_next, state, sup_corr=sup_corr)
                scores_c = dot / _col(jnp.maximum(1.0, sup_corr))
                gdot = cut.ghat @ X
            else:
                dot = X.T @ state.theta
                sup_corr = jnp.max(jnp.abs(dot))
                test = gap_sphere(y, lam_next, state, sup_corr=sup_corr)
                scores_c = dot / jnp.maximum(1.0, sup_corr)
                gdot = X.T @ cut.ghat
        else:
            test = SPHERE_RULES[base](y, lam_next, state)
            if _is_batched(y):
                scores_c = test.centre @ X
                gdot = cut.ghat @ X
            else:
                scores_c = X.T @ test.centre
                gdot = X.T @ cut.ghat
        return halfspace_sup(scores_c, gdot, col_norms, test, cut) \
            < 1.0 - eps

    mask.__name__ = f"{base}_cut_mask"
    mask.__doc__ = (
        f"{base.upper()}-sphere ∩ λ_max feasibility cut: the {base!r} safe "
        f"ball intersected with {{θ : ĝᵀθ ≤ 1/‖g‖}} (g = sign(x*ᵀy)·x*). "
        f"Safe (both regions contain θ*(λ)); discards ⊇ the plain "
        f"{base!r} rule's.")
    return mask


#: ``<base>_cut`` for every sequential sphere rule: the base safe ball
#: intersected with the λ_max feasibility cut — evaluated by the engine in
#: the SAME single fused pass (the cut dot rides the stacked matvec).
CUT_RULES = {f"{base}_cut": _make_cut_rule(base) for base in SPHERE_RULES}

gap_cut_mask = CUT_RULES["gap_cut"]
edpp_cut_mask = CUT_RULES["edpp_cut"]


# ---------------------------------------------------------------------------
# KKT post-check (needed by the strong rule; free safety telemetry otherwise)
# ---------------------------------------------------------------------------

def kkt_violations(X, y, beta, lam, discarded, tol: float = 1e-4,
                   fitted=None):
    """Features whose KKT condition |x_iᵀr| ≤ λ is violated among the
    discarded set — the strong rule's correctness loop (paper §1).
    Batched: y/beta (B, ·), lam (B,) → (B, p) violation flags.

    ``fitted`` (the values Xβ, same shape as y) skips the full X·β pass:
    the path driver supplies them from the reduced bucket, which also keeps
    the residual arithmetic identical between sharded and unsharded runs
    (a column-sharded X·β would psum in shard-count-dependent order)."""
    if _is_batched(y):
        r = y - (beta @ X.T if fitted is None else fitted)
        viol = jnp.abs(r @ X) > _col(lam) * (1.0 + tol)
        return jnp.logical_and(viol, discarded)
    r = y - (X @ beta if fitted is None else fitted)
    viol = jnp.abs(X.T @ r) > lam * (1.0 + tol)
    return jnp.logical_and(viol, discarded)


RULES = {
    "dpp": dpp_mask,
    "imp1": imp1_mask,
    "imp2": imp2_mask,
    "edpp": edpp_mask,
    "seq_safe": seq_safe_mask,
    "gap": gap_mask,
    "strong": strong_mask,
    **CUT_RULES,
}

SAFE_RULES = ("dpp", "imp1", "imp2", "edpp", "seq_safe", "gap", "safe",
              "dome", "none", *CUT_RULES)
HEURISTIC_RULES = ("strong",)


@functools.partial(jax.jit, static_argnames=("rule",))
def screen(X, y, lam_next, state: DualState, rule: str = "edpp",
           eps: float = EPS_DEFAULT):
    """Jitted dispatch over the sequential rules."""
    return RULES[rule](X, y, lam_next, state, eps)
