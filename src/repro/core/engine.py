"""ScreeningEngine: every ball-test rule through one fused kernel pass.

The λ-path hot loop used to hand-roll each rule in plain jnp — recomputing
``|Xᵀc|`` AND ``‖x_j‖`` from HBM at every grid step (2 full passes over X
per screen, 4 for DOME). But X is *fixed* along the path: the column norms,
``|Xᵀy|``, λ_max and the λ_max ray v₁ are all λ-independent. This module
caches them in a :class:`PathWorkspace` (computed by ONE fused
``edpp_screen_scores`` pass at path start) and then serves every per-step
screen — DPP, Imp1/Imp2, EDPP, sequential SAFE, GAP-sphere, basic SAFE,
strong, DOME — through the ``kernels.screen_matvec`` streaming kernel with
the cached norms: **one HBM pass over X per screen** (two for DOME's extra
direction).

Dictionary vs query
-------------------
The cache splits along the paper's own geometry: the dual polytope F, the
column norms ‖x_j‖ and the Gram/Lipschitz machinery depend on **X only**
(:class:`DictionaryGeometry` — immutable, computed once, shared across
every query against this dictionary), while |Xᵀy|, λ_max, the λ_max ray v₁
and the dual state θ are cheap **per-query** state (:class:`PathWorkspace`
= geometry + one query batch). A workspace built over a (B, n) batch of
response vectors screens all B queries per single fused pass over X:
``screen`` takes per-query λ (B,) and a batched
:class:`~repro.core.screening.DualState` and returns a (B, p) mask — HBM
traffic over X is amortised 1/B per query (the serving regime: one fitted
dictionary, millions of y's).

Backend registry
----------------
The kernels are dispatched through ``kernels.ops.BACKENDS``:

    pallas     compiled Mosaic kernels (TPU)
    interpret  same kernel bodies on the Pallas interpreter (CI / CPU)
    jnp        pure-jnp oracles from kernels/ref.py (CPU default, GSPMD)

Selection order: explicit ``backend=`` argument → ``REPRO_SCREEN_BACKEND``
env var → ``INTERPRET=1`` env var (CI) → ``pallas`` on TPU → ``jnp``.
Register additional implementations with :func:`register_backend`.

The pure-jnp mask functions in :mod:`repro.core.screening` remain the
oracles; tests/test_engine.py checks the engine against them bit-for-bit
on every rule and backend.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from . import group_screening as gscr
from . import screening as scr
from . import tracing

# Full HBM passes over X that one screen costs, per rule: through the engine
# (norms/argmax geometry cached in the workspace) vs the hand-rolled jnp
# oracle masks (dot + column norms each time; DOME also redoes Xᵀy). The
# ``<base>_cut`` rules stay ONE engine pass — the cut dot rides the same
# stacked matvec — while their oracles pay four (Xᵀcentre, column norms,
# Xᵀy for the cut construction, Xᵀĝ).
ENGINE_X_PASSES = {"strong": 1, "dome": 2, "none": 0, "safe": 1,
                   **{f"{b}_cut": 1 for b in scr.SPHERE_RULES}}
ORACLE_X_PASSES = {"strong": 1, "dome": 4, "none": 0, "safe": 2,
                   **{f"{b}_cut": 4 for b in scr.SPHERE_RULES}}


def engine_x_passes(rule: str) -> int:
    """HBM passes over X per screen through the engine (1 for ball rules)."""
    return ENGINE_X_PASSES.get(rule, 1)


def oracle_x_passes(rule: str) -> int:
    """HBM passes over X per screen for the pure-jnp oracle mask."""
    return ORACLE_X_PASSES.get(rule, 2)


def _next_pow2(k: int) -> int:
    """Smallest power of two ≥ k (bucket size for the narrow re-test)."""
    return 1 << max(0, (k - 1).bit_length())


def _narrow_bucket(k: int, p: int) -> int:
    """Bucket size for the narrow f32 gathers: the smallest of
    {8, 16, 24, 32, 48, 64, 96, ...} — powers of two plus their 3/4
    midpoints, all multiples of 8 so the gathered width stays divisible
    by the feature-mesh sizes the sharded backend supports — that holds
    k columns, capped at p. The midpoints halve the worst-case rounding
    overhead (1.5× instead of 2×) for ~2× the compiled gather variants,
    still O(log p)."""
    b = _next_pow2(max(k, 8))
    if b >= 32 and 3 * b // 4 >= k:
        b = 3 * b // 4
    return min(b, p)


# Rules that have requested screen_dtype="bfloat16" but had to run f32
# because no certified margin covers them — warn once per rule per process
# so a silent fallback can't mislabel a bench row (the effective dtype is
# also recorded in PathStepStats.screen_dtype_effective).
_BF16_FALLBACK_WARNED: set[str] = set()


def _note_f32_fallback(rule: str) -> None:
    if rule in _BF16_FALLBACK_WARNED:
        return
    _BF16_FALLBACK_WARNED.add(rule)
    warnings.warn(
        f"screen_dtype='bfloat16' has no certified margin for rule "
        f"{rule!r}; screening it in float32 instead (masks unchanged, no "
        f"byte saving — see docs/kernels.md#mixed-precision-screening)",
        RuntimeWarning, stacklevel=4)


# ---------------------------------------------------------------------------
# Backend registry (thin policy layer over kernels.ops.BACKENDS)
# ---------------------------------------------------------------------------

def available_backends() -> tuple[str, ...]:
    return tuple(ops.BACKENDS)


def register_backend(name: str, backend: ops.ScreenBackend) -> None:
    """Add a ScreenBackend implementation (see kernels/ops.py contract)."""
    ops.BACKENDS[name] = backend


def default_backend() -> str:
    return ops.default_backend_name("REPRO_SCREEN_BACKEND")


def resolve_backend(
        name: str | ops.ScreenBackend | None = None) -> ops.ScreenBackend:
    if isinstance(name, ops.ScreenBackend):
        return name
    name = name or default_backend()
    try:
        return ops.BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown screening backend {name!r}; "
            f"available: {available_backends()}") from None


def block_scores(Xb, centre, rho, col_norms=None):
    """Sphere scores for one feature block — pure jnp, shard_map-safe.

    The distributed layer's per-shard entry point: identical arithmetic to
    ref.edpp_screen_ref / the fused kernel's finish step, so sharded and
    single-chip screens agree bitwise on the same block.
    """
    dot = jnp.matmul(Xb.T, centre, precision=jax.lax.Precision.HIGHEST)
    if col_norms is None:
        col_norms = jnp.sqrt(jnp.sum(jnp.square(Xb), axis=0))
    return jnp.abs(dot) + rho * col_norms


# ---------------------------------------------------------------------------
# Jitted combine steps (O(p) or O(B·p), applied to the kernel's single-pass
# output). Each branches on a leading batch axis at trace time: batched
# inputs use the (B, ·) arithmetic of the screening module's batched oracles.
# ---------------------------------------------------------------------------

@jax.jit
@jax.named_scope("screen")
def _sphere_combine(dot, rho, col_norms, eps):
    if dot.ndim == 2:
        return jnp.abs(dot) + scr._col(rho) * col_norms \
            < 1.0 - scr._col(jnp.asarray(eps))
    return jnp.abs(dot) + rho * col_norms < 1.0 - eps


@jax.jit
@jax.named_scope("screen")
def _gap_combine_from(dot, sup_corr, y, lam_next, state, col_norms, eps):
    """The GAP combine with the feasibility rescale ``sup_corr = ‖Xᵀθ₀‖∞``
    supplied explicitly — shared by the one-pass f32 combine (sup_corr from
    the same dot) and the bf16 narrow fallback (sup_corr recovered exactly
    from the gathered f32 dots, see ``_gap_screen_margin`` notes)."""
    test = scr.gap_sphere(y, lam_next, state, sup_corr=sup_corr)
    s = jnp.maximum(1.0, sup_corr)
    if dot.ndim == 2:
        return jnp.abs(dot) / scr._col(s) \
            + scr._col(test.rho) * col_norms < 1.0 - eps
    return jnp.abs(dot) / s + test.rho * col_norms < 1.0 - eps


@jax.jit
@jax.named_scope("screen")
def _gap_combine(dot, y, lam_next, state, col_norms, eps):
    sup_corr = (jnp.max(jnp.abs(dot), axis=-1) if dot.ndim == 2
                else jnp.max(jnp.abs(dot)))
    return _gap_combine_from(dot, sup_corr, y, lam_next, state, col_norms,
                             eps)


@jax.jit
@jax.named_scope("screen")
def _strong_combine(dot, lam_next, lam_prev, eps):
    if dot.ndim == 2:
        return jnp.abs(dot) < scr._col(2.0 * lam_next - lam_prev - eps)
    return jnp.abs(dot) < 2.0 * lam_next - lam_prev - eps


# Margin-aware twins of the combines above, for the reduced-precision fast
# pass: alongside the discard mask they return the BAND of columns whose
# score lies within ``margin`` of the decision threshold — exactly the
# columns whose bf16 decision is not provably the f32 decision
# (kernels/ops.bf16_score_margin) and must be re-tested in full precision.

@jax.jit
@jax.named_scope("screen")
def _sphere_combine_margin(dot, rho, col_norms, eps, margin):
    if dot.ndim == 2:
        scores = jnp.abs(dot) + scr._col(rho) * col_norms
        thresh = 1.0 - scr._col(jnp.asarray(eps))
    else:
        scores = jnp.abs(dot) + rho * col_norms
        thresh = 1.0 - eps
    return scores < thresh, jnp.abs(scores - thresh) <= margin


@jax.jit
@jax.named_scope("screen")
def _strong_combine_margin(dot, lam_next, lam_prev, eps, margin):
    if dot.ndim == 2:
        thresh = scr._col(2.0 * lam_next - lam_prev - eps)
    else:
        thresh = 2.0 * lam_next - lam_prev - eps
    a = jnp.abs(dot)
    return a < thresh, jnp.abs(a - thresh) <= margin


@jax.jit
@jax.named_scope("screen")
def _dome_combine(scores_c, gdot, col_norms, c, rho, ghat, b, eps):
    return scr.dome_scores(scores_c, gdot, col_norms, c, rho, ghat, b) \
        < 1.0 - eps


@jax.jit
@jax.named_scope("screen")
def _gap_cut_combine_from(dot, gdot, sup_corr, y, lam_next, state, col_norms,
                          ghat, b, eps):
    """The gap_cut combine with ``sup_corr`` supplied explicitly (see
    ``_gap_combine_from`` — same split, same fallback consumer)."""
    test = scr.gap_sphere(y, lam_next, state, sup_corr=sup_corr)
    if dot.ndim == 2:
        scores_c = dot / scr._col(jnp.maximum(1.0, sup_corr))
    else:
        scores_c = dot / jnp.maximum(1.0, sup_corr)
    return scr.dome_scores(scores_c, gdot, col_norms, test.centre, test.rho,
                           ghat, b) < 1.0 - eps


@jax.jit
@jax.named_scope("screen")
def _gap_cut_combine(dot, gdot, y, lam_next, state, col_norms, ghat, b, eps):
    """gap_cut: the GAP sphere's feasibility rescale (served by the dot the
    pass already produced, exactly like _gap_combine) composed with the
    half-space sup over ball ∩ cut."""
    sup_corr = (jnp.max(jnp.abs(dot), axis=-1) if dot.ndim == 2
                else jnp.max(jnp.abs(dot)))
    return _gap_cut_combine_from(dot, gdot, sup_corr, y, lam_next, state,
                                 col_norms, ghat, b, eps)


# --- per-piece margin combines for the bf16 fast pass -----------------------
# The dome sup and the HalfSpaceCut combine are only PIECEWISE-linear in the
# two dots (x_j·c, x_j·ĝ), so PR 8's single scalar band does not transfer.
# Instead each combine below propagates one interval per dot (centre dot
# ± e_c, cut dot ± e_g from ops.bf16_score_margin) through every linear
# regime of the closed form (scr.dome_score_bounds evaluates the cap term at
# both interval endpoints AND the regime breakpoint g = ‖x_j‖), yielding
# certified [lo, hi] bounds on the exact f32 score. Outside [lo, hi]'s
# straddle of the threshold the bf16 decision is provably the f32 decision;
# the returned band marks the columns that must be re-tested in f32.
#
# The GAP rules add a wrinkle: their feasibility rescale sup_corr = ‖Xᵀθ₀‖∞
# is a global max the bf16 pass can only bracket. Propagating that bracket
# through u = 1/max(1, sc) and the radius ρ(u) = √(2·gap(u))/λ is far too
# loose near convergence: gap(u*) ≈ 0, so a bracket of width 2m inflates ρ
# by ~√(λ|θᵀy|·m) and hundreds of columns straddle the threshold at small
# λ. The engine therefore recovers sup_corr EXACTLY first, with a separate
# tiny gather of the argmax CANDIDATES (|d̃_j|+m_j ≥ max_k(|d̃_k|−m_k)): the
# true f32 argmax column is provably a candidate, every gathered f32 dot is
# ≤ the true max, hence the max over the gathered exact dots IS the global
# f32 sup bit-for-bit (`_narrow_sup`). With u and ρ exact scalars the only
# residual uncertainty is the per-column dot margin, and the band collapses
# to the true threshold straddlers (tens of columns, not hundreds).

@jax.jit
@jax.named_scope("screen")
def _dome_combine_margin(scores_c, gdot, e_c, e_g, col_norms, c, rho, ghat,
                         b, eps):
    t_b = scr.dome_t_b(c, rho, ghat, b)
    lo, hi = scr.dome_score_bounds(scores_c - e_c, scores_c + e_c,
                                   gdot - e_g, gdot + e_g, col_norms,
                                   rho, rho, t_b, t_b)
    thresh = 1.0 - eps
    return hi < thresh, (hi >= thresh) & (lo < thresh)


@jax.jit
@jax.named_scope("screen")
def _gap_cand(dot, margin):
    """Argmax-candidate mask for the exact sup_corr recovery: every column
    whose bf16 upper bound |d̃_j| + m_j reaches the best lower bound
    max_k(|d̃_k| − m_k) could be the true f32 argmax. The threshold is
    additionally floored at 1 because every consumer reads sup_corr
    through max(1, ·) (gap_sphere's u = 1/max(1, sup) and the combine's
    rescale): a column with |d̃_j| + m_j < 1 has exact |d_j| < 1 and so
    can never move that max — if the true sup exceeds 1 its argmax column
    clears the floor by itself, and if it doesn't the gathered max is ≤ 1
    and the consumer's floor takes over either way. The set CAN be empty
    (all upper bounds < 1); the zero-padded gather then returns some
    exact |d_0| ≤ sup < 1, which the floor also absorbs."""
    a = jnp.abs(dot)
    abs_hi = a + margin
    abs_lo = jnp.maximum(a - margin, 0.0)
    if dot.ndim == 2:
        t = jnp.maximum(jnp.max(abs_lo, axis=-1), 1.0)
        return abs_hi >= scr._col(t)
    return abs_hi >= jnp.maximum(jnp.max(abs_lo), 1.0)


@jax.jit
@jax.named_scope("screen")
def _gap_combine_margin(dot, margin, sup_corr, y, lam_next, state,
                        col_norms, eps):
    """GAP margin combine with the EXACT f32 rescale in hand (see the
    block comment above): u = 1/max(1, sup_corr) and ρ are exact scalars,
    so the certified bounds differ from the exact score only by the dot
    margin and the band is the true threshold straddlers."""
    test = scr.gap_sphere(y, lam_next, state, sup_corr=sup_corr)
    s = jnp.maximum(1.0, sup_corr)
    a = jnp.abs(dot)
    if dot.ndim == 2:
        sc, rc = scr._col(s), scr._col(test.rho)
        hi = (a + margin) / sc + rc * col_norms
        lo = jnp.maximum(a - margin, 0.0) / sc + rc * col_norms
    else:
        hi = (a + margin) / s + test.rho * col_norms
        lo = jnp.maximum(a - margin, 0.0) / s + test.rho * col_norms
    thresh = 1.0 - eps
    return hi < thresh, (hi >= thresh) & (lo < thresh)


@jax.jit
@jax.named_scope("screen")
def _gap_cut_combine_margin(dot, gdot, e_c, e_g, sup_corr, y, lam_next,
                            state, col_norms, ghat, b, eps):
    """gap_cut margin combine with the exact rescale: the sphere geometry
    (centre θ₀/s, ρ, and the clip breakpoint t_b) is exact, so only the
    two dot intervals flow through the piecewise closed form — the same
    `dome_score_bounds` call the dome margin combine makes."""
    test = scr.gap_sphere(y, lam_next, state, sup_corr=sup_corr)
    t_b = scr.dome_t_b(test.centre, test.rho, ghat, b)
    s = scr._col(jnp.maximum(1.0, sup_corr)) if dot.ndim == 2 \
        else jnp.maximum(1.0, sup_corr)
    lo, hi = scr.dome_score_bounds((dot - e_c) / s, (dot + e_c) / s,
                                   gdot - e_g, gdot + e_g, col_norms,
                                   test.rho, test.rho, t_b, t_b)
    thresh = 1.0 - eps
    return hi < thresh, (hi >= thresh) & (lo < thresh)


@jax.jit
@jax.named_scope("state")
def _make_state(X, y, beta, lam, lmax, v1max):
    """Sequential DualState with the λ_max branch served from cache — no
    per-step Xᵀy pass (make_dual_state recomputes it every call)."""
    theta_seq = (y - X @ beta) / lam
    at_max = lam >= lmax * (1.0 - 1e-12)
    theta = jnp.where(at_max, y / lmax, theta_seq)
    v1 = jnp.where(at_max, v1max, y / lam - theta_seq)
    return scr.DualState(
        theta=theta,
        lam=jnp.where(at_max, lmax, jnp.asarray(lam, X.dtype)),
        v1=v1,
        at_lmax=jnp.asarray(at_max),
        beta_l1=jnp.where(at_max, 0.0, jnp.sum(jnp.abs(beta))),
    )


@jax.jit
@jax.named_scope("state")
def _make_state_batched(X, y, beta, lam, lmax, v1max):
    """Batched `_make_state`: y/beta (B, ·), lam/lmax (B,), v1max (B, n).
    Each query selects its own eq. (17) branch."""
    theta_seq = (y - beta @ X.T) / scr._col(lam)
    at_max = lam >= lmax * (1.0 - 1e-12)                 # (B,)
    at_col = scr._col(at_max)
    theta = jnp.where(at_col, y / scr._col(lmax), theta_seq)
    v1 = jnp.where(at_col, v1max, y / scr._col(lam) - theta_seq)
    return scr.DualState(
        theta=theta,
        lam=jnp.where(at_max, lmax, lam).astype(X.dtype),
        v1=v1,
        at_lmax=at_max,
        beta_l1=jnp.where(at_max, 0.0, jnp.sum(jnp.abs(beta), axis=-1)),
    )


@jax.jit
@jax.named_scope("state")
def _make_state_fit(y, fitted, beta, lam, lmax, v1max):
    """`_make_state` with the fitted values Xβ supplied by the caller.

    The path driver computes them from the *reduced bucket* (Xr·β_r — the
    bucket is gathered replicated), so the dual point costs no full-X pass
    AND its float arithmetic is identical between sharded and unsharded
    runs: a column-sharded X·β would psum partial fits in a shard-count-
    dependent order, flipping last-bit mask decisions (docs/distributed.md
    exactness contract)."""
    theta_seq = (y - fitted) / lam
    at_max = lam >= lmax * (1.0 - 1e-12)
    theta = jnp.where(at_max, y / lmax, theta_seq)
    v1 = jnp.where(at_max, v1max, y / lam - theta_seq)
    return scr.DualState(
        theta=theta,
        lam=jnp.where(at_max, lmax, jnp.asarray(lam, y.dtype)),
        v1=v1,
        at_lmax=jnp.asarray(at_max),
        beta_l1=jnp.where(at_max, 0.0, jnp.sum(jnp.abs(beta))),
    )


@jax.jit
@jax.named_scope("state")
def _make_state_batched_fit(y, fitted, beta, lam, lmax, v1max):
    """Batched `_make_state_fit`: y/fitted (B, n), beta (B, p), lam (B,)."""
    theta_seq = (y - fitted) / scr._col(lam)
    at_max = lam >= lmax * (1.0 - 1e-12)                 # (B,)
    at_col = scr._col(at_max)
    theta = jnp.where(at_col, y / scr._col(lmax), theta_seq)
    v1 = jnp.where(at_col, v1max, y / scr._col(lam) - theta_seq)
    return scr.DualState(
        theta=theta,
        lam=jnp.where(at_max, lmax, lam).astype(y.dtype),
        v1=v1,
        at_lmax=at_max,
        beta_l1=jnp.where(at_max, 0.0, jnp.sum(jnp.abs(beta), axis=-1)),
    )


@jax.jit
@jax.named_scope("state")
def _make_group_state(X, y, beta, lam, lmax, theta_max, v1max):
    theta_seq = (y - X @ beta) / lam
    at_max = lam >= lmax * (1.0 - 1e-12)
    return gscr.GroupDualState(
        theta=jnp.where(at_max, theta_max, theta_seq),
        lam=jnp.where(at_max, lmax, jnp.asarray(lam, X.dtype)),
        v1=jnp.where(at_max, v1max, y / lam - theta_seq),
    )


@jax.jit
@jax.named_scope("state")
def _make_group_state_fit(y, fitted, beta, lam, lmax, theta_max, v1max):
    """`_make_group_state` from caller-supplied fitted values Xβ."""
    theta_seq = (y - fitted) / lam
    at_max = lam >= lmax * (1.0 - 1e-12)
    return gscr.GroupDualState(
        theta=jnp.where(at_max, theta_max, theta_seq),
        lam=jnp.where(at_max, lmax, jnp.asarray(lam, y.dtype)),
        v1=jnp.where(at_max, v1max, y / lam - theta_seq),
    )


@jax.jit
@jax.named_scope("state")
def _make_group_state_batched_fit(y, fitted, lam, lmax, theta_max, v1max):
    """Batched `_make_group_state_fit`: y/fitted (B, n), lam/lmax (B,).
    Each query selects its own λ̄_max branch."""
    theta_seq = (y - fitted) / lam[:, None]
    at_max = lam >= lmax * (1.0 - 1e-12)                 # (B,)
    at_col = at_max[:, None]
    return gscr.GroupDualState(
        theta=jnp.where(at_col, theta_max, theta_seq),
        lam=jnp.where(at_max, lmax, lam).astype(y.dtype),
        v1=jnp.where(at_col, v1max, y / lam[:, None] - theta_seq),
    )


@jax.jit
@jax.named_scope("screen")
def _group_edpp_geometry(y, lam_next, state):
    vp = gscr.group_v2_perp(y, lam_next, state)
    return state.theta + 0.5 * vp, 0.5 * jnp.linalg.norm(vp, axis=-1)


@jax.jit
@jax.named_scope("screen")
def _group_edpp_combine(gscores, rho, spec_norms, sqm, eps):
    """Corollary 21's test on the kernel's scores, (G,) or (B, G)."""
    return gscores < sqm - scr._col(rho) * spec_norms - eps


@jax.jit
@jax.named_scope("screen")
def _group_strong_combine(gscores, lam_next, lam_prev, sqm, eps):
    return gscores < scr._col(sqm * (2.0 * lam_next - lam_prev) - eps)


@functools.partial(jax.jit, static_argnames="m")
@jax.named_scope("screen")
def _group_fit_batched(X, gscores, y, m: int):
    """The batch's λ̄_max (B,) and λ̄_max ray v̄₁ = X*X*ᵀy (B, n) (eq. 59)
    from the (B, G) scores ‖X_gᵀy‖: each query's argmax group stays on the
    device, and only λ̄_max is read to the host."""
    gnorms = gscores / jnp.sqrt(float(m))
    gstar = jnp.argmax(gnorms, axis=-1)                            # (B,)
    lmax = jnp.take_along_axis(gnorms, gstar[:, None], axis=-1)[:, 0]

    def ray(g, yq):
        xs = jax.lax.dynamic_slice_in_dim(X, g * m, m, axis=1)     # (n, m)
        return xs @ (xs.T @ yq)

    return lmax, jax.vmap(ray)(gstar, y)


_group_spec_norms = jax.jit(gscr.group_spectral_norms, static_argnames="m")


def _patch_slots_impl(X, vecs, slots, blk, vec_blocks, lo_dtypes):
    """Patch recycled slots — one fused dispatch for a geometry's whole
    per-column state. ``slots`` is sorted-unique by construction (a prefix
    of the sorted drop set), which lets XLA lower the column scatter ~4x
    faster than the generic path. The reduced-precision screen copies are
    re-cast whole from the patched X instead of scattered: XLA's bf16
    scatter is scalar-looped (~3x the f32 scatter despite half the
    bytes), while the elementwise cast pass both vectorises and is
    bitwise-identical to the cold ``astype`` by construction — fusion
    cannot reorder an elementwise op."""
    Xn = X.at[:, slots].set(blk, unique_indices=True,
                            indices_are_sorted=True)
    los = [Xn.astype(jnp.dtype(dt)) for dt in lo_dtypes]
    vecs = [v.at[slots].set(b, unique_indices=True,
                            indices_are_sorted=True)
            for v, b in zip(vecs, vec_blocks)]
    return Xn, los, vecs


@jax.jit
@jax.named_scope("screen")
def _stream_fit_single(X, istar, y):
    """λ_max ray v₁ = sign(x*ᵀy)·x* and the DOME halfspace direction for a
    single query — the ONE jitted helper both the cold PathWorkspace fit
    and update_workspace (core/update.py) go through, so a carried stream
    is bitwise-identical to a cold one by construction."""
    acc = jnp.promote_types(X.dtype, jnp.float32)
    xstar = X[:, istar]
    sgn = jnp.sign(jnp.vdot(xstar.astype(acc), y.astype(acc)))
    v1 = sgn * xstar
    ghat = v1 / (jnp.linalg.norm(v1, axis=-1, keepdims=True) + 1e-30)
    return v1, ghat


@jax.jit
@jax.named_scope("screen")
def _stream_fit_batched(X, istar, y):
    """Batched twin of :func:`_stream_fit_single` — (B,) argmaxes."""
    acc = jnp.promote_types(X.dtype, jnp.float32)
    xstar = X[:, istar].T
    sgn = jnp.sign(jnp.sum(xstar.astype(acc) * y.astype(acc), axis=-1))
    v1 = scr._col(sgn) * xstar
    ghat = v1 / (jnp.linalg.norm(v1, axis=-1, keepdims=True) + 1e-30)
    return v1, ghat


# apply_update's block-vs-full probe results (core/update.py carry):
# (backend id, X shape, churn size, err dtypes) → did the (n, c) block
# reduction reproduce the (n, p) full-shape reduction bit-for-bit? XLA's
# accumulation order is fixed per compiled executable and independent of
# the data, so ONE probe decides a shape for the process lifetime.
_BLOCK_CARRY_OK: dict = {}

_ADD_BLOCK_STATS = {}


def _add_block_stats(backend, err_dtypes):
    """Jitted fresh-column products for an added block — the cold fit's
    fused sumsq pass, its column norms, and one quantisation-error bound
    per cached screen dtype, in ONE dispatch. Fusion only inlines each
    reduction's elementwise producers/consumers (the cast feeding the
    error bound, the sqrt reading sumsq); the per-column reductions
    themselves are the exact ones a cold fit runs standalone, so the
    outputs stay bit-identical to refitting the edited X (asserted by the
    oracle contract, tests/test_update.py)."""
    key = (id(backend), err_dtypes)
    fn = _ADD_BLOCK_STATS.get(key)
    if fn is None:
        fused = backend.fused_scores

        @jax.jit
        def fn(add):
            _, sumsq = fused(add, jnp.zeros((add.shape[0],), add.dtype),
                             0.0)
            errs = tuple(
                ops.bf16_column_err(add, add.astype(jnp.dtype(dt)))
                for dt in err_dtypes)
            return sumsq, jnp.sqrt(sumsq), errs
        _ADD_BLOCK_STATS[key] = fn
    return fn


# Two-phase buffer ownership (apply_update): the FIRST update must copy —
# the fit-time X may alias a caller-held jax array (jnp.asarray is a no-op
# on device arrays), and multiple backend geometries can share one buffer.
# Its outputs are fresh buffers owned by this geometry alone, so every
# LATER update donates them and patches without the O(n·p) copy — that
# in-place reuse is what keeps a balanced churn edit at O(n·c).
_patch_slots_copy = jax.jit(_patch_slots_impl, static_argnums=5)
_patch_slots_donated = jax.jit(_patch_slots_impl, static_argnums=5,
                               donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# Dictionary geometry (query-independent, computed once) + per-query state
# ---------------------------------------------------------------------------

class DictionaryGeometry:
    """The immutable, query-independent geometry of a fitted dictionary X.

    Everything the screens and solvers reuse across *different response
    vectors y*: the device-resident X itself, ``‖x_j‖²`` and the column
    norms (one fused kernel pass with a zero centre — the scores vanish,
    the sum-of-squares accumulator is the payload). The serving loop
    (launch/serve.py) builds this ONCE and then attaches micro-batches of
    queries via :class:`PathWorkspace`, so per-query setup is a single
    batched ``|XᵀY|`` pass instead of a full re-fit.
    """

    def __init__(self, X, backend: str | None = None, *, _sumsq=None):
        self.backend = resolve_backend(backend)
        self.X = jnp.asarray(X)
        self.version = 0          # bumped by apply_update (core/update.py)
        self.fit_passes = 0       # fused workspace passes over X (fit-once)
        self.query_passes = 0     # per-query |XᵀY| attach passes
        self.update_passes = 0    # partial (touched-columns-only) passes
        self._owns_buffers = False  # True once apply_update replaced every
        #                             buffer — enables donated patching
        self._screen_copies: dict[str, jax.Array] = {}
        if _sumsq is None:
            _, _sumsq = self.backend.fused_scores(
                self.X, jnp.zeros((self.X.shape[0],), self.X.dtype), 0.0)
            self.fit_passes = 1
        self.sumsq = _sumsq                       # ‖x_j‖²
        self.col_norms = jnp.sqrt(_sumsq)

    def screen_copy(self, dtype) -> jax.Array:
        """A reduced-precision copy of X for screening passes, built lazily
        and cached for the dictionary's lifetime (fit-once, like everything
        else here). Only X is down-cast — sumsq/col_norms/|Xᵀy| always come
        from the full-precision fit pass, and the tile dots accumulate in
        f32 regardless of storage dtype (kernels contract). ``astype`` is
        elementwise, so a sharded X keeps its column placement."""
        dtype = jnp.dtype(dtype)
        if dtype == self.X.dtype:
            return self.X
        cached = self._screen_copies.get(dtype.name)
        if cached is None:
            cached = self.X.astype(dtype)
            self._screen_copies[dtype.name] = cached
        return cached

    def screen_err(self, dtype) -> jax.Array:
        """Per-column dot-error bound (p,) for screening through the
        ``screen_copy(dtype)`` — the measured quantisation residual of
        ops.bf16_column_err, cached like the copy itself. Zero when the
        copy IS X (no down-cast)."""
        dtype = jnp.dtype(dtype)
        if dtype == self.X.dtype:
            return jnp.zeros_like(self.col_norms)
        key = dtype.name + ":err"
        cached = self._screen_copies.get(key)
        if cached is None:
            cached = ops.bf16_column_err(self.X, self.screen_copy(dtype))
            self._screen_copies[key] = cached
        return cached

    def _full_column_state(self, X_new, copies, err_dtypes):
        """Per-column state at FULL shape via the exact eager calls a
        cold fit runs on the edited X — same function, same shapes, same
        content → the same compiled executable → identical bits (the
        fallback and probe reference of apply_update). Mutates ``copies``
        in place with the fresh ``:err`` columns; returns
        ``(sumsq, col_norms)``."""
        _, sumsq = self.backend.fused_scores(
            X_new, jnp.zeros((X_new.shape[0],), X_new.dtype), 0.0)
        for dt in err_dtypes:
            copies[dt + ":err"] = ops.bf16_column_err(X_new, copies[dt])
        return sumsq, jnp.sqrt(sumsq)

    def apply_update(self, plan, X_add=None, *,
                     place_x=None, place_col=None) -> int:
        """Apply a column edit IN PLACE, following the plan's layout rule
        (core/update.py): the first ``plan.n_recycle`` added columns are
        scattered into the dropped slots (ascending), leftover drops
        compact the survivors left, leftover adds append at the end.

        A *balanced* edit patches ONLY the edited columns — per-array
        ``.at[:, slots].set`` scatters, no full-dictionary gathers — which
        is what makes a churn update ≪ a refit
        (benchmarks/bench_update.py). Survivors carry every piece of
        cached per-column state — ``sumsq``/``col_norms``, each
        reduced-precision screen copy and its ``:err`` bound — untouched;
        only the ADDED block pays fresh per-column reductions.

        Exactness: those reductions are mathematically per-column, but
        XLA's *accumulation order* for an (n, c) block can differ from
        the (n, p) full-shape reduction a cold fit runs (the strategy is
        shape-dependent), so block results are not bitwise-trustworthy a
        priori. The FIRST update at a given (shape, churn size) therefore
        recomputes the per-column state at full shape with the cold
        path's own eager calls — bit-identical by construction — and
        *probes* the block reduction against it: if the block bits match
        (accumulation order is content-independent, so one probe decides
        the shape), later same-shaped updates take the O(n·c) incremental
        carry; if not, that shape permanently recomputes at full shape
        (still ≪ refit: no session rebuild, fused patches, warm eig
        cache). Shape-changing edits always recompute at the new full
        shape. Net: the oracle-refit contract (core/update.py) holds
        bit-for-bit at EVERY shape. ``place_x``/``place_col`` re-place
        (n, p) / (p,) results on a mesh (see LassoSession.update).

        Ownership: the first update patches COPIES (fit-time buffers may
        be aliased by the caller or sibling geometries); once every
        buffer is geometry-owned, later updates donate them to the patch
        — outside references captured between updates are invalidated
        (see the two-phase note at ``_patch_slots_copy``).

        Returns the new ``version``."""
        place_given = place_x is not None or place_col is not None
        place_x = place_x or (lambda a: a)
        place_col = place_col or (lambda a: a)
        add = None
        if X_add is not None:
            add = jnp.asarray(X_add, self.X.dtype)
            if add.ndim != 2 or add.shape[0] != self.X.shape[0]:
                raise ValueError(
                    f"X_add must be (n, p_add) with n={self.X.shape[0]}, "
                    f"got {add.shape}")
            if add.shape[1] == 0:
                add = None

        copies = dict(self._screen_copies)
        mat_keys = [key for key in copies if not key.endswith(":err")]
        err_dtypes = tuple(key for key in mat_keys
                           if key + ":err" in copies)

        k = int(getattr(plan, "n_recycle", 0))
        X_new, sumsq, col_norms = self.X, self.sumsq, self.col_norms
        if k:
            slots = jnp.asarray(plan.recycle_idx, jnp.int32)
            blk = add if k == add.shape[1] else add[:, :k]
            # donation needs sole ownership AND plain placement (device_put
            # on a mesh may alias, which would defeat the ownership proof)
            patch = (_patch_slots_donated
                     if self._owns_buffers and not place_given
                     else _patch_slots_copy)
            ck = (id(self.backend), self.X.shape, k, err_dtypes)
            carry = (_BLOCK_CARRY_OK.get(ck)
                     if plan.pure_recycle else False)
            if carry is not False:
                # fresh per-column products for the added block in one
                # jitted dispatch (only trusted where the probe below
                # validated the block reduction's bits for this shape)
                sumsq_b, norms_b, errs = _add_block_stats(
                    self.backend, err_dtypes)(blk)
                errs_b = dict(zip(err_dtypes, errs))
            self.update_passes += 1
            if carry:
                vecs = [sumsq, col_norms]
                vec_blocks = [sumsq_b, norms_b]
                err_keys = []
                for dt in err_dtypes:
                    err_keys.append(dt + ":err")
                    vecs.append(copies[dt + ":err"])
                    vec_blocks.append(errs_b[dt])
                X_new, los, vecs = patch(X_new, vecs, slots, blk,
                                         vec_blocks, tuple(mat_keys))
                sumsq, col_norms = vecs[0], vecs[1]
                copies.update(zip(mat_keys, los))
                copies.update(zip(err_keys, vecs[2:]))
            else:
                lo_dtypes = tuple(mat_keys) if plan.pure_recycle else ()
                X_new, los, _ = patch(X_new, [], slots, blk, [], lo_dtypes)
                copies.update(zip(lo_dtypes, los))
                if plan.pure_recycle:
                    sumsq, col_norms = self._full_column_state(
                        X_new, copies, err_dtypes)
                    if carry is None:
                        ok = np.array_equal(np.asarray(sumsq_b),
                                            np.asarray(sumsq)[
                                                plan.recycle_idx])
                        for dt in err_dtypes:
                            ok = ok and np.array_equal(
                                np.asarray(errs_b[dt]),
                                np.asarray(copies[dt + ":err"])[
                                    plan.recycle_idx])
                        _BLOCK_CARRY_OK[ck] = bool(ok)

        if not plan.pure_recycle:
            # residual drops compact the survivors; residual adds append;
            # the per-column state rebuilds at the NEW full shape (the
            # cold executable for p_new — see the docstring)
            keep_idx = jnp.asarray(plan.keep_idx, jnp.int32)
            X_new = jnp.take(X_new, keep_idx, axis=1)
            if add is not None and plan.n_append:
                X_new = jnp.concatenate([X_new, add[:, k:]], axis=1)
            for key in mat_keys:
                copies[key] = X_new.astype(jnp.dtype(key))
            sumsq, col_norms = self._full_column_state(X_new, copies,
                                                       err_dtypes)
            if add is not None and not k:
                self.update_passes += 1

        for key in list(copies):
            copies[key] = (place_col if key.endswith(":err")
                           else place_x)(copies[key])
        self.X = place_x(X_new)
        self.sumsq = place_col(sumsq)
        self.col_norms = place_col(col_norms)
        self._screen_copies = copies
        # from here on every buffer above was created by this update (or
        # re-placed), so the next update may donate it (see _patch_slots_*)
        self._owns_buffers = place_given is False
        self.version += 1
        return self.version

    @property
    def shape(self) -> tuple[int, int]:
        return self.X.shape


class GroupDictionaryGeometry:
    """Query-independent geometry of a fitted *group* dictionary.

    The group twin of :class:`DictionaryGeometry`: caches X and the per-group
    spectral norms ‖X_g‖₂ (Theorem 20 — an m×m eigh per group, the expensive
    y-independent piece of group screening). A :class:`LassoSession` fitted
    with ``groups=m`` builds this once; every query then only pays the cheap
    per-query ``‖X_gᵀy‖`` pass in :class:`GroupScreeningEngine`.
    """

    def __init__(self, X, m: int, backend: str | None = None):
        self.backend = resolve_backend(backend)
        self.X = jnp.asarray(X)
        self.m = m
        self.spec_norms = _group_spec_norms(self.X, m)
        self.version = 0    # group dictionaries have no incremental update
        self.fit_passes = 1
        self.query_passes = 0

    @property
    def shape(self) -> tuple[int, int]:
        return self.X.shape


class PathWorkspace:
    """Caches everything about (X, y) the screens reuse across the λ-grid:
    a :class:`DictionaryGeometry` plus the per-query fit.

    One fused ``edpp_screen_scores(X, y, rho=0)`` pass yields BOTH
    ``|Xᵀy|`` (→ λ_max, the argmax feature) and ``‖x_j‖²`` (→ the column
    norms every sphere test needs); the λ_max ray v₁ = sign(x*ᵀy)·x* and
    ‖y‖ follow in O(n). Nothing here is recomputed per grid step.

    ``y`` may be a (B, n) batch: the SAME single fused pass then fits all
    B queries (scores (B, p)), and the per-query fields grow a leading
    batch axis — ``lam_max``/``istar`` (B,), ``v1_at_lmax``/``ghat``
    (B, n). Pass ``geometry=`` to reuse a prefitted dictionary: setup then
    costs one batched matvec pass instead of the fused pass.
    """

    def __init__(self, X, y, backend: str | None = None, *,
                 geometry: DictionaryGeometry | None = None):
        if geometry is None:
            y_arr = jnp.asarray(y)
            backend_r = resolve_backend(backend)
            scores, sumsq = backend_r.fused_scores(jnp.asarray(X), y_arr, 0.0)
            geometry = DictionaryGeometry(X, backend_r, _sumsq=sumsq)
            geometry.fit_passes = 1   # the fused pass above fitted it
        else:
            y_arr = jnp.asarray(y)
            scores = jnp.abs(geometry.backend.matvec(geometry.X, y_arr))
        geometry.query_passes += 1
        self.geometry = geometry
        self.backend = geometry.backend
        self.y = y_arr
        self.batch = None if y_arr.ndim == 1 else y_arr.shape[0]
        self.abs_xty = scores                     # |Xᵀy|, (p,) or (B, p)
        if self.batch is None:
            self.istar = int(tracing.fetch(jnp.argmax(scores)))
            self.lam_max = float(tracing.fetch(scores[self.istar]))
            # eq. (17) at λ₀ = λ_max, + the DOME halfspace direction
            self.v1_at_lmax, self.ghat = _stream_fit_single(
                self.X, jnp.asarray(self.istar, jnp.int32), self.y)
        else:
            istar = jnp.argmax(scores, axis=-1)               # (B,)
            self.istar = tracing.fetch(istar)
            self.lam_max = tracing.fetch(
                jnp.take_along_axis(scores, istar[:, None], axis=-1)[:, 0],
                np.float64)                                   # (B,)
            self.v1_at_lmax, self.ghat = _stream_fit_batched(
                self.X, istar, self.y)

    @property
    def X(self) -> jax.Array:
        return self.geometry.X

    @property
    def sumsq(self) -> jax.Array:
        return self.geometry.sumsq

    @property
    def col_norms(self) -> jax.Array:
        return self.geometry.col_norms

    def lam_max_array(self) -> jax.Array:
        """λ_max as a device array: scalar (single) or (B,) (batched)."""
        return jnp.asarray(self.lam_max, self.X.dtype)

    def state_at_lambda_max(self) -> scr.DualState:
        """β* = 0, θ* = y/λ_max (eq. 9) — from cache, no X pass."""
        lmax = self.lam_max_array()
        if self.batch is None:
            return scr.DualState(
                theta=self.y / lmax,
                lam=lmax,
                v1=self.v1_at_lmax,
                at_lmax=jnp.asarray(True),
                beta_l1=jnp.zeros((), dtype=self.X.dtype),
            )
        return scr.DualState(
            theta=self.y / scr._col(lmax),
            lam=lmax,
            v1=self.v1_at_lmax,
            at_lmax=jnp.ones((self.batch,), dtype=bool),
            beta_l1=jnp.zeros((self.batch,), dtype=self.X.dtype),
        )


class ScreeningEngine:
    """One entry point for every per-step screen on a Lasso λ-path.

    Usage (what lasso_path does)::

        eng = ScreeningEngine(X, y)               # one fused pass over X
        state = eng.state_at_lambda_max()
        for lam in grid:
            discard = eng.screen(lam, state, rule="edpp")   # one X pass
            ... reduced solve -> beta ...
            state = eng.make_state(beta, lam)

    Batched (one fitted dictionary, B queries): construct with ``y`` of
    shape (B, n) — ideally passing a shared prefitted ``geometry=`` — and
    call ``screen`` with per-query λ (B,) and a batched DualState. Each
    screen is STILL one streaming pass over X; ``last_x_passes`` counts
    passes per *batch*, so the per-query cost is ``last_x_passes / B``.

    ``last_x_passes`` / ``total_x_passes`` count full HBM passes over X so
    callers (benchmarks, PathStepStats) can report data movement.
    """

    #: Rules the bf16 fast pass serves with a certified margin. PR 8 covered
    #: the single-dot sphere/strong shape; the per-piece interval bounds
    #: (scr.dome_score_bounds + the GAP rescale/radius intervals in the
    #: ``*_margin`` combines above) extend the contract to ``gap``, ``dome``
    #: and every ``<base>_cut`` composite — the whole scalar-rule family now
    #: streams the bf16 copy with masks bit-identical to f32. A future rule
    #: dispatched without a margin derivation runs f32 with a one-time
    #: warning (``_note_f32_fallback``) and reports
    #: ``last_effective_dtype == "float32"``.
    BF16_FAST_RULES = ("dpp", "imp1", "imp2", "edpp", "seq_safe", "safe",
                       "strong", "gap", "dome",
                       *(f"{b}_cut" for b in scr.SPHERE_RULES))

    def __init__(self, X, y, backend: str | None = None,
                 eps: float = scr.EPS_DEFAULT, *,
                 geometry: DictionaryGeometry | None = None,
                 screen_dtype: str = "float32"):
        if screen_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"screen_dtype must be 'float32' or 'bfloat16', "
                f"got {screen_dtype!r}")
        self.ws = PathWorkspace(X, y, backend, geometry=geometry)
        self.eps = eps
        self.screen_dtype = screen_dtype
        # bf16 copy for the fast pass (lazy + cached on the geometry);
        # all thresholds/norms stay full precision.
        self._x_fast = (self.ws.geometry.screen_copy(jnp.bfloat16)
                        if screen_dtype == "bfloat16" else None)
        self._x_fast_err = (self.ws.geometry.screen_err(jnp.bfloat16)
                            if screen_dtype == "bfloat16" else None)
        self.n_screens = 0
        self.total_x_passes = 0
        self.last_x_passes = 0
        self.total_screen_bytes = 0.0
        self.last_screen_bytes = 0.0
        self.last_fallback_cols = 0
        # dtype the last screen actually streamed ("bfloat16" only when the
        # fast pass ran — the narrow f32 fallback doesn't demote it)
        self.last_effective_dtype = "float32"

    def _use_bf16(self, rule: str) -> bool:
        """Whether this screen runs the bf16 fast pass; warns once per rule
        when bfloat16 was requested but no certified margin covers it."""
        if self._x_fast is None:
            return False
        if rule in self.BF16_FAST_RULES:
            self.last_effective_dtype = "bfloat16"
            return True
        _note_f32_fallback(rule)
        return False

    @property
    def lam_max(self):
        """float (single query) or float64 (B,) array (batched)."""
        return self.ws.lam_max

    @property
    def batch(self) -> int | None:
        return self.ws.batch

    @property
    def geometry(self) -> DictionaryGeometry:
        return self.ws.geometry

    @property
    def backend_name(self) -> str:
        return self.ws.backend.name

    def state_at_lambda_max(self) -> scr.DualState:
        return self.ws.state_at_lambda_max()

    def make_state(self, beta, lam, *, fitted=None) -> scr.DualState:
        """Sequential DualState from the solution at λ (KKT eq. 3).
        Batched: beta (B, p), lam (B,) → batched state, still no X pass.
        ``fitted`` (= Xβ, shaped like y) skips even the X·β matvec and
        keeps θ's arithmetic shard-invariant (see `_make_state_fit`)."""
        if self.ws.batch is not None:
            lam_b = jnp.asarray(lam, self.ws.X.dtype)
            if fitted is not None:
                return _make_state_batched_fit(
                    self.ws.y, fitted, beta, lam_b,
                    self.ws.lam_max_array(), self.ws.v1_at_lmax)
            return _make_state_batched(
                self.ws.X, self.ws.y, beta, lam_b,
                self.ws.lam_max_array(), self.ws.v1_at_lmax)
        if fitted is not None:
            return _make_state_fit(self.ws.y, fitted, beta, lam,
                                   self.ws.lam_max, self.ws.v1_at_lmax)
        return _make_state(self.ws.X, self.ws.y, beta, lam,
                           self.ws.lam_max, self.ws.v1_at_lmax)

    def _count(self, passes: int, screen_bytes: float | None = None):
        self.n_screens += 1
        self.last_x_passes = passes
        self.total_x_passes += passes
        if screen_bytes is None:
            n, p = self.ws.X.shape
            screen_bytes = float(passes) * n * p * self.ws.X.dtype.itemsize
        self.last_screen_bytes = screen_bytes
        self.total_screen_bytes += screen_bytes

    def _fast_bytes(self) -> float:
        """HBM bytes one streaming pass over the bf16 screen copy moves."""
        n, p = self.ws.X.shape
        return float(n) * p * self._x_fast.dtype.itemsize

    def _bf16_fallback(self, dec, band, recompute):
        """Re-test the band columns in full precision and override their
        decisions, making the returned mask bit-identical to the f32
        engine's: outside the band the bf16 decision is provably the f32
        decision (the margin bounds |score_bf − score_f32|); inside it the
        narrow full-precision pass IS the f32 decision. Returns
        (mask, extra_passes, extra_bytes)."""
        ws = self.ws
        band_np = tracing.fetch(band)
        cols = np.flatnonzero(
            band_np if band_np.ndim == 1 else band_np.any(axis=0))
        self.last_fallback_cols = int(cols.size)
        if cols.size == 0:
            return dec, 0, 0.0
        p = ws.X.shape[1]
        # bucketed gather (floor 8, multiples of 8): bounds recompilations
        # and keeps the gathered block's width divisible by the
        # feature-mesh sizes the sharded backend supports, so shard_map
        # re-dispatch just works.
        bucket = _narrow_bucket(int(cols.size), p)
        idx = np.zeros((bucket,), dtype=np.int32)
        idx[:cols.size] = cols
        idx_dev = jnp.asarray(idx)
        Xn = jnp.take(ws.X, idx_dev, axis=1)      # full-precision columns
        dec_n = recompute(Xn, idx_dev)
        out = tracing.fetch(dec).copy()
        out[..., cols] = tracing.fetch(dec_n)[..., :cols.size]
        return jnp.asarray(out), 1, float(ws.X.shape[0]) * bucket \
            * ws.X.dtype.itemsize

    def _narrow_sup(self, cand, centre, batched):
        """Exact max(1, ‖Xᵀθ₀‖∞) from a narrow f32 gather of the argmax
        candidates (`_gap_cand`): whenever the true sup exceeds 1 — the
        only case any consumer can distinguish, all of them read the value
        through max(1, ·) — its argmax column is provably a candidate and
        every gathered exact dot is ≤ the true max, so the max over the
        gathered dots recovers the global f32 sup bit-for-bit; otherwise
        the gathered max is some exact dot ≤ sup < 1 and the consumer's
        floor yields the same 1 either way. Pad/union columns that are not
        candidates for a given query only ever contribute values ≤ that
        query's sup, so they never corrupt the max. Returns
        (sup_corr, gather_bytes)."""
        ws = self.ws
        cand_np = tracing.fetch(cand)
        cols = np.flatnonzero(
            cand_np if cand_np.ndim == 1 else cand_np.any(axis=0))
        p = ws.X.shape[1]
        bucket = _narrow_bucket(int(cols.size), p)
        idx = np.zeros((bucket,), dtype=np.int32)
        idx[:cols.size] = cols
        Xn = jnp.take(ws.X, jnp.asarray(idx), axis=1)
        dot_n = ws.backend.matvec(Xn, centre)
        sup = (jnp.max(jnp.abs(dot_n), axis=-1) if batched
               else jnp.max(jnp.abs(dot_n)))
        return sup, float(ws.X.shape[0]) * bucket * ws.X.dtype.itemsize

    def _sphere_screen(self, test: scr.SphereTest, eps_val,
                       rule: str) -> jax.Array:
        """One streaming pass for a plain sphere test — through the bf16
        copy with the margin-aware fallback when screen_dtype asks for it."""
        ws = self.ws
        if not self._use_bf16(rule):
            dot = ws.backend.matvec(ws.X, test.centre)
            self._count(1)
            return _sphere_combine(dot, test.rho, ws.col_norms, eps_val)
        dot = ws.backend.matvec(self._x_fast, test.centre)
        margin = ops.bf16_score_margin(
            self._x_fast_err, jnp.linalg.norm(test.centre, axis=-1))
        dec, band = _sphere_combine_margin(dot, test.rho, ws.col_norms,
                                           eps_val, margin)

        def recompute(Xn, idx_dev):
            return _sphere_combine(ws.backend.matvec(Xn, test.centre),
                                   test.rho, jnp.take(ws.col_norms, idx_dev),
                                   eps_val)

        dec, extra, narrow_bytes = self._bf16_fallback(dec, band, recompute)
        self._count(1 + extra, self._fast_bytes() + narrow_bytes)
        return dec

    def screen(self, lam_next, state: scr.DualState | None,
               rule: str = "edpp") -> jax.Array:
        """Discard mask for λ_next; dispatches every rule through the
        backend's streaming matvec with cached column norms. Single query:
        scalar λ → bool[p]. Batched: λ (B,) → bool[B, p], one X pass for
        the whole batch."""
        ws = self.ws
        batched = ws.batch is not None
        self.last_effective_dtype = "float32"
        if batched:
            lam_next = jnp.asarray(lam_next, ws.X.dtype)
        if rule == "none":
            self._count(0, 0.0)
            shape = (ws.X.shape[1],) if not batched else (ws.batch,
                                                          ws.X.shape[1])
            return jnp.zeros(shape, dtype=bool)
        if rule == "safe":
            lmax = ws.lam_max_array() if batched else ws.lam_max
            test = scr.safe_sphere(ws.y, lam_next, lmax)
            # eq. 15's eps margin is at λ scale: eps/λ once unit-normalised
            return self._sphere_screen(test, self.eps / lam_next, rule)
        if rule == "dome":
            if batched:
                lmax = ws.lam_max_array()
                c = ws.y / scr._col(lam_next)
                rho = jnp.linalg.norm(ws.y, axis=-1) * (
                    1.0 / lam_next - 1.0 / lmax)
                gnorm = jnp.linalg.norm(ws.v1_at_lmax, axis=-1) + 1e-30
            else:
                c = ws.y / lam_next
                rho = jnp.linalg.norm(ws.y) * (
                    1.0 / lam_next - 1.0 / ws.lam_max)
                gnorm = jnp.linalg.norm(ws.v1_at_lmax) + 1e-30
            b_cut = 1.0 / gnorm

            def keep_istar(dec):
                # The dome sup at istar is identically 1 (θ = y/λ_max sits
                # on both the sphere and half-space boundaries with
                # x_*ᵀθ = 1), so the test is exactly ON the discard
                # threshold there and f32 rounding could evict the
                # λ_max-attaining feature. Pin it kept — mirrors
                # scr.dome_mask so engine and oracle masks stay identical.
                if batched:
                    return dec & (jnp.arange(ws.X.shape[1])[None, :]
                                  != jnp.asarray(ws.istar)[:, None])
                return dec.at[ws.istar].set(False)

            if self._use_bf16(rule):
                # both directions ride ONE stacked bf16 pass (the f32 dome
                # spends two passes), bounded per piece by the margins
                dot_c, gdot, stacked = self._stacked_matvec(
                    self._x_fast, c, batched)
                e_c = ops.bf16_score_margin(
                    self._x_fast_err, jnp.linalg.norm(c, axis=-1))
                e_g = ops.bf16_score_margin(
                    self._x_fast_err, jnp.linalg.norm(ws.ghat, axis=-1))
                dec, band = _dome_combine_margin(
                    dot_c, gdot, e_c, e_g, ws.col_norms, c, rho, ws.ghat,
                    b_cut, self.eps)

                def recompute(Xn, idx_dev):
                    dc, dg = self._split_stacked(
                        ws.backend.matvec(Xn, stacked), batched)
                    return _dome_combine(
                        dc, dg, jnp.take(ws.col_norms, idx_dev), c, rho,
                        ws.ghat, b_cut, self.eps)

                dec, extra, narrow_bytes = self._bf16_fallback(
                    dec, band, recompute)
                self._count(1 + extra, self._fast_bytes() + narrow_bytes)
                return keep_istar(dec)
            scores_c = ws.backend.matvec(ws.X, c)
            gdot = ws.backend.matvec(ws.X, ws.ghat)
            self._count(2)
            return keep_istar(_dome_combine(scores_c, gdot, ws.col_norms, c,
                                            rho, ws.ghat, b_cut, self.eps))
        if rule == "strong":
            theta_lam = (state.theta * scr._col(state.lam) if batched
                         else state.theta * state.lam)
            if not self._use_bf16(rule):
                dot = ws.backend.matvec(ws.X, theta_lam)
                self._count(1)
                return _strong_combine(dot, lam_next, state.lam, self.eps)
            dot = ws.backend.matvec(self._x_fast, theta_lam)
            margin = ops.bf16_score_margin(
                self._x_fast_err, jnp.linalg.norm(theta_lam, axis=-1))
            dec, band = _strong_combine_margin(dot, lam_next, state.lam,
                                               self.eps, margin)

            def recompute(Xn, idx_dev):
                return _strong_combine(ws.backend.matvec(Xn, theta_lam),
                                       lam_next, state.lam, self.eps)

            dec, extra, narrow_bytes = self._bf16_fallback(
                dec, band, recompute)
            self._count(1 + extra, self._fast_bytes() + narrow_bytes)
            return dec
        if rule == "gap":
            if not self._use_bf16(rule):
                # one matvec serves the feasibility rescale AND the scores
                dot = ws.backend.matvec(ws.X, state.theta)
                self._count(1)
                return _gap_combine(dot, ws.y, lam_next, state, ws.col_norms,
                                    self.eps)
            dot = ws.backend.matvec(self._x_fast, state.theta)
            margin = ops.bf16_score_margin(
                self._x_fast_err, jnp.linalg.norm(state.theta, axis=-1))
            # stage 1: exact feasibility rescale from the tiny candidate
            # gather, so u and ρ in the margin combine are exact scalars
            sup_corr, sup_bytes = self._narrow_sup(
                _gap_cand(dot, margin), state.theta, batched)
            dec, band = _gap_combine_margin(dot, margin, sup_corr, ws.y,
                                            lam_next, state, ws.col_norms,
                                            self.eps)

            def recompute(Xn, idx_dev):
                # stage 2: the gathered exact dots + the stage-1 sup_corr
                # reproduce the f32 combine's scores bit-for-bit
                return _gap_combine_from(
                    ws.backend.matvec(Xn, state.theta), sup_corr, ws.y,
                    lam_next, state, jnp.take(ws.col_norms, idx_dev),
                    self.eps)

            dec, _, narrow_bytes = self._bf16_fallback(dec, band, recompute)
            # the candidate gather always runs, so gap always pays exactly
            # one narrow extra pass on top of the wide bf16 stream
            self._count(2, self._fast_bytes() + sup_bytes + narrow_bytes)
            return dec
        if rule.endswith("_cut") and rule[:-4] in scr.SPHERE_RULES:
            return self._cut_screen(rule[:-4], lam_next, state, batched)
        if rule not in scr.SPHERE_RULES:
            raise ValueError(
                f"unknown screening rule {rule!r}; available: "
                f"{(*scr.SPHERE_RULES, *scr.CUT_RULES, 'safe', 'dome', 'strong', 'none')}")
        test = scr.make_sphere(rule, ws.y, lam_next, state)
        return self._sphere_screen(test, self.eps, rule)

    def _stacked_matvec(self, X_src, centre, batched: bool):
        """[centre; ĝ] through ONE streaming matvec against ``X_src``.
        Returns (dot_c, gdot, stacked) — ``stacked`` so narrow fallbacks
        can replay the identical operand against gathered f32 columns."""
        ws = self.ws
        if batched:
            # stack-then-reshape, NOT concatenate: jnp.concatenate along a
            # query-sharded axis miscomputes on multi-device meshes
            # (observed on jax 0.4.37 host platforms); the (2, B, n) stack
            # keeps the sharded axis intact and reshapes to the same
            # [centre-rows; ghat-rows] layout.
            stacked = jnp.stack([centre, ws.ghat]).reshape(
                2 * ws.batch, centre.shape[-1])                   # (2B, n)
            dot = ws.backend.matvec(X_src, stacked)
            return dot[:ws.batch], dot[ws.batch:], stacked
        stacked = jnp.stack([centre, ws.ghat])                    # (2, n)
        dot = ws.backend.matvec(X_src, stacked)
        return dot[0], dot[1], stacked

    def _split_stacked(self, dot, batched: bool):
        if batched:
            return dot[:self.ws.batch], dot[self.ws.batch:]
        return dot[0], dot[1]

    def _cut_screen(self, base: str, lam_next, state: scr.DualState,
                    batched: bool) -> jax.Array:
        """``<base>_cut``: the base rule's sphere ∩ the λ_max feasibility
        cut, in ONE streaming pass — the cut normal ĝ (cached in the
        workspace since the fit) is stacked with the sphere centre into a
        single batched matvec, so the extra dot per column rides the same
        HBM pass (same trick the batched query path uses). Under
        screen_dtype="bfloat16" the stacked pass streams the bf16 copy and
        the per-piece margin combines band the decisions (masks stay
        bit-identical — see the margin-combine block above)."""
        ws = self.ws
        gnorm = jnp.linalg.norm(ws.v1_at_lmax, axis=-1) + 1e-30
        b_cut = 1.0 / gnorm                       # ĝᵀθ ≤ 1/‖g‖ on all of F
        if base == "gap":
            centre = state.theta                  # rescale folds into combine
            test = None
        else:
            test = scr.make_sphere(base, ws.y, lam_next, state)
            centre = test.centre
        fast = self._use_bf16(base + "_cut")
        dot_c, gdot, stacked = self._stacked_matvec(
            self._x_fast if fast else ws.X, centre, batched)
        if not fast:
            self._count(1)
            if base == "gap":
                return _gap_cut_combine(dot_c, gdot, ws.y, lam_next, state,
                                        ws.col_norms, ws.ghat, b_cut,
                                        self.eps)
            return _dome_combine(dot_c, gdot, ws.col_norms, test.centre,
                                 test.rho, ws.ghat, b_cut, self.eps)
        e_c = ops.bf16_score_margin(
            self._x_fast_err, jnp.linalg.norm(centre, axis=-1))
        e_g = ops.bf16_score_margin(
            self._x_fast_err, jnp.linalg.norm(ws.ghat, axis=-1))
        sup_corr = sup_bytes = None
        if base == "gap":
            # stage 1 (see the gap branch of `screen`): exact rescale from
            # the tiny candidate gather collapses u, ρ and t_b to exact
            # scalars before the piecewise bounds run
            sup_corr, sup_bytes = self._narrow_sup(
                _gap_cand(dot_c, e_c), centre, batched)
            dec, band = _gap_cut_combine_margin(
                dot_c, gdot, e_c, e_g, sup_corr, ws.y, lam_next, state,
                ws.col_norms, ws.ghat, b_cut, self.eps)
        else:
            dec, band = _dome_combine_margin(
                dot_c, gdot, e_c, e_g, ws.col_norms, test.centre, test.rho,
                ws.ghat, b_cut, self.eps)

        def recompute(Xn, idx_dev):
            dc, dg = self._split_stacked(ws.backend.matvec(Xn, stacked),
                                         batched)
            cn = jnp.take(ws.col_norms, idx_dev)
            if base == "gap":
                return _gap_cut_combine_from(
                    dc, dg, sup_corr, ws.y, lam_next, state, cn, ws.ghat,
                    b_cut, self.eps)
            return _dome_combine(dc, dg, cn, test.centre, test.rho, ws.ghat,
                                 b_cut, self.eps)

        dec, extra, narrow_bytes = self._bf16_fallback(dec, band, recompute)
        if base == "gap":
            # the candidate gather always runs — exactly one narrow extra
            # pass regardless of whether the band gather fired too
            extra, narrow_bytes = 1, narrow_bytes + sup_bytes
        self._count(1 + extra, self._fast_bytes() + narrow_bytes)
        return dec


# ---------------------------------------------------------------------------
# Group-Lasso engine (Corollary 21): same workspace idea, group kernel
# ---------------------------------------------------------------------------

class GroupScreeningEngine:
    """Group-EDPP / group-strong screens through the fused group kernel.

    Caches ‖X_g‖₂ (spectral norms, Theorem 20), λ̄_max and the λ̄_max ray
    v̄₁ = X*X*ᵀy once per path; each screen is then one
    ``group_screen_scores`` pass over X. Pass ``geometry`` (a
    :class:`GroupDictionaryGeometry`) to reuse a prefitted dictionary across
    queries — the spectral norms are then served from cache and only the
    per-query ``‖X_gᵀy‖`` pass runs here.

    Batched (one fitted dictionary, B queries): construct with ``y`` of
    shape (B, n). The fit is one pass over X for the batch and one read of
    its λ̄_max (B,); ``screen`` takes per-query λ (B,) and a batched
    :class:`~repro.core.group_screening.GroupDualState` and returns a
    (B, G) mask from ONE pass over X (``last_x_passes`` counts passes per
    batch), as :class:`ScreeningEngine` does for the Lasso.
    """

    def __init__(self, X, y, m: int, backend: str | None = None,
                 eps: float = gscr.EPS_DEFAULT, *,
                 geometry: GroupDictionaryGeometry | None = None):
        if geometry is None:
            geometry = GroupDictionaryGeometry(X, m, backend)
        geometry.query_passes += 1
        self.geometry = geometry
        self.backend = geometry.backend
        self.X = geometry.X
        self.y = jnp.asarray(y)
        self.m = m
        self.eps = eps
        gscores = self.backend.group_scores(self.X, self.y, m)   # ‖X_gᵀy‖
        if self.y.ndim == 2:
            lmax, self.v1_at_lmax = _group_fit_batched(self.X, gscores,
                                                       self.y, m)
            self.lam_max = tracing.fetch(lmax, np.float64)       # (B,)
        else:
            gnorms = gscores / jnp.sqrt(float(m))
            gstar = int(tracing.fetch(jnp.argmax(gnorms)))
            self.lam_max = float(tracing.fetch(gnorms[gstar]))
            Xstar = jax.lax.dynamic_slice_in_dim(
                self.X, gstar * m, m, axis=1)                    # (N, m)
            self.v1_at_lmax = Xstar @ (Xstar.T @ self.y)         # eq. (59)
        self.spec_norms = geometry.spec_norms
        self.n_screens = 0
        self.total_x_passes = 0
        self.last_x_passes = 0
        self.total_screen_bytes = 0.0
        self.last_screen_bytes = 0.0

    @property
    def batch(self) -> int | None:
        return None if self.y.ndim == 1 else self.y.shape[0]

    @property
    def backend_name(self) -> str:
        return self.backend.name

    def _lam_max_array(self) -> jax.Array:
        return jnp.asarray(self.lam_max, self.X.dtype)

    def state_at_lambda_max(self) -> gscr.GroupDualState:
        lmax = self._lam_max_array()
        return gscr.GroupDualState(theta=self.y / scr._col(lmax), lam=lmax,
                                   v1=self.v1_at_lmax)

    def make_state(self, beta, lam, *, fitted=None) -> gscr.GroupDualState:
        """Sequential dual state from the solution at λ (KKT eq. 52).
        Batched: beta (B, p), lam (B,) and ``fitted`` (B, n), which the
        batched path always supplies."""
        if self.batch is not None:
            lmax = self._lam_max_array()
            return _make_group_state_batched_fit(
                self.y, fitted, jnp.asarray(lam, self.X.dtype), lmax,
                self.y / scr._col(lmax), self.v1_at_lmax)
        if fitted is not None:
            return _make_group_state_fit(
                self.y, fitted, beta, lam, self.lam_max,
                self.y / self.lam_max, self.v1_at_lmax)
        return _make_group_state(
            self.X, self.y, beta, lam, self.lam_max,
            self.y / self.lam_max, self.v1_at_lmax)

    def _count(self, passes: int):
        self.n_screens += 1
        self.last_x_passes = passes
        self.total_x_passes += passes
        n, p = self.X.shape
        screen_bytes = float(passes) * n * p * self.X.dtype.itemsize
        self.last_screen_bytes = screen_bytes
        self.total_screen_bytes += screen_bytes

    def screen(self, lam_next, state: gscr.GroupDualState,
               rule: str = "edpp") -> jax.Array:
        """Discard mask for λ_next: bool[G] for a scalar λ, bool[B, G] for
        per-query λ (B,) — one pass over X either way."""
        G = self.X.shape[1] // self.m
        sqm = float(np.sqrt(self.m))
        if rule == "none":
            self._count(0)
            shape = (G,) if self.batch is None else (self.batch, G)
            return jnp.zeros(shape, dtype=bool)
        if self.batch is not None:
            lam_next = jnp.asarray(lam_next, self.X.dtype)
        if rule == "strong":
            gscores = self.backend.group_scores(
                self.X, state.theta * scr._col(state.lam), self.m)
            mask = _group_strong_combine(gscores, lam_next, state.lam, sqm,
                                         self.eps)
        else:
            centre, rho = _group_edpp_geometry(self.y, lam_next, state)
            gscores = self.backend.group_scores(self.X, centre, self.m)
            mask = _group_edpp_combine(gscores, rho, self.spec_norms, sqm,
                                       self.eps)
        self._count(1)
        return mask
