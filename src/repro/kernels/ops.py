"""Jit'd public wrappers + backend dispatch for the Pallas screening kernels.

On TPU the kernels compile to Mosaic; on a CPU they run on the Pallas
interpreter (``interpret=True``). The platform is read when a backend is
resolved or a kernel is called, never at import, so importing this module
does not claim an accelerator.

``BACKENDS`` is the registry the :class:`repro.core.engine.ScreeningEngine`
dispatches through. Each entry is a :class:`ScreenBackend` with three ops
sharing one contract (see docs/kernels.md):

    matvec(X, centre)            -> dot[p]          = x_jᵀ·centre
    fused_scores(X, centre, rho) -> (scores[p], sumsq[p])
                                    scores = |dot| + rho·‖x_j‖, sumsq = ‖x_j‖²
    group_scores(X, centre, m)   -> gscores[G]      = ‖X_gᵀ·centre‖

Backends: ``pallas`` (compiled Mosaic, TPU), ``interpret`` (the same kernel
bodies on the Pallas interpreter — CI/CPU), ``jnp`` (the pure-jnp oracles of
ref.py, also the GSPMD-friendly fallback). All accumulate in f32, and every
dot runs at ``Precision.HIGHEST`` on every backend: the default TPU matmul
precision is one bf16 pass, too coarse for a 1 − 1e-6 screening threshold
and for FISTA's 1e-6 duality-gap stop. The compiled ``pallas`` kernels
refuse float64 operands.

Every op is **batch-polymorphic** over the query operands (see ref.py):
``centre``/``r``/``z``/``beta`` may carry a leading batch axis (B, ·) with
per-query scalars as (B,) vectors — one fitted dictionary, B queries, ONE
pass over X per call. ``sumsq`` stays (p,): dictionary geometry. This is
the kernel-level contract the batched engines and ``lasso_path_batched``
ride on (docs/serving.md); rank-1 inputs keep single-query arithmetic
bit-for-bit.
"""

from __future__ import annotations

import functools
import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import ref
from .edpp_screen import edpp_screen_scores, resolve_tiles, screen_matvec
from .group_screen import group_screen_scores
from .prox_step import prox_step
from .solver_step import GRAM_BUCKET_MAX, cd_gram_sweep, fista_step


class ScreenBackend(NamedTuple):
    """One implementation of the kernel-op contract (see module doc).

    The first three ops are the screening contract the ScreeningEngine
    dispatches through; the trailing solver ops (fista_step /
    cd_gram_sweep / prox_step, see docs/solvers.md) serve the
    SolverEngine. They default to ``None`` so screen-only backends
    registered before the solver layer existed keep working — the
    SolverEngine falls back to the ref.py oracles for missing ops.
    """

    name: str
    matvec: Callable
    fused_scores: Callable
    group_scores: Callable
    fista_step: Callable | None = None
    cd_gram_sweep: Callable | None = None
    prox_step: Callable | None = None


def _kernel_backend(name: str, interpret: bool) -> ScreenBackend:
    return ScreenBackend(
        name=name,
        matvec=functools.partial(screen_matvec, interpret=interpret),
        fused_scores=functools.partial(edpp_screen_scores,
                                       interpret=interpret),
        group_scores=functools.partial(group_screen_scores,
                                       interpret=interpret),
        fista_step=functools.partial(fista_step, interpret=interpret),
        cd_gram_sweep=functools.partial(cd_gram_sweep, interpret=interpret),
        prox_step=functools.partial(prox_step, interpret=interpret),
    )


def _interpret_by_default() -> bool:
    return jax.default_backend() != "tpu"


def default_backend_name(env_var: str) -> str:
    """Shared backend auto-detection policy: explicit env var →
    ``INTERPRET=1`` (CI) → ``pallas`` on TPU → ``jnp``. The two engines
    differ only in the env var (``REPRO_SCREEN_BACKEND`` vs
    ``REPRO_SOLVER_BACKEND``) so they can be A/B'd independently."""
    env = os.environ.get(env_var)
    if env:
        return env
    if os.environ.get("INTERPRET", "") not in ("", "0"):
        return "interpret"
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


BACKENDS: dict[str, ScreenBackend] = {
    "pallas": _kernel_backend("pallas", interpret=False),
    "interpret": _kernel_backend("interpret", interpret=True),
    "jnp": ScreenBackend(
        name="jnp",
        matvec=jax.jit(ref.screen_matvec_ref),
        fused_scores=jax.jit(ref.edpp_screen_ref),
        group_scores=jax.jit(ref.group_screen_ref, static_argnames="m"),
        fista_step=jax.jit(ref.fista_step_ref),
        cd_gram_sweep=jax.jit(ref.cd_gram_sweep_ref,
                              static_argnames="sweeps"),
        prox_step=jax.jit(ref.prox_step_ref),
    ),
}


# --------------------------------------------------------------------------
# Mixed-precision screening contract (docs/kernels.md).
#
# X may be STORED in bf16 while every tile dot ACCUMULATES in f32 — the
# pallas kernel body casts tiles up before the MXU dot and ref._acc_dtype
# promotes the jnp oracle the same way. The only storage error is the
# rounding of X itself: with Δx_j = x_j − bf16(x_j), Cauchy-Schwarz bounds
# the dot against any full-precision centre by
#
#     |x̂_jᵀc − x_jᵀc| ≤ ‖Δx_j‖·‖c‖.
#
# ‖Δx_j‖ is MEASURED per column at screen-copy time (bf16_column_err) —
# typically ≈ 2⁻⁹‖x_j‖/√3 (rounding errors add in quadrature), ~7× tighter
# than the worst-case u‖x_j‖ bound, so ~7× fewer columns land in the
# fallback band. On top ride the f32 accumulation noise of both passes
# (γ_n ≈ n·2⁻²⁴ relative, the F32_ACC_ROUND term — covers reduction-order
# differences between the wide bf16 pass and the narrow f32 re-test too)
# and a 2× safety factor.
# --------------------------------------------------------------------------

BF16_ROUND = 2.0 ** -8         # bf16 unit roundoff (worst case, 8-bit mant.)
F32_ACC_ROUND = 2.0 ** -24     # f32 accumulation unit roundoff
BF16_MARGIN_SAFETY = 2.0


def bf16_column_err(X, X_lo):
    """Per-column dot-error bound for screening through the low-precision
    copy ``X_lo``: ``err[j] = ‖x_j − x̂_j‖ + 2·n·u_f32·‖x_j‖`` (measured
    quantisation residual + the accumulation noise of both the wide and the
    narrow pass). Computed once per screen copy, cached on the geometry."""
    Xf = jnp.asarray(X, jnp.float32)
    quant = jnp.linalg.norm(Xf - jnp.asarray(X_lo, jnp.float32), axis=0)
    col_norms = jnp.linalg.norm(Xf, axis=0)
    n = Xf.shape[0]
    return quant + 2.0 * n * F32_ACC_ROUND * col_norms


def bf16_score_margin(col_err, centre_norm):
    """Per-column error bound on a linear screen score evaluated through a
    bf16 copy of X: ``margin[j] = 2·err_j·‖centre‖`` with ``err_j`` from
    :func:`bf16_column_err`. The ρ‖x_j‖ term of a sphere score is exact
    (both factors stay full precision), so this bounds the whole score
    error. Columns whose bf16 score lands within the margin of the decision
    threshold are re-tested in full precision (the ScreeningEngine's
    margin-aware fallback), which makes bf16 masks bit-identical to the f32
    engine's. ``centre_norm``: scalar or (B,) → margin (p,) or (B, p)."""
    cn = jnp.asarray(centre_norm, jnp.float32)[..., None]
    return BF16_MARGIN_SAFETY * cn * jnp.asarray(col_err)


# Solver-side mixed precision (docs/solvers.md#mixed-precision-solves).
# The FISTA iteration matvecs (forward fit + fused gradient step — the
# 2·cadence HBM passes between gap checks) and the Gram-CD build
# (G̃ = X̃ᵀX̃, c̃ = X̃ᵀy — the ONE HBM pass that solver path takes over the
# bucket) may stream a bf16 copy of the reduced bucket; the duality-gap
# CERTIFICATE itself always streams f32 X,
# so convergence declared in the low-precision phase is true convergence —
# exactness never rests on the bf16 data. `bf16_gap_budget` bounds the gap
# level below which a bf16 gradient can no longer make certified progress;
# the low-precision phase hands over to the f32 polish when the (exact) gap
# both sits under BF16_SOLVE_SLACK × budget AND has stopped decaying by
# BF16_SOLVE_PROGRESS per check (iterating bf16 past its own noise floor is
# pure waste — but a loose worst-case budget alone must not evict a stream
# that is still measurably converging).

BF16_SOLVE_SLACK = 2.0
BF16_SOLVE_PROGRESS = 0.7      # min per-check gap decay to keep bf16 going:
#                                a cadence block that fails to cut the gap
#                                by 30% while inside the certified band is
#                                noise-limited — hand over to f32


def bf16_gap_budget(resid_norm, beta_l1, err_max, col_norm_max):
    """Certified first-order bound on the duality-gap excess a bf16
    gradient stream can leave uncorrected, evaluated at the current iterate
    (per-column dot-error bounds err_j ≤ err_max from
    :func:`bf16_column_err`, ‖x_j‖ ≤ col_norm_max).

    Hölder gives the residual error  e_r = ‖r − r̃‖ ≤ err_max·‖β‖₁  and the
    gradient error  e_d = ‖X̂ᵀr̃ − Xᵀr‖∞ ≤ err_max·‖r‖ + col_norm_max·e_r.
    A fixed point of the perturbed proximal-gradient iteration satisfies
    the true KKT system shifted by at most e_d per coordinate — i.e. its
    dual infeasibility contributes at most e_d·‖β‖₁ to the gap — and the
    residual perturbation moves the primal term by at most e_r·‖r‖::

        budget = e_d·‖β‖₁ + e_r·‖r‖

    Below ~this level the bf16 stream cannot certifiably decrease the
    (exactly measured) gap further. Batch-polymorphic: scalars or (B,)
    vectors throughout."""
    e_r = err_max * beta_l1
    e_d = err_max * resid_norm + col_norm_max * e_r
    return e_d * beta_l1 + e_r * resid_norm


def bf16_certified_stop(gap, budget, prev_gap, tol_scale):
    """The certified handover rule every bf16 solve stream shares (FISTA's
    lo iteration phase and the Gram-CD lo build — both perturb the gradient
    to X̃ᵀ(X̃β − y), which is exactly what :func:`bf16_gap_budget` bounds).

    Stop the low-precision phase when the EXACTLY-measured gap is already
    under ``tol_scale`` (true convergence — the certificate streamed f32
    X), or when it has both stalled (failed to decay by
    ``BF16_SOLVE_PROGRESS`` over the last check) and sits under
    ``BF16_SOLVE_SLACK ×`` the certified budget (noise-floored — a bf16
    gradient can no longer provably improve it). Batch-polymorphic:
    scalars or (B,) vectors throughout."""
    stalled = gap > BF16_SOLVE_PROGRESS * prev_gap
    floored = gap <= BF16_SOLVE_SLACK * budget
    return jnp.logical_or(gap <= tol_scale,
                          jnp.logical_and(stalled, floored))


def edpp_screen(X, centre, rho, eps: float = 1e-6, *, col_norms=None,
                interpret: bool | None = None):
    """Full fused screening decision.

    Returns (discard_mask, scores, sumsq). If ``col_norms`` (‖x_j‖₂) is
    provided — cached across a λ-path — only the matvec kernel runs.
    """
    it = _interpret_by_default() if interpret is None else interpret
    if col_norms is not None:
        dot = screen_matvec(X, centre, interpret=it)
        rho = jnp.asarray(rho)
        if dot.ndim == 2:                 # batched: per-query rho column
            rho = rho[..., None]
        scores = jnp.abs(dot) + rho * col_norms
        sumsq = jnp.square(col_norms)
    else:
        scores, sumsq = edpp_screen_scores(X, centre, rho, interpret=it)
    return scores < 1.0 - eps, scores, sumsq


def group_edpp_screen(X, centre, rho, m: int, spec_norms, eps: float = 1e-6,
                      *, interpret: bool | None = None):
    """Fused group screening decision (Corollary 21).

    gscores[g] = ‖X_gᵀ·centre‖; discard iff gscores[g] < √m − rho·‖X_g‖₂ − eps.
    """
    it = _interpret_by_default() if interpret is None else interpret
    gscores = group_screen_scores(X, centre, m, interpret=it)
    thresh = jnp.sqrt(float(m)) - rho * spec_norms - eps
    return gscores < thresh, gscores


__all__ = [
    "BACKENDS",
    "BF16_MARGIN_SAFETY",
    "BF16_ROUND",
    "BF16_SOLVE_PROGRESS",
    "BF16_SOLVE_SLACK",
    "GRAM_BUCKET_MAX",
    "ScreenBackend",
    "F32_ACC_ROUND",
    "bf16_certified_stop",
    "bf16_column_err",
    "bf16_gap_budget",
    "bf16_score_margin",
    "cd_gram_sweep",
    "edpp_screen",
    "edpp_screen_scores",
    "fista_step",
    "group_edpp_screen",
    "group_screen_scores",
    "prox_step",
    "resolve_tiles",
    "screen_matvec",
]
