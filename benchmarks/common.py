"""Shared benchmark harness for the paper-reproduction experiments.

Protocol (mirrors paper §4): solve the Lasso along 100 λ values equally
spaced on λ/λ_max ∈ [0.05, 1.0]; measure

  * rejection ratio — per λ: #discarded-by-rule / #actually-zero (ground
    truth = unscreened float64 solve at tight duality gap);
  * speedup        — time(unscreened path) / time(rule + reduced path);
  * screening cost — the rule's own running time (paper Tables 1-3, last
    columns);
  * solver telemetry — duality-gap checks (host syncs) per λ-step, the
    Gram-CD step fraction and solver HBM passes, via the SolverEngine
    fields of PathStepStats.

Timing is warm (jit pre-compiled by a first throwaway run; the paper's
MATLAB numbers have no compile phase either). Default sizes are scaled for
the CPU container; ``--full`` restores paper sizes.

``write_bench_section`` merges a section into ``BENCH_solver.json`` at the
repo root — the machine-readable artifact CI's solver-bench smoke job
schema-checks (tools/check_bench_schema.py).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

from repro.core import (LassoSession, PathConfig, lambda_grid, lambda_max,
                        oracle_x_passes)
# the ONE percentile definition (numpy's linear-interpolation convention),
# shared by the serve loop, the benches and the tests
from repro.launch.serve_loop import percentile  # noqa: F401
import jax.numpy as jnp

ZERO_TOL = 1e-8
BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_solver.json")

# One fitted LassoSession per (dictionary, backend) for the whole bench
# process: ground_truth + every rule/config A/B against the same X reuse
# the session's DictionaryGeometry and Lipschitz cache, so the fused
# dictionary-fit pass over X runs exactly once per dataset per process.
# id(X) is only a valid key while X is alive, so the cache pins the keyed
# array alongside its session (a freed ndarray's id gets recycled by the
# very next allocation — without the pin a later dataset could silently
# hit the previous dataset's session). The session's dictionary VERSION at
# fit time rides along too: a session mutated by `session.update(...)` no
# longer describes X, so serving it from the cache as if pristine would
# hand later benches a silently edited dictionary — such entries miss and
# refit.
_SESSIONS: dict[int, "tuple[object, LassoSession, int]"] = {}


def session_for(X) -> LassoSession:
    """The process-wide session for this dictionary (fitted on first use).

    Per-call configs (rules, solvers, backends) ride through
    ``session.path(..., config=cfg)`` — geometry is cached per backend
    inside the session, so even backend A/Bs fit each at most once.
    A cached session whose dictionary version moved (``session.update``
    mutated it in place) is discarded and refitted from the pristine X."""
    entry = _SESSIONS.get(id(X))
    if (entry is None or entry[0] is not X
            or getattr(entry[1], "version", 0) != entry[2]):
        sess = LassoSession.fit(X)
        entry = (X, sess, getattr(sess, "version", 0))
        _SESSIONS[id(X)] = entry
    return entry[1]


@dataclasses.dataclass
class RuleResult:
    rule: str
    path_time_s: float
    screen_time_s: float
    rejection: np.ndarray          # per-λ rejection ratio
    speedup: float
    max_beta_err: float
    x_passes_per_step: float = 0.0  # engine HBM passes over X per screen
    jnp_x_passes: int = 0           # what the hand-rolled jnp mask would cost
    gap_checks_per_step: float = 0.0  # solver duality-gap evals (host syncs)
    gram_step_frac: float = 0.0     # fraction of steps solved via Gram CD
    solver_backend: str = ""
    solver_iters: int = 0           # total inner iterations across the path
    solver_x_passes_per_step: float = 0.0  # full-X-equivalent solver passes
    batch_size: int = 1             # queries sharing each screen/solve pass
    x_passes_per_query: float = 0.0  # amortised screen passes: passes/B —
    #                                  the axis bench_batched.py reports its
    #                                  multi-query runs on (docs/serving.md)
    masks: np.ndarray | None = None     # per-λ discard masks (exactness A/Bs)


def beta_err_tol(y, solver_tol: float, kappa: float = 25.0) -> float:
    """Exactness threshold for comparing two solver-precision paths.

    Both paths stop at relative duality gap ``solver_tol``, i.e. absolute
    gap ε ≤ solver_tol·½‖y‖². For a gap-ε point, ‖β − β*‖ ≤ √(2ε/μ) with μ
    the smallest curvature of the active block (σ²_min(X_active)); comparing
    two ε-points doubles it. μ is data-dependent — on the ill-conditioned
    near-square reduced problems the weak rules keep (seq-SAFE at n ≈ kept)
    σ²_min drops to ~1e-2·‖y‖²/n — so ``kappa`` absorbs √(2·2/μ) with
    headroom. The point of tying the bound to ``solver_tol``: halve the
    solver precision and the acceptable drift scales as √solver_tol instead
    of silently failing (the seed's fixed 5e-4 did exactly that on
    leukemia-like at 8.26e-4).
    """
    scale = 0.5 * float(np.asarray(y) @ np.asarray(y))
    return kappa * float(np.sqrt(solver_tol * scale))


def stats_means(res, attr: str) -> float:
    """Mean of a PathStepStats field over the screened (non-trivial) steps."""
    vals = [getattr(s, attr) for s in res.stats if s.screen_time_s > 0]
    return float(np.mean(vals)) if vals else 0.0


def ground_truth(X, y, grid, solver_tol=1e-12) -> "tuple[np.ndarray, float]":
    """Unscreened float64 path (the paper's 'solver' column) + its time."""
    cfg = PathConfig(rule="none", solver_tol=solver_tol)
    sess = session_for(X)
    sess.reset_solver_cache()          # deterministic replay (see run_rule)
    sess.path(y, grid, config=cfg)                 # warm compile
    t0 = time.perf_counter()
    res = sess.path(y, grid, config=cfg).squeeze()
    return res.betas, time.perf_counter() - t0


def run_rule(X, y, grid, rule, betas_ref, t_ref, solver_tol=1e-12,
             sequential=True, **cfg_overrides) -> RuleResult:
    # kkt_tol tight so the heuristic strong rule recovers the exact
    # solution (its violations are re-added down to fp precision)
    cfg = PathConfig(rule=rule, solver_tol=solver_tol,
                     sequential=sequential, kkt_tol=1e-8, **cfg_overrides)
    sess = session_for(X)                # fit-once: shared with ground_truth
    # Every arm starts from the same deterministic cold Lipschitz cache:
    # the warm-started eigenpairs make solves depend on the session's call
    # HISTORY, and the precision A/Bs below assert masks bit-identical
    # between arms — GAP's ρ = √(2·gap)/λ amplifies an ulp of history-
    # dependent β into a flipped threshold-straddling mask bit otherwise.
    sess.reset_solver_cache()
    sess.path(y, grid, config=cfg)                 # warm compile
    t0 = time.perf_counter()
    res = sess.path(y, grid, config=cfg).squeeze()
    dt = time.perf_counter() - t0

    rej = np.zeros(len(grid))
    for k in range(len(grid)):
        zero_truth = np.abs(betas_ref[k]) <= ZERO_TOL
        n_zero = int(zero_truth.sum())
        rej[k] = res.stats[k].n_discarded / max(n_zero, 1)
    err = float(np.abs(res.betas - betas_ref).max())
    screened = [s for s in res.stats if s.screen_time_s > 0]
    return RuleResult(
        rule=rule, path_time_s=dt,
        screen_time_s=res.total_screen_time,
        rejection=rej, speedup=t_ref / max(dt, 1e-12),
        max_beta_err=err,
        # trivial-region steps (λ ≥ λmax) never screen/solve; excluded
        x_passes_per_step=stats_means(res, "x_passes"),
        jnp_x_passes=oracle_x_passes(rule),
        gap_checks_per_step=stats_means(res, "gap_checks"),
        gram_step_frac=stats_means(res, "gram_step_frac"),
        solver_backend=screened[0].solver_backend if screened else "",
        solver_iters=int(sum(s.solver_iters for s in res.stats)),
        solver_x_passes_per_step=stats_means(res, "solver_x_passes"),
        batch_size=screened[0].batch_size if screened else 1,
        x_passes_per_query=stats_means(res, "x_passes_per_query"),
        masks=None if res.masks is None else np.asarray(res.masks),
    )


def emit(name: str, us_per_call: float, derived: str):
    """The run.py CSV convention: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.1f},{derived}")


def write_bench_section(section: str, meta: dict, rows: list[dict],
                        path: str = BENCH_JSON) -> None:
    """Merge {section: {meta, rows}} into the BENCH_solver.json artifact."""
    doc = {"sections": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (json.JSONDecodeError, OSError):
            doc = {"sections": {}}
    doc.setdefault("sections", {})[section] = {"meta": meta, "rows": rows}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")


def normalize_columns(X, y=None):
    X = X / (np.linalg.norm(X, axis=0, keepdims=True) + 1e-30)
    if y is None:
        return X
    return X, y / np.linalg.norm(y)


def grid_for(X, y, num=100, lo=0.05):
    lmax = float(lambda_max(jnp.asarray(X), jnp.asarray(y)))
    return lambda_grid(lmax, num=num, lo_frac=lo)
