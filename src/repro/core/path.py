"""Sequential λ-path driver: screen → reduce → solve → (KKT re-check) → next.

This is the regime the paper targets (§1): model selection solves the Lasso
over a grid λ₁ > λ₂ > … > λ_K, and the sequential rules thread the exact dual
point θ*(λ_k) from each solution into the screen for λ_{k+1}.

Engineering notes
-----------------
* Callers reach this module through the session front door
  (:class:`repro.core.session.LassoSession`); the old ``lasso_path`` /
  ``lasso_path_batched`` / ``group_lasso_path`` functions at the bottom of
  this file are deprecation shims over it. Everything funnels into ONE
  generic :func:`_path_driver` that owns bucketing, column gather, the
  warm-start scatter/gather of β between buckets and the KKT re-check
  rounds — and consumes BOTH engines:

  - every per-step screen goes through the :class:`repro.core.engine`
    ``ScreeningEngine`` (λ-independent geometry cached once, one streaming
    HBM pass over X per screen, ``PathStepStats.x_passes``);
  - every reduced solve goes through the :class:`repro.core.solver`
    ``SolverEngine`` (device-resident ``lax.while_loop`` iteration through
    the fused solver kernels, duality gap checked every
    ``gap_check_cadence`` iterations — ``PathStepStats.gap_checks`` — and
    the Gram-CD crossover recorded in ``gram_step_frac``).

  Backends for the two engines are selected independently:
  ``PathConfig.backend`` / ``REPRO_SCREEN_BACKEND`` for screens,
  ``PathConfig.solver_backend`` / ``REPRO_SOLVER_BACKEND`` for solves
  ("pallas" | "interpret" | "jnp" | None = auto).
* The *reduced* problems have data-dependent sizes, which fights XLA's static
  shapes. We gather surviving columns (whole groups for m > 1) into
  power-of-two **buckets** (zero padded); solvers treat zero columns as fixed
  points, and jit compiles at most O(log p) program variants per path.
* **Batched multi-query paths** (``lasso_path_batched``): one fitted
  dictionary, B response vectors through the whole loop. Per grid step the
  engine screens all B queries in ONE fused pass over X, the survivors are
  **union-bucketed** into a shared buffer, and a single batched solve runs
  with per-query λ, per-query validity masks and per-query convergence
  freezing inside the solver ``lax.while_loop`` (converged queries become
  fixed points — counted in ``PathStepStats.queries_converged``). Queries in
  their trivial region (λ ≥ own λ_max) stay at β = 0. Program variants stay
  O(log p) per batch shape (buckets are pow-2, B is fixed per call), and
  screen HBM cost is amortised ~1/B per query
  (``PathStepStats.x_passes_per_query``).
* The strong rule is heuristic: after each reduced solve we run the paper's
  KKT violation loop — violated features are added back and the problem
  re-solved until clean (§1, §4.1.2). Safe rules never trigger it (property-
  tested), but the check runs for them too in ``paranoid`` mode as telemetry.
* Each grid step emits a :class:`PathStepStats` and (optionally) checkpoints
  (λ_k, β*_k) so a long path can resume mid-grid (see repro.checkpoint).
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from . import screening as scr
from . import group_screening as gscr
from . import tracing


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


# Module-level jitted helpers (a fresh `jax.jit(f)` per call would retrace).
_kkt_violations = jax.jit(scr.kkt_violations)
_group_kkt_violations = jax.jit(gscr.group_kkt_violations,
                                static_argnames="m")


@dataclasses.dataclass
class PathStepStats:
    lam: float
    n_discarded: int              # units: features (m=1) or groups (m>1)
    n_kept: int
    solver_iters: int
    gap: float
    kkt_rounds: int
    screen_time_s: float
    solve_time_s: float
    x_passes: int = 0             # full HBM passes over X this screen took
    gap_checks: int = 0           # duality-gap evals this step's solves ran
    gram_step_frac: float = 0.0   # fraction of this step's solves on Gram CD
    solver_backend: str = ""      # kernel backend the solves dispatched to
    screen_backend: str = ""      # backend the screens dispatched to
    #                               ("shard:<tile>" on a mesh session)
    bucket: int = 0               # padded bucket size (columns) solved at
    solver_x_passes: float = 0.0  # solver HBM passes in full-X equivalents
    batch_size: int = 1           # queries screened/solved together this step
    queries_converged: int = 0    # queries whose reduced solve converged
    x_passes_per_query: float = 0.0  # amortised screen passes: x_passes/B
    screen_dtype_effective: str = ""  # dtype the screen stream actually ran
    #                               ("float32" when a bf16 request fell back)
    solve_dtype_effective: str = ""   # dtype the solver matvecs streamed
    solver_lo_iters: int = 0      # solver iterations run on the bf16 stream
    geometry_version: int = 0     # dictionary version this step ran against
    #                               (0 at fit; +1 per session.update — lets
    #                               serve traces attribute results to the
    #                               dictionary they were computed on)
    # Host-side breakdown of the step (core/tracing.py; each interval is
    # also a span in the profiler's trace, docs/serving.md#tracing-a-
    # served-path):
    host_syncs: int = 0           # device→host reads (tracing.fetch)
    host_sync_s: float = 0.0      # time spent in them
    gather_time_s: float = 0.0    # path.gather: bucket columns + warm start
    copyout_time_s: float = 0.0   # path.copyout: β's kept columns (host
    #                               scatter into float64) and the mask
    state_time_s: float = 0.0     # path.state: the next step's dual state
    step_time_s: float = 0.0      # path.step: the whole step
    compiles: int = 0             # programs compiled or loaded from the
    #                               persistent cache during the step
    member_major_solves: int = 0  # reduced solves that iterated on the
    #                               member-major bucket (group solver, m > 1)


@dataclasses.dataclass
class PathResult:
    """The ONE path result type, single- and multi-query alike.

    :meth:`LassoSession.path <repro.core.session.LassoSession.path>` always
    returns the batched layout — a leading batch axis on every array, B = 1
    for a single query — so callers never branch on a second result class:

        lambdas  (B, K)        per-query λ grids
        betas    (B, K, p)     per-query coefficient paths
        masks    (B, K, units) per-query post-KKT discard masks
        stats    [PathStepStats] per grid step (shared across the batch)
        query_converged (B,)   per-query completion flag: True iff every
                               non-trivial reduced solve for that query hit
                               its duality-gap stop within max_iter (a
                               query "forced past max iters" reports False
                               here — what the serve loop surfaces per
                               ticket)

    ``squeeze()`` drops the batch axis of a B = 1 result (what the
    deprecated ``lasso_path`` / ``group_lasso_path`` shims return, with
    ``betas`` (K, p));  ``query(b)`` views one query of a batched result in
    that squeezed layout. ``betas[b]``/``masks[b]``/``lambdas[b]`` line up
    with the squeezed single-query result of query b (same grid, same rule;
    masks bit-identical for grid points strictly inside (0, λ_max) — see
    docs/api.md#exactness-contract for the λ = λ_max endpoint caveat).
    """

    lambdas: np.ndarray
    betas: np.ndarray
    stats: list[PathStepStats]
    masks: np.ndarray | None = None
    query_converged: np.ndarray | None = None

    @property
    def batched(self) -> bool:
        """True while the leading batch axis is present (betas (B, K, p))."""
        return self.betas.ndim == 3

    @property
    def batch(self) -> int:
        return self.betas.shape[0] if self.batched else 1

    @property
    def total_solve_time(self) -> float:
        return sum(s.solve_time_s for s in self.stats)

    @property
    def total_screen_time(self) -> float:
        return sum(s.screen_time_s for s in self.stats)

    def squeeze(self) -> "PathResult":
        """Drop the batch axis of a B = 1 result: betas (K, p), masks
        (K, units), lambdas (K,). Values are the same arrays viewed without
        the leading axis — bit-identical, no copy."""
        if not self.batched:
            return self
        if self.batch != 1:
            raise ValueError(
                f"squeeze() needs a single-query result, got B={self.batch};"
                " use query(b) to select one query")
        return PathResult(lambdas=self.lambdas[0], betas=self.betas[0],
                          stats=self.stats, masks=self.masks[0],
                          query_converged=self.query_converged)

    def query(self, b: int) -> "PathResult":
        """View of query b in the squeezed layout (stats stay shared;
        ``query_converged`` narrows to query b's flag)."""
        if not self.batched:
            raise ValueError("query(b) needs a batched result")
        qc = self.query_converged
        return PathResult(lambdas=self.lambdas[b], betas=self.betas[b],
                          stats=self.stats, masks=self.masks[b],
                          query_converged=None if qc is None else qc[b:b + 1])


@functools.partial(jax.jit, static_argnames=("bucket",))
@jax.named_scope("gather")
def _gather_cols(X: jax.Array, idx: jax.Array, valid: jax.Array, bucket: int):
    """Gather `bucket` columns (zero-filled where invalid)."""
    cols = jnp.take(X, idx, axis=1, mode="clip")
    return cols * valid[None, :]


@functools.partial(jax.jit, static_argnames=("p",))
@jax.named_scope("scatter")
def _step_epilogue(beta_r, iters, gap, converged, Xr, idx, valid, p: int):
    """What follows a reduced solve, as one program keyed on (B, bucket,
    n, p, dtype) alone, never on the kept count.

    ``beta_r`` is the solve's (b,) or (B, b) β on the bucket, ``iters`` /
    ``gap`` / ``converged`` its scalar or (B,) telemetry, ``idx``/``valid``
    the padded bucket indices of :func:`_pad_indices`. Returns
    ``beta_full`` (B, p) (β scattered back to p), ``fitted`` (B, n) = Xr·β_r
    and the packed summary (B, b + 3): β_r, then iters, gap and converged
    per query, for the host to read in one transfer."""
    b = Xr.shape[1]
    beta_r = beta_r.reshape(-1, b)
    B = beta_r.shape[0]
    # padded slots carry index 0, a real column: send them out of range
    cols = jnp.where(valid > 0, idx, p)
    beta_full = (jnp.zeros((B, p), Xr.dtype)
                 .at[:, cols].set(beta_r, mode="drop"))
    fitted = (Xr @ beta_r[0])[None, :] if B == 1 else beta_r @ Xr.T
    dt = jnp.promote_types(jnp.promote_types(beta_r.dtype, gap.dtype),
                           jnp.float32)
    per_query = jnp.stack([a.reshape(B).astype(dt)
                           for a in (iters, gap, converged)], axis=1)
    summary = jnp.concatenate([beta_r.astype(dt), per_query], axis=1)
    return beta_full, fitted, summary


def _pad_indices(kept: np.ndarray, bucket: int):
    idx = np.zeros((bucket,), dtype=np.int32)
    idx[: kept.size] = kept
    valid = np.zeros((bucket,), dtype=np.float32)
    valid[: kept.size] = 1.0
    return jnp.asarray(idx), jnp.asarray(valid)


def lambda_grid(lam_max: float, num: int = 100, lo_frac: float = 0.05,
                hi_frac: float = 1.0) -> np.ndarray:
    """The paper's grid: `num` values equally spaced in λ/λmax ∈ [lo, hi]."""
    return np.linspace(hi_frac, lo_frac, num) * lam_max


def _path_driver(X, Y, lambdas, cfg, *, m: int, screen_engine,
                 solver_engine: SolverEngine, need_kkt: bool,
                 kkt_fn, batch: int | None = None, reshard=None,
                 lo_gather=None):
    """The shared screen → reduce → solve → KKT loop over a decreasing grid.

    ``m`` is the unit size: 1 for the Lasso (units = features), the group
    size for the group Lasso (units = groups; whole groups are gathered).
    ``kkt_fn(beta_full, lam, discard, fitted)`` flags violations per unit.

    ``reshard`` (mesh sessions) is applied to the gathered reduced bucket:
    `jnp.take` from a column-sharded X already yields a replicated block,
    but the hook pins that down so every reduced solve — whatever kernel
    backend — runs on replicated arrays. Together with the bucket-computed
    fitted values (``fitted = Xr·β_r``, threaded into KKT and the next
    dual state instead of a full, psum-ordered X·β), this is what makes
    sharded and unsharded masks bit-identical (docs/distributed.md).

    ``lo_gather`` (set by the session when ``solve_dtype="bfloat16"``) maps
    the same ``(idx, valid, bucket)`` the f32 gather uses onto the cached
    bf16 dictionary copy: it returns ``(X_lo_r, err_max, cn_max)`` — the
    reduced low-precision bucket plus the per-bucket error/norm bounds the
    solver's certified bf16 phase needs (docs/solvers.md). The driver
    threads it as ``lo=`` into every reduced solve so the session-level
    copy is fitted once and shared with the bf16 screen path.

    ``batch``: None runs the classic single-query path (Y (n,), lambdas
    (K,), engine called with scalar λ). batch=B runs B queries against one
    fitted dictionary END-TO-END: Y (B, n), per-query grids (B, K), one
    fused screen per step for the whole batch, survivors UNION-bucketed
    into a shared buffer, a single batched solve with per-query validity
    masks and convergence freezing (``solve_batched``), per-query KKT
    re-check rounds, and per-query trivial-region handling (a query whose
    λ ≥ its own λ_max stays at β = 0 and screens everything). Internally
    everything is (B, ·)-shaped with B = 1 for the single-query case, so
    both modes share one loop.
    """
    X = jnp.asarray(X)
    Y = jnp.asarray(Y)
    p = X.shape[1]
    units = p // m
    assert units * m == p
    B = 1 if batch is None else batch
    bucket_min = cfg.bucket_min if cfg.bucket_min is not None \
        else (32 if m == 1 else 16)
    # hybrid safe+strong (Zeng et al. 2017): OR the heuristic strong-rule
    # discards into the safe rule's, with the KKT loop as the backstop
    hybrid = bool(getattr(cfg, "hybrid_strong", False)) \
        and cfg.rule not in ("strong", "none")
    lambdas = np.asarray(lambdas, dtype=np.float64)
    if batch is None:
        assert np.all(np.diff(lambdas) <= 1e-12), "grid must be decreasing"
        K = lambdas.shape[0]
    else:
        assert lambdas.ndim == 2 and lambdas.shape[0] == B, \
            "batched grids must be (B, K)"
        assert np.all(np.diff(lambdas, axis=1) <= 1e-12), \
            "grids must be decreasing"
        K = lambdas.shape[1]

    with tracing.span("path.prologue"):
        lmax = np.atleast_1d(np.asarray(screen_engine.lam_max,
                                        dtype=np.float64))      # (B,)
        state = screen_engine.state_at_lambda_max()
        arange_m = np.arange(m)[None, :]
        geo_version = int(getattr(getattr(screen_engine, "geometry", None),
                                  "version", 0))

        betas = np.zeros((B, K, p), dtype=np.float64)
        masks = np.ones((B, K, units), dtype=bool)
        stats: list[PathStepStats] = []
        beta_prev = jnp.zeros((B, p), dtype=X.dtype)
        # per-query completion: a query stays True iff every non-trivial
        # reduced solve it took part in converged (query_converged)
        q_converged = np.ones((B,), dtype=bool)

    steps = []                    # each λ step's tracing counters
    for k in range(K):
        with tracing.step(k) as step:
            steps.append(step)
            lam_vec = lambdas[None, k] if batch is None else lambdas[:, k]
            live = lam_vec < lmax          # per-query trivial region (eq. 8)
            if not live.any():             # β* = 0 for the whole batch
                stats.append(PathStepStats(
                    float(lam_vec.max()), units, 0, 0, 0.0, 0, 0.0, 0.0,
                    batch_size=B, queries_converged=B,
                    geometry_version=geo_version))
                if cfg.checkpoint_fn:
                    if batch is None:
                        cfg.checkpoint_fn(k, float(lam_vec[0]), np.zeros((p,)))
                    else:
                        cfg.checkpoint_fn(k, lam_vec, np.zeros((B, p)))
                continue

            # ---- screen (one fused kernel pass over X for ALL queries) --
            with tracing.span("path.screen"):
                t0 = time.perf_counter()
                lam_dev = (float(lam_vec[0]) if batch is None
                           else jnp.asarray(lam_vec, X.dtype))
                discard = screen_engine.screen(lam_dev, state, rule=cfg.rule)
                screen_passes = screen_engine.last_x_passes
                screen_dtype_eff = getattr(screen_engine,
                                           "last_effective_dtype", "float32")
                if hybrid:
                    discard = discard | screen_engine.screen(lam_dev, state,
                                                             rule="strong")
                    screen_passes += screen_engine.last_x_passes
                discard_np = tracing.fetch(discard)
                if batch is None:
                    discard_np = discard_np[None, :]
                # dead queries keep nothing
                discard_np = discard_np | ~live[:, None]
                screen_time = time.perf_counter() - t0

            # ---- reduced solve (+ strong-rule KKT loop) ------------------
            with tracing.span("path.solve"):
                t0 = time.perf_counter()
                kkt_rounds = 0
                solves = gram_solves = gap_checks = member_major = 0
                solver_x_passes = 0.0
                solver_lo_iters = 0
                gather_time = 0.0
                solve_dtype_eff = "float32"
                bucket = 0
                while True:
                    # union of survivors across the batch: one shared buffer
                    kept = np.flatnonzero((~discard_np).any(axis=0))
                    bucket = min(next_pow2(max(kept.size, bucket_min)), units)
                    if kept.size == 0:
                        beta_full = jnp.zeros((B, p), dtype=X.dtype)
                        fitted = jnp.zeros((B, X.shape[0]), dtype=X.dtype)
                        beta_r = None        # β = 0: betas already holds it
                        res_iters, res_gap, q_conv = 0, 0.0, B
                        conv_vec = np.ones((B,), dtype=bool)
                    else:
                        with tracing.span("path.gather") as gather:
                            col_idx = (kept[:, None] * m
                                       + arange_m).reshape(-1)
                            idx, valid = _pad_indices(col_idx, bucket * m)
                            Xr = _gather_cols(X, idx, valid, bucket * m)
                            if reshard is not None:
                                Xr = reshard(Xr)
                            lo = None
                            if lo_gather is not None:
                                lo = lo_gather(idx, valid, bucket * m)
                                if reshard is not None:
                                    lo = (reshard(lo[0]),) + tuple(lo[1:])
                            if batch is None:
                                beta0 = jnp.take(beta_prev[0], idx) * valid
                            else:
                                # per-query validity on the union buffer: each
                                # query solves exactly its own reduced problem
                                kept_q = np.repeat(~discard_np[:, kept], m,
                                                   axis=1)
                                vq_np = np.zeros((B, bucket * m),
                                                 dtype=np.float32)
                                vq_np[:, : col_idx.size] = kept_q
                                vq = jnp.asarray(vq_np)
                                beta0 = jnp.take(beta_prev, idx, axis=1) * vq
                        gather_time += gather.seconds
                        if batch is None:
                            res = solver_engine.solve(Xr, float(lam_vec[0]),
                                                      beta0, m=m, lo=lo)
                        else:
                            res = solver_engine.solve_batched(
                                Xr, jnp.asarray(lam_vec, X.dtype), beta0,
                                valid=vq, m=m, lo=lo)
                        # β back to p and the fitted values Xr·β_r, from the
                        # reduced bucket (replicated, shard-invariant: they
                        # feed KKT and the next dual state), then ONE read
                        # of the packed summary
                        with tracing.span("path.scatter"):
                            beta_full, fitted, summary = _step_epilogue(
                                res.beta, res.iters, res.gap, res.converged,
                                Xr, idx, valid, p=p)
                        summary = tracing.fetch(summary)
                        beta_r = summary[:, :-3]
                        res_iters = int(summary[:, -3].max())
                        res_gap = float(summary[:, -2].max())
                        conv_vec = summary[:, -1] > 0
                        q_conv = int(conv_vec.sum())
                        solves += 1
                        gram_solves += int(solver_engine.last_used_gram)
                        member_major += int(solver_engine.last_member_major)
                        gap_checks += solver_engine.last_gap_checks
                        solver_x_passes += (solver_engine.last_x_passes
                                            * (bucket * m) / p)
                        solver_lo_iters += getattr(solver_engine,
                                                   "last_lo_iters", 0)
                        solve_dtype_eff = getattr(solver_engine,
                                                  "last_effective_dtype",
                                                  "float32")
                    if not need_kkt:
                        break
                    with tracing.span("path.kkt"):
                        if batch is None:
                            viol = tracing.fetch(kkt_fn(
                                beta_full[0], float(lam_vec[0]),
                                jnp.asarray(discard_np[0]),
                                fitted[0]))[None, :]
                        else:
                            viol = tracing.fetch(kkt_fn(
                                beta_full, jnp.asarray(lam_vec, X.dtype),
                                jnp.asarray(discard_np), fitted))
                    viol = viol & live[:, None]
                    if not viol.any() or kkt_rounds >= cfg.max_kkt_rounds:
                        break
                    kkt_rounds += 1
                    discard_np = discard_np & ~viol
                solve_time = time.perf_counter() - t0

            with tracing.span("path.copyout") as copyout:
                # a host scatter of the kept columns into the float64
                # buffer (f32 → f64 is exact; the rest stays 0)
                if beta_r is not None:
                    betas[:, k, col_idx] = beta_r[:, : col_idx.size]
                masks[:, k] = discard_np
            # a dead (trivial-region) query's lane is vacuously converged
            q_converged &= conv_vec | ~live
            stats.append(PathStepStats(
                lam=(float(lam_vec[0]) if batch is None
                     else float(lam_vec.max())),
                n_discarded=int(discard_np.all(axis=0).sum()),
                n_kept=int(kept.size),
                solver_iters=res_iters, gap=res_gap, kkt_rounds=kkt_rounds,
                screen_time_s=screen_time, solve_time_s=solve_time,
                x_passes=screen_passes,
                gap_checks=gap_checks,
                gram_step_frac=gram_solves / solves if solves else 0.0,
                solver_backend=solver_engine.backend_name,
                screen_backend=screen_engine.backend_name,
                bucket=bucket * m,
                solver_x_passes=solver_x_passes,
                batch_size=B,
                queries_converged=q_conv,
                x_passes_per_query=screen_passes / B,
                screen_dtype_effective=screen_dtype_eff,
                solve_dtype_effective=solve_dtype_eff,
                solver_lo_iters=solver_lo_iters,
                geometry_version=geo_version,
                gather_time_s=gather_time,
                copyout_time_s=copyout.seconds,
                member_major_solves=member_major,
            ))
            if cfg.checkpoint_fn:
                if batch is None:
                    cfg.checkpoint_fn(k, float(lam_vec[0]), betas[0, k])
                else:
                    cfg.checkpoint_fn(k, lam_vec, betas[:, k])

            beta_prev = beta_full
            if cfg.sequential:
                with tracing.span("path.state") as state_span:
                    if batch is None:
                        state = screen_engine.make_state(beta_full[0],
                                                         float(lam_vec[0]),
                                                         fitted=fitted[0])
                    else:
                        state = screen_engine.make_state(
                            beta_full, jnp.asarray(lam_vec, X.dtype),
                            fitted=fitted)
                stats[-1].state_time_s = state_span.seconds
            # basic variants keep `state` pinned at λmax (paper §4.1.1)

    for st, step in zip(stats, steps):
        st.host_syncs, st.host_sync_s = step.host_syncs, step.host_sync_s
        st.compiles, st.step_time_s = step.compiles, step.seconds

    # Unified result: the leading batch axis is ALWAYS present (B = 1 for a
    # single query — the values are bit-identical to the squeezed layout).
    if batch is None:
        lambdas = lambdas[None, :]
    return PathResult(lambdas=lambdas, betas=betas, stats=stats, masks=masks,
                      query_converged=q_converged)


# ---------------------------------------------------------------------------
# Deprecated entry points. Each is a thin shim over ONE front door —
# repro.core.session.LassoSession — kept for source compatibility: a fresh
# session per call reproduces the old behaviour exactly (screen masks
# bit-identical on grid points strictly inside (0, λ_max) — tested in
# tests/test_session.py). Fit-once / query-many callers should hold a
# session instead: docs/api.md#migrating-from-the-old-entry-points.
# ---------------------------------------------------------------------------

def _deprecated(old: str, new: str):
    warnings.warn(
        f"repro.core.{old} is deprecated; use {new} (see docs/api.md)",
        DeprecationWarning, stacklevel=3)


def lasso_path(X, y, lambdas, cfg=None, *, geometry=None) -> PathResult:
    """DEPRECATED shim over :class:`~repro.core.session.LassoSession`.

    Solve the Lasso along a decreasing λ grid with screening. `lambdas`
    must be sorted decreasing and ≤ λmax for sequential rules to be valid
    (the theorems require λ ≤ λ₀). Pass ``geometry`` (a
    :class:`repro.core.engine.DictionaryGeometry`) to reuse a prefitted
    dictionary across many calls — or better, hold a ``LassoSession``.
    Returns the squeezed single-query layout (betas (K, p)).
    """
    from .session import LassoSession
    _deprecated("lasso_path", "LassoSession.fit(X).path(y)")
    sess = LassoSession.fit(X, config=cfg, geometry=geometry)
    return sess.path(jnp.asarray(y), lambdas).squeeze()


def lasso_path_batched(X, Y, lambdas=None, cfg=None, *,
                       num_lambdas: int = 100, lo_frac: float = 0.05,
                       geometry=None) -> PathResult:
    """DEPRECATED shim over :class:`~repro.core.session.LassoSession`.

    Solve B Lasso paths against ONE fitted dictionary, batched end-to-end.
    ``Y`` is (B, n); ``lambdas`` is a (B, K) array of per-query decreasing
    grids, a shared (K,) grid (broadcast), or None — then each query gets
    the paper's grid over its own λ_max. Returns the unified (batched)
    :class:`PathResult`. See ``LassoSession.path`` for the full contract.
    """
    from .session import LassoSession
    _deprecated("lasso_path_batched", "LassoSession.fit(X).path(Y)")
    Y = jnp.asarray(Y)
    assert Y.ndim == 2, "lasso_path_batched needs Y of shape (B, n)"
    sess = LassoSession.fit(X, config=cfg, geometry=geometry)
    return sess.path(Y, lambdas, num_lambdas=num_lambdas, lo_frac=lo_frac)


def group_lasso_path(X, y, m: int, lambdas, cfg=None) -> PathResult:
    """DEPRECATED shim over :class:`~repro.core.session.LassoSession`.

    Group-Lasso along a decreasing grid with group-EDPP screening. Groups
    are contiguous with equal size ``m``; reduction gathers whole groups
    into power-of-two group buckets. Returns the squeezed layout.
    """
    from .session import LassoSession
    _deprecated("group_lasso_path", "LassoSession.fit(X, groups=m).path(y)")
    sess = LassoSession.fit(X, groups=m, config=cfg)
    return sess.path(jnp.asarray(y), lambdas).squeeze()
