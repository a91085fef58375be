"""repro.core — the paper's contribution: DPP/EDPP screening for (group) Lasso.

Layering (see docs/screening-rules.md for the rule-by-rule map):

    session.py          LassoSession — THE front door: fit(X) once (owns
                        the DictionaryGeometry, resolved backends, the
                        per-bucket Lipschitz cache, optional mesh
                        placement), then path(y | Y) promotes a single
                        y to a batch of one and runs the one driver for
                        the session's groups and mesh, returning ONE
                        unified PathResult; PathConfig = ScreenSpec +
                        SolveSpec, validated at construction (docs/api.md)
    screening.py        rule geometry — every ball rule as a SphereTest
                        (centre, ρ) constructor + its pure-jnp oracle mask
    engine.py           ScreeningEngine — the ONE entry point every screen
                        goes through: an immutable DictionaryGeometry (X,
                        ‖x_j‖² — query-independent, fitted once) plus a
                        per-query PathWorkspace (|XᵀY|, λ_max, v₁ — one
                        fused kernel pass, batched over B queries), then
                        each per-step screen is one streaming HBM pass over
                        X for the WHOLE batch, dispatched through the
                        kernels.ops.BACKENDS registry
                        (pallas | interpret | jnp)
    solver.py           SolverEngine — the solver twin of the screening
                        engine: fista/cd/group_fista as registered
                        strategies, device-resident while_loop iteration
                        through the fused solver kernels (same BACKENDS
                        registry), gap-check cadence, Gram-CD crossover,
                        per-bucket Lipschitz cache
    path.py             sequential λ-path driver (screen → reduce → solve →
                        KKT re-check): one generic _path_driver consuming
                        both engines over a (B, n) query batch (per-query
                        λ-grids, union bucketing, convergence freezing —
                        docs/serving.md); a single query is B = 1
    distributed.py      shard_map / pjit variants whose per-shard score and
                        solver-update blocks reuse the engines' arithmetic;
                        batched multi-query variants psum (B, N) blocks
    update.py           incremental dictionary edits — session.update(add=,
                        drop=) plans (UpdatePlan), in-place geometry /
                        workspace carry across versions, mask carry-over
                        (docs/api.md#incremental-updates)

Public API:
    LassoSession, PathConfig, ScreenSpec, SolveSpec           (session — THE
                                                               front door)
    PathResult, PathStepStats, lambda_grid                    (results)
    lambda_max, DualState, screen, edpp_mask, dpp_mask, ...   (screening)
    SphereTest, edpp_sphere, gap_mask, make_sphere, ...       (geometry)
    HalfSpaceCut, feasibility_cut, cut_mask, gap_cut_mask     (dual cuts)
    ScreeningEngine, GroupScreeningEngine, PathWorkspace      (engine)
    DictionaryGeometry, GroupDictionaryGeometry               (fitted dict)
    register_backend, available_backends, default_backend     (backends)
    SolverEngine, register_solver, available_solvers          (solver engine)
    fista, cd, group_fista, soft_threshold, SolveResult       (solvers)
    group_lambda_max, group_duality_gap                       (group solver)
    group_screen, group_edpp_mask, GroupDualState             (group screening)
    UpdatePlan, UpdateReport, make_plan, carry_mask,
    update_workspace                                          (incremental
                                                               updates)
    lasso_path, lasso_path_batched, group_lasso_path,
    GroupPathConfig                                           (deprecated
                                                               session shims)
"""

from .lasso import (  # noqa: F401
    duality_gap,
    dual_objective,
    feasible_dual_point,
    gap_from_residual,
    power_iteration,
    primal_objective,
    soft_threshold,
    top_eigenpair,
)
from .solver import (  # noqa: F401
    FistaResult,
    GroupFistaResult,
    SOLVERS,
    SolveResult,
    SolverEngine,
    available_solvers,
    cd,
    default_solver_backend,
    fista,
    group_fista,
    register_solver,
    resolve_solver_backend,
)
from .screening import (  # noqa: F401
    CUT_RULES,
    EPS_DEFAULT,
    HEURISTIC_RULES,
    RULES,
    SAFE_RULES,
    SPHERE_RULES,
    DualState,
    HalfSpaceCut,
    SphereTest,
    cut_from_ray,
    cut_mask,
    dome_mask,
    dpp_mask,
    dpp_sphere,
    edpp_cut_mask,
    edpp_mask,
    edpp_sphere,
    feasibility_cut,
    gap_cut_mask,
    gap_mask,
    gap_sphere,
    halfspace_sup,
    imp1_mask,
    imp1_sphere,
    imp2_mask,
    imp2_sphere,
    kkt_violations,
    lambda_max,
    make_dual_state,
    make_sphere,
    safe_mask,
    safe_sphere,
    screen,
    seq_safe_mask,
    seq_safe_sphere,
    sphere_mask,
    strong_mask,
    v2_perp,
)
from .engine import (  # noqa: F401
    DictionaryGeometry,
    GroupDictionaryGeometry,
    GroupScreeningEngine,
    PathWorkspace,
    ScreeningEngine,
    available_backends,
    block_scores,
    default_backend,
    engine_x_passes,
    oracle_x_passes,
    register_backend,
    resolve_backend,
)
from .group_lasso import (  # noqa: F401
    group_duality_gap,
    group_gap_from_residual,
    group_lambda_max,
    group_primal,
    group_soft_threshold,
)
from .group_screening import (  # noqa: F401
    GroupDualState,
    group_edpp_mask,
    group_kkt_violations,
    group_screen,
    group_spectral_norms,
    group_state_at_lambda_max,
    group_state_from_solution,
    group_strong_mask,
    group_v2_perp,
    make_group_dual_state,
)
from .path import (  # noqa: F401
    PathResult,
    PathStepStats,
    group_lasso_path,
    lambda_grid,
    lasso_path,
    lasso_path_batched,
    next_pow2,
)
from .session import (  # noqa: F401
    GroupPathConfig,
    LassoSession,
    PathConfig,
    ScreenSpec,
    SolveSpec,
)
from .update import (  # noqa: F401
    UpdatePlan,
    UpdateReport,
    carry_mask,
    make_plan,
    update_workspace,
)
