"""Paper Fig. 1 + Table 1 — the DPP family: DPP / Improvement 1 /
Improvement 2 / EDPP. Rejection ratios + speedup on three data sets shaped
like the paper's (Prostate Cancer 132×15154, PIE 1024×11553, MNIST
784×50000), scaled by default for the CPU container.

Real sets are not redistributable offline (DESIGN §9.2): we use synthetic
matrices with matched aspect ratio and dense-response structure (y = dense
mix of many columns, mimicking image-from-dictionary regression, which is
what PIE/MNIST trials do).

Beyond the paper's four rules this bench also A/Bs the two fused-pass
upgrades (docs/screening-rules.md, docs/kernels.md):

  * ``gap`` vs ``gap_cut`` — the λ_max feasibility half-space composed
    with the gap ball. Safety gives cut-discards ⊇ ball-discards per λ;
    the bench asserts the superset AND a strict total improvement.
  * screen f32 vs bfloat16 copy — masks must be bit-identical while the
    per-step screen HBM bytes drop to ≤ 0.55× for the single-dot sphere
    rules (``edpp``) and ≤ 0.6× for the two-dot per-piece-margin rules
    (``gap``, ``gap_cut``, ``dome`` — the stacked bf16 matvec keeps
    ``x_passes == 1`` where the f32 engine needs 2; the narrow f32
    fallback gather is counted in the bytes). The bytes are the
    ``ScreeningEngine``'s own counter (``total_screen_bytes``), read on a
    replay of the path's screens (:func:`engine_screen_bytes`).

Every arm lands in the ``bench_dpp_family`` section of BENCH_solver.json
with a ``rejection_rate`` column (tools/check_bench_schema.py enforces
the row schema).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import ScreeningEngine

from .common import (beta_err_tol, emit, grid_for, ground_truth, run_rule,
                     write_bench_section)

DATASETS_QUICK = {
    "prostate-like": (66, 1500),
    "pie-like": (256, 1200),
    "mnist-like": (196, 1800),
}
DATASETS_FULL = {
    "prostate-like": (132, 15154),
    "pie-like": (1024, 11553),
    "mnist-like": (784, 50000),
}
# one small set for the CI smoke job (INTERPRET=1 makes kernels slow)
DATASETS_SMOKE = {
    "pie-like": (64, 384),
}

RULES = ["dpp", "imp1", "imp2", "edpp", "gap", "gap_cut", "dome"]

# f32 vs bf16 A/B arms: rule → max allowed screen-bytes ratio. edpp
# keeps the single-dot 0.55 bar; the two-dot rules (per-piece margins,
# stacked matvec) get the ISSUE 9 0.6 bar — their f32 baseline already
# needs 2 passes, the bf16 path does everything in 1.
BF16_AB = {"edpp": 0.55, "gap": 0.6, "gap_cut": 0.6, "dome": 0.6}


def make_dataset(n, p, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, p))
    # dense-ish response: a mixture of ~n/2 columns + noise (image-style)
    w = np.zeros(p)
    idx = rng.choice(p, n // 2, replace=False)
    w[idx] = rng.standard_normal(n // 2)
    y = X @ w + 0.05 * rng.standard_normal(n)
    return X, y


def _row(name, rule, dtype, num_lambdas, r):
    return {
        "dataset": name, "rule": rule, "screen_dtype": dtype,
        "num_lambdas": int(num_lambdas),
        "rejection_rate": float(r.rejection.mean()),
        "speedup_vs_unscreened": float(r.speedup),
        "wall_time_s": float(r.path_time_s),
        "max_beta_err": float(r.max_beta_err),
    }


def _emit_rule(name, tag, r):
    # derived is parsed as key=value pairs (tools/make_claims.py), so new
    # keys append safely; speedup= and mean_rej= must keep their meaning
    emit(f"dpp_family/{name}/{tag}", r.path_time_s * 1e6,
         f"speedup={r.speedup:.2f} mean_rej={r.rejection.mean():.4f}"
         f" screen_s={r.screen_time_s:.3f}"
         f" hbm_passes_per_step={r.x_passes_per_step:.2f}"
         f" jnp_hbm_passes={r.jnp_x_passes}")


def engine_screen_bytes(X, y, grid, betas, rule, screen_dtype):
    """HBM bytes the ScreeningEngine streams over the path's screens, by
    its own counter: every λ below λ_max screened from the dual state of
    the step before, built from ``betas`` (the reference path), as the
    sequential driver threads it."""
    eng = ScreeningEngine(jnp.asarray(X, jnp.float32),
                          jnp.asarray(y, jnp.float32),
                          screen_dtype=screen_dtype)
    state = eng.state_at_lambda_max()
    lmax = float(eng.lam_max)
    for k, lam in enumerate(grid):
        if lam >= lmax:
            continue
        eng.screen(float(lam), state, rule=rule)
        state = eng.make_state(jnp.asarray(betas[k], jnp.float32),
                               float(lam))
    return eng.total_screen_bytes


def run(full: bool = False, num_lambdas: int = 100, datasets=None,
        ratio_slack: float = 0.0):
    if datasets is None:
        datasets = DATASETS_FULL if full else DATASETS_QUICK
    rows = []
    json_rows = []
    for name, (n, p) in datasets.items():
        X, y = make_dataset(n, p)
        grid = grid_for(X, y, num=num_lambdas)
        betas_ref, t_ref = ground_truth(X, y, grid)
        emit(f"dpp_family/{name}/solver", t_ref * 1e6, "speedup=1.00")
        # solver-precision bound ~ sqrt(gap/mu), tied to solver_tol
        # (common.beta_err_tol); floor at the seed's 5e-4
        tol = max(5e-4, beta_err_tol(y, 1e-12))
        res = {}
        for rule in RULES:
            r = run_rule(X, y, grid, rule, betas_ref, t_ref)
            # strong is heuristic: borderline features (|x·r|≈λ)
            # re-enter only to solver precision (paper §1 KKT loop)
            assert r.max_beta_err < tol, (rule, r.max_beta_err)
            res[rule] = r
            _emit_rule(name, rule, r)
            json_rows.append(_row(name, rule, "float32", num_lambdas, r))
            rows.append((name, rule, r))

        # --- half-space cut: superset per λ, strictly better in total ----
        m_gap, m_cut = res["gap"].masks, res["gap_cut"].masks
        assert (~m_gap | m_cut).all(), \
            f"{name}: gap_cut dropped a gap discard (safety superset broken)"
        assert int(m_cut.sum()) > int(m_gap.sum()), \
            f"{name}: gap_cut did not strictly improve on gap"

        # --- mixed precision: bit-identical masks at ~half the bytes -----
        for rule, max_ratio in BF16_AB.items():
            rb = run_rule(X, y, grid, rule, betas_ref, t_ref,
                          screen_dtype="bfloat16")
            assert rb.max_beta_err < tol, (f"{rule}-bf16", rb.max_beta_err)
            f32 = res[rule]
            assert np.array_equal(rb.masks, f32.masks), \
                f"{name}/{rule}: bfloat16 masks differ from float32 " \
                "(margin fallback broken)"
            ratio = (engine_screen_bytes(X, y, grid, betas_ref, rule,
                                         "bfloat16")
                     / max(engine_screen_bytes(X, y, grid, betas_ref, rule,
                                               "float32"), 1e-30))
            # ratio_slack covers the smoke set only: the narrow fallback
            # gather is size-bucketed (pow-2 + 3/4 midpoints, floor 8), so
            # at tiny p a ~40-column margin band rounds up to a 48-column
            # bucket — a structural overhead that vanishes at the
            # quick/full shapes, where the strict bars hold.
            bar = max_ratio + ratio_slack
            assert ratio <= bar, \
                f"{name}/{rule}: bf16 screen bytes {ratio:.3f}x f32 " \
                f"(want <= {bar}x)"
            # the stacked bf16 matvec folds both dots into ONE wide pass;
            # the pass counter adds a whole extra pass on any step with a
            # narrow f32 fallback gather (PR 8's convention), so the mean
            # tops out at 2.0 — never a THIRD stream. The bytes ratio above
            # is the bar that proves the fallback stayed narrow.
            assert rb.x_passes_per_step <= 2.0, \
                f"{name}/{rule}: bf16 screen took " \
                f"{rb.x_passes_per_step} passes (want 1 wide + narrow)"
            _emit_rule(name, f"{rule}-bf16", rb)
            print(f"# dpp_family/{name}/{rule}-bf16 screen_bytes_ratio="
                  f"{ratio:.3f} (bar {bar})")
            json_rows.append(_row(name, rule, "bfloat16", num_lambdas, rb))
            rows.append((name, f"{rule}-bf16", rb))

    write_bench_section("bench_dpp_family",
                        {"datasets": {k: list(v) for k, v in
                                      datasets.items()},
                         "num_lambdas": int(num_lambdas)},
                        json_rows)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--full", action="store_true",
                    help="paper-size data sets")
    ap.add_argument("--quick", action="store_true",
                    help="one small data set (the CI smoke config)")
    ap.add_argument("--num-lambdas", type=int, default=None)
    args = ap.parse_args()
    if args.quick:
        run(num_lambdas=args.num_lambdas or 25, datasets=DATASETS_SMOKE,
            ratio_slack=0.1)
    else:
        run(full=args.full, num_lambdas=args.num_lambdas or 100)
