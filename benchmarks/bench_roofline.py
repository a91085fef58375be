"""Roofline term reader + the sharded screening A/B bench.

Two entry points:

* :func:`run` (benchmarks/run.py) — one CSV row per completed dry-run
  cell: reads results/dryrun/*.json (produced by repro.launch.dryrun) and
  emits the three roofline terms + dominant bottleneck per (arch, shape,
  mesh). The full analysis with MODEL_FLOPS ratios is assembled into
  EXPERIMENTS.md by tools/make_experiments.py.

* :func:`main` (``python -m benchmarks.bench_roofline --quick``, CI job
  dist-bench-smoke) — the distributed screening A/B on a live device
  mesh:

    - **sharded-jnp**: the open-coded two-pass screen
      (``dist_edpp_screen``: residual psum + a fused-scores pass that
      recomputes ‖x_j‖² every λ step),
    - **sharded-fused**: the backend-routed cached screen
      (``dist_edpp_screen_cached``: residual psum + ONE per-shard
      ``screen_matvec`` pass against cached column norms — the same
      dispatch ``LassoSession.fit(X, mesh=...)`` resolves to).

  Both arms run the explicit ``jnp`` tile so INTERPRET=1 smoke runs stay
  honest about wall-clock (the bench_batched convention), masks are
  asserted bit-identical between the arms AND against the local
  single-device reference, and the fused arm must not lose to the
  open-coded one (the ISSUE 7 acceptance gate). Writes a schema-checked
  ``bench_dist`` section into ``BENCH_dist.json``
  (tools/check_bench_schema.py).

  The same entry point closes with the **mixed-precision solver A/B**
  (ISSUE 9): one reduced FISTA solve, f32 vs ``solve_dtype="bfloat16"``
  (bf16 iteration matvecs, f32 gap certificates + polish —
  docs/solvers.md#mixed-precision-solves). β-parity against the f32 arm is
  asserted to ``beta_err_tol`` and the headline ``bytes_per_solve_iter``
  must come in ≤ 0.6× f32; both arms land in the schema-checked
  ``bench_solve_dtype`` section of ``BENCH_dist.json``.

  On CPU fake the mesh devices first:
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time

from .common import emit

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                           "dryrun")
DIST_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_dist.json")


def run(full: bool = False):
    files = sorted(glob.glob(os.path.join(RESULTS_DIR, "*.json")))
    if not files:
        emit("roofline/none", 0.0, "no-dryrun-results-yet")
        return
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        name = f"roofline/{rec['arch']}/{rec.get('shape')}/{rec.get('mesh')}"
        if rec.get("status") == "skipped":
            emit(name, 0.0, f"skipped:{rec['reason'][:50]}")
            continue
        if rec.get("status") != "ok":
            emit(name, 0.0, f"status={rec.get('status')}")
            continue
        rl = rec["roofline"]
        t_total = max(rl["t_compute_s"], rl["t_memory_s"],
                      rl["t_collective_s"])
        ratio = rec.get("useful_flops_ratio")
        emit(name, t_total * 1e6,
             f"dom={rl['dominant']}"
             f" t_comp={rl['t_compute_s']:.3e}"
             f" t_mem={rl['t_memory_s']:.3e}"
             f" t_coll={rl['t_collective_s']:.3e}"
             f" useful_ratio={ratio if ratio is None else round(ratio, 3)}"
             f" peak_gb={rec['memory']['peak_per_device_gb']:.2f}")


# ---------------------------------------------------------------------------
# The sharded screening A/B (CI: dist-bench-smoke)
# ---------------------------------------------------------------------------

def _time_arm(screen, grid, repeats: int):
    """Best-of-R wall-clock for one full λ sweep (warm-twice first)."""
    for lam in grid:                      # warm: compile + caches
        screen(lam)[0].block_until_ready()
    for lam in grid:
        screen(lam)[0].block_until_ready()
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        for lam in grid:
            screen(lam)[0].block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes (seconds, interpret-safe)")
    ap.add_argument("--mesh", default=None, metavar="QxF",
                    help="2D device mesh 'QxF' (default: 1 x all visible "
                         "devices)")
    ap.add_argument("--backend", default="jnp",
                    help="tile backend for BOTH timed arms (explicit jnp "
                         "by default so INTERPRET=1 smoke runs stay "
                         "honest about wall-clock)")
    ap.add_argument("--num-lambdas", type=int, default=None)
    ap.add_argument("--repeats", type=int, default=3,
                    help="best-of-R timing per arm")
    ap.add_argument("--bench-json", default=DIST_JSON)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import distributed as D

    if args.mesh is not None:
        q, f = (int(t) for t in args.mesh.lower().split("x"))
    else:
        q, f = 1, len(jax.devices())
    mesh = D.make_mesh((q, f), ("query", "feature"))

    n, p = (64, 4096) if args.quick else (256, 1 << 14)
    K = args.num_lambdas or (8 if args.quick else 16)
    rng = np.random.default_rng(5)
    X = rng.standard_normal((n, p)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    print(f"bench_dist: n={n} p={p} K={K} mesh={q}x{f} "
          f"tile={args.backend}")

    Xd, yd = D.shard_problem(mesh, X, y)
    corr = X.T @ y
    istar = int(np.argmax(np.abs(corr)))
    lm = float(np.abs(corr[istar]))
    v1max = jnp.asarray(np.sign(corr[istar]) * X[:, istar])
    beta0 = jax.device_put(jnp.zeros(p, jnp.float32), D.beta_sharding(mesh))
    norms = jax.device_put(jnp.linalg.norm(jnp.asarray(X), axis=0),
                           D.beta_sharding(mesh))
    grid = np.linspace(0.95, 0.1, K) * lm

    # both arms jitted once (λ is a traced scalar — one compile per arm),
    # basic screens from the λ_max state: identical geometry either way
    open_coded = jax.jit(lambda lam: D.dist_edpp_screen(
        mesh, Xd, yd, lam, lm, beta0, lm, v1max,
        backend=args.backend))                          # → (mask, scores)
    fused = jax.jit(lambda lam: D.dist_edpp_screen_cached(
        mesh, Xd, yd, lam, lm, beta0, lm, v1max, norms,
        backend=args.backend))                          # → (scores, mask)

    # -- exactness first: arms agree with each other AND the local oracle
    from repro.core import DualState, edpp_mask
    st = DualState.at_lambda_max(jnp.asarray(X), jnp.asarray(y))
    masks_ok = True
    refs = []
    for lam in grid:
        m_open = np.asarray(open_coded(float(lam))[0])
        m_fused = np.asarray(fused(float(lam))[1])
        ref = np.asarray(edpp_mask(jnp.asarray(X), jnp.asarray(y),
                                   float(lam), st))
        refs.append(ref)
        masks_ok &= np.array_equal(m_open, ref)
        masks_ok &= np.array_equal(m_fused, ref)
    assert masks_ok, "sharded masks diverged from the local reference"

    t_open = _time_arm(lambda lam: open_coded(float(lam)), grid,
                       args.repeats)
    t_fused = _time_arm(lambda lam: (fused(float(lam))[1],), grid,
                        args.repeats)
    speedup = t_open / max(t_fused, 1e-12)
    n_disc = int(np.asarray(fused(float(grid[-1]))[1]).sum())
    print(f"  sharded-jnp (open-coded 2-pass) {t_open * 1e3:8.1f} ms")
    print(f"  sharded-fused (routed, cached)  {t_fused * 1e3:8.1f} ms  "
          f"speedup {speedup:.2f}x  masks identical: {masks_ok}")

    # ISSUE 7 acceptance: the backend-routed cached screen must not lose
    # to the open-coded two-pass screen (it strictly skips one X pass).
    # Both arms run sub-millisecond on the CPU quick config, so allow
    # scheduler jitter: 10% relative + 0.1 ms absolute.
    assert t_fused <= t_open * 1.10 + 1e-4, (t_fused, t_open)

    # -- mixed-precision A/B: the SAME fused sharded screen through the
    # ScreeningEngine, f32 vs bfloat16 screen copy. bf16 halves the bytes
    # each screen streams over the mesh; the margin-aware f32 fallback
    # keeps masks bit-identical to the f32 (and local-oracle) masks
    # (docs/kernels.md).
    from repro.core import ScreeningEngine
    sb = D.sharded_backend(mesh, args.backend)
    arms = {}
    for dtype in ("float32", "bfloat16"):
        eng = ScreeningEngine(Xd, yd[None], backend=sb, screen_dtype=dtype)
        st0 = eng.state_at_lambda_max()

        def sweep():
            return np.stack([np.asarray(eng.screen(
                jnp.asarray([lam], jnp.float32), st0, "edpp"))[0]
                for lam in grid])
        sweep(), sweep()                      # warm: compile + caches
        eng.total_screen_bytes = 0.0
        t0 = time.perf_counter()
        masks_eng = sweep()
        t_eng = time.perf_counter() - t0
        arms[dtype] = (masks_eng, t_eng, eng.total_screen_bytes / len(grid))
    dtype_ok = (np.array_equal(arms["bfloat16"][0], arms["float32"][0])
                and np.array_equal(arms["float32"][0], np.stack(refs)))
    assert dtype_ok, "bfloat16 engine masks diverged from f32/local oracle"
    byte_ratio = arms["bfloat16"][2] / max(arms["float32"][2], 1e-30)
    assert byte_ratio <= 0.55, \
        f"bf16 screen bytes {byte_ratio:.3f}x f32 (want <= 0.55x)"
    print(f"  engine-f32  {arms['float32'][1] * 1e3:8.1f} ms  "
          f"{arms['float32'][2]:.0f} B/screen")
    print(f"  engine-bf16 {arms['bfloat16'][1] * 1e3:8.1f} ms  "
          f"{arms['bfloat16'][2]:.0f} B/screen "
          f"({byte_ratio:.2f}x)  masks identical: {dtype_ok}")

    from .common import beta_err_tol, write_bench_section
    item = np.dtype(np.float32).itemsize
    meta = {"n": n, "p": p, "num_lambdas": K, "mesh": f"{q}x{f}",
            "backend": args.backend, "repeats": args.repeats,
            "quick": bool(args.quick)}
    row_common = {"dataset": f"synthetic n={n} p={p}",
                  "mesh": f"{q}x{f}", "backend": args.backend,
                  "num_lambdas": K, "masks_identical": bool(masks_ok),
                  "n_discarded_last": n_disc, "screen_dtype": "float32"}
    write_bench_section(
        "bench_dist", meta=meta,
        rows=[dict(row_common, arm="sharded_jnp", wall_time_s=t_open,
                   speedup_vs_open_coded=1.0,
                   bytes_per_screen=2.0 * n * p * item),
              dict(row_common, arm="sharded_fused", wall_time_s=t_fused,
                   speedup_vs_open_coded=speedup,
                   bytes_per_screen=float(n) * p * item),
              dict(row_common, arm="engine_fused",
                   masks_identical=bool(dtype_ok),
                   wall_time_s=arms["float32"][1],
                   speedup_vs_open_coded=t_open / max(arms["float32"][1],
                                                      1e-12),
                   bytes_per_screen=arms["float32"][2]),
              dict(row_common, arm="engine_fused",
                   screen_dtype="bfloat16",
                   masks_identical=bool(dtype_ok),
                   wall_time_s=arms["bfloat16"][1],
                   speedup_vs_open_coded=t_open / max(arms["bfloat16"][1],
                                                      1e-12),
                   bytes_per_screen=arms["bfloat16"][2])],
        path=args.bench_json)

    # -- mixed-precision solver A/B: bytes per FISTA iteration, f32 vs the
    # gap-certified bf16 stream. The bf16 arm runs its iteration matvecs
    # (2 HBM passes per iter) off a bf16 copy of the reduced bucket while
    # every duality-gap certificate and the final polish stream f32 X, so
    # convergence and β accuracy are certified by exact arithmetic
    # (docs/solvers.md#mixed-precision-solves). Cadence 20 amortises the
    # f32 certificate cost: per lo block the ratio is
    # (2·20·2 + 2·4)/((2·20 + 2)·4) ≈ 0.52.
    from repro.core.solver import SolverEngine
    ns, ps = (96, 256) if args.quick else (512, 2048)
    tol_s, cadence = 1e-3, 20
    rngs = np.random.default_rng(7)
    Xnp = (rngs.standard_normal((ns, ps)) / np.sqrt(ns)).astype(np.float32)
    # planted-signal response like bench_dpp_family's generator: a pure
    # noise y at this λ has its bf16 gradient noise floor ABOVE tol·scale
    # (the lo phase can only stall), which benchmarks the fallback, not
    # the certified stream
    ws = np.zeros(ps)
    ws[rngs.choice(ps, ps // 8, replace=False)] = rngs.standard_normal(
        ps // 8)
    Xs = jnp.asarray(Xnp)
    ys = jnp.asarray((Xnp @ ws
                      + 0.05 * rngs.standard_normal(ns)).astype(np.float32))
    lam_s = 0.3 * float(jnp.max(jnp.abs(Xs.T @ ys)))
    arms_s = {}
    for dtype in ("float32", "bfloat16"):
        eng = SolverEngine(ys[None], tol=tol_s, gap_check_cadence=cadence,
                           solve_dtype=dtype)
        lam_v = jnp.asarray([lam_s], jnp.float32)
        eng.solve(Xs, lam_v).beta.block_until_ready()    # warm compile
        t0 = time.perf_counter()
        res = eng.solve(Xs, lam_v)
        res.beta.block_until_ready()
        dt = time.perf_counter() - t0
        iters = max(int(res.iters[0]), 1)
        arms_s[dtype] = {
            "beta": np.asarray(res.beta[0]), "iters": iters,
            "lo_iters": eng.last_lo_iters, "wall_time_s": dt,
            "bytes_per_solve_iter": eng.last_solve_bytes / iters,
            "converged": bool(res.converged[0]),
            "effective_dtype": eng.last_effective_dtype,
        }
    per32 = arms_s["float32"]["bytes_per_solve_iter"]
    per16 = arms_s["bfloat16"]["bytes_per_solve_iter"]
    solve_ratio = per16 / max(per32, 1e-30)
    err_tol = beta_err_tol(np.asarray(ys), tol_s)
    beta_err = float(np.abs(arms_s["bfloat16"]["beta"]
                            - arms_s["float32"]["beta"]).max())
    # ISSUE 9 acceptance: β-parity within the solver-precision bound and
    # the headline bytes/iter near-halved
    assert arms_s["float32"]["converged"] and arms_s["bfloat16"]["converged"]
    assert beta_err <= err_tol, (beta_err, err_tol)
    assert solve_ratio <= 0.6, \
        f"bf16 bytes_per_solve_iter {solve_ratio:.3f}x f32 (want <= 0.6x)"
    print(f"  solver-f32  {per32:12.0f} B/iter  "
          f"({arms_s['float32']['iters']} iters)")
    print(f"  solver-bf16 {per16:12.0f} B/iter  "
          f"({arms_s['bfloat16']['iters']} iters, "
          f"{arms_s['bfloat16']['lo_iters']} on the bf16 stream)  "
          f"{solve_ratio:.2f}x  beta_err {beta_err:.2e} <= {err_tol:.2e}")
    solve_rows = [
        {"dataset": f"synthetic n={ns} p={ps}", "solver": "fista",
         "solve_dtype": dtype, "tol": tol_s, "gap_check_cadence": cadence,
         "solve_iters": a["iters"], "lo_iters": a["lo_iters"],
         "bytes_per_solve_iter": a["bytes_per_solve_iter"],
         "byte_ratio_vs_f32": (a["bytes_per_solve_iter"]
                               / max(per32, 1e-30)),
         "max_beta_err": (0.0 if dtype == "float32" else beta_err),
         "beta_err_tol": err_tol, "wall_time_s": a["wall_time_s"],
         "converged": a["converged"],
         "effective_dtype": a["effective_dtype"]}
        for dtype, a in arms_s.items()]
    write_bench_section(
        "bench_solve_dtype",
        meta={"n": ns, "p": ps, "tol": tol_s, "gap_check_cadence": cadence,
              "lam_over_lam_max": 0.3, "quick": bool(args.quick)},
        rows=solve_rows, path=args.bench_json)
    print(f"wrote {args.bench_json}")


if __name__ == "__main__":
    main()
