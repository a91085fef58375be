"""Lasso path-solving entrypoint (the paper's workload as a service).

    PYTHONPATH=src python -m repro.launch.solve --n 150 --p 3000 \
        --rule edpp --num-lambdas 100 [--group-size 5] [--ckpt-dir DIR]

One :class:`repro.core.LassoSession` is fitted per run (the fused
workspace pass over X happens exactly once) and the path is solved
through ``session.path`` — group mode is just ``fit(..., groups=m)``.
Checkpoints (λ_k, β_k) per grid point; a killed run resumes mid-path.

Precision: f32 by default, as in launch/serve.py. ``--x64`` enables
jax_enable_x64 BEFORE any jax import touches arrays, for float64 paths on
the jnp backends (the compiled pallas kernels refuse float64). Flag wiring
shared with serve.py lives in launch/cli.py.
"""

from __future__ import annotations

import argparse
import time

from . import cli


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    cli.add_problem_args(ap, n=150, p=3000, nnz=60)
    cli.add_engine_args(ap)
    cli.add_mesh_arg(ap)
    cli.add_x64_arg(ap)
    ap.add_argument("--num-lambdas", type=int, default=100)
    ap.add_argument("--group-size", type=int, default=0,
                    help=">0 switches to group Lasso with this group size")
    ap.add_argument("--ckpt-dir", default="")
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse_args(argv)
    cli.setup_jax(args)

    import jax.numpy as jnp  # noqa: E402

    from repro.checkpoint import save  # noqa: E402
    from repro.core import LassoSession  # noqa: E402
    from repro.data import group_lasso_problem, lasso_problem  # noqa: E402

    groups = args.group_size if args.group_size > 0 else None
    dtype = "float64" if args.x64 else "float32"
    ckpt_fn = None
    if args.ckpt_dir:                  # group and plain paths both resume
        def ckpt_fn(k, lam, beta):
            save(args.ckpt_dir, k,
                 {"beta": jnp.asarray(beta)}, extra={"lam": lam})
    if groups:
        m = args.group_size
        X, y, _ = group_lasso_problem(args.n, args.p, m,
                                      active_groups=args.nnz // m + 1,
                                      dtype=dtype)
        if args.solver == "fista":     # the plain-Lasso default
            args.solver = "group_fista"
        elif not args.solver.startswith("group"):
            # a plain-l1 strategy would minimise the wrong objective under
            # the group penalty (and group-EDPP's safety assumes the l2,1
            # solution) — refuse rather than silently mis-solve
            raise SystemExit(
                f"--group-size needs a group solver strategy "
                f"(got {args.solver!r}); use group_fista or a registered "
                f"group_* strategy")
    else:
        X, y, _ = lasso_problem(args.n, args.p, nnz=args.nnz,
                                corr=args.corr, dtype=dtype)

    # the library's 1e-8 relative gap is below f32 resolution (2⁻²³ ≈
    # 1.2e-7): f32 stops at serve.py's 1e-6, float64 keeps 1e-8
    cfg = cli.path_config(args, solver_tol=None if args.x64 else 1e-6,
                          checkpoint_fn=ckpt_fn)
    sess = LassoSession.fit(X, groups=groups, mesh=cli.make_mesh(args),
                            config=cfg)

    t0 = time.perf_counter()
    res = sess.path(y, num_lambdas=args.num_lambdas).squeeze()
    dt = time.perf_counter() - t0
    lmax = float(res.lambdas[0])      # grid starts at λ_max (hi_frac=1)

    print(f"rule={args.rule} solver={cfg.solve.resolved_strategy(sess.groups)} "
          f"grid={args.num_lambdas} λmax={lmax:.3f}")
    print(f"path time {dt:.2f}s (screen {res.total_screen_time:.3f}s); "
          f"dictionary fitted once (fused passes: {sess.fit_passes})")
    if cfg.solve.solve_dtype != "float32":
        lo = sum(s.solver_lo_iters for s in res.stats)
        it = sum(s.solver_iters for s in res.stats)
        eff = next((s.solve_dtype_effective for s in res.stats
                    if s.solver_iters > 0), "float32")
        print(f"solve dtype {cfg.solve.solve_dtype} (effective {eff}): "
              f"{lo}/{it} iterations on the low-precision stream")
    K = len(res.lambdas)
    for k in range(0, K, max(K // 10, 1)):
        s = res.stats[k]
        print(f"  λ/λmax={s.lam/lmax:5.2f} discarded={s.n_discarded:7d} "
              f"kept={s.n_kept:6d} iters={s.solver_iters}")


if __name__ == "__main__":
    main()
