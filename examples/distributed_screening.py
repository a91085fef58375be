"""Distributed EDPP screening + FISTA on a virtual 8-chip mesh.

Demonstrates the production multi-chip layout (docs/distributed.md) at
two levels:

  1. **The session front door** — ``LassoSession.fit(X, mesh=mesh)`` on a
     2D ``--mesh QxF`` (axes ``('query', 'feature')``) places the
     dictionary column-sharded over the feature axis, shards query
     batches over the query axis, and resolves the screen backend to the
     per-shard tile dispatcher (``session.backend_name ==
     "shard:<tile>"``): each device runs the SAME Pallas/jnp kernels as
     the single-chip engines on its local block, and masks come out
     bit-identical to the unsharded session.
  2. **The explicit shard_map suite** (`repro.core.distributed`) — the
     hand-written collectives the session path is built from: per-shard
     tile screening with zero communication, FISTA with one N-vector
     psum per iteration (chunked-overlap schedule).

The identical code lowers on the 256/512-chip production meshes in the
dry-run (cells lasso-screen-16m / lasso-fista-16m).

    PYTHONPATH=src python examples/distributed_screening.py \
        [--quick] [--mesh 2x4]

``--quick`` shrinks shapes for CI smoke runs (INTERPRET=1 friendly).
"""

import argparse
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import LassoSession, PathConfig
from repro.core import DualState, distributed as D, edpp_mask, lambda_max
from repro.data import lasso_problem


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes for CI smoke runs")
    ap.add_argument("--mesh", default="2x4", metavar="QxF",
                    help="2D device mesh 'QxF': Q query shards × F "
                         "feature shards (default 2x4 on the 8 virtual "
                         "devices)")
    args = ap.parse_args(argv)

    q, f = (int(t) for t in args.mesh.lower().split("x"))
    mesh = D.make_mesh((q, f), ("query", "feature"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    n, p = (128, 1 << 12) if args.quick else (256, 1 << 15)
    fista_iters = 60 if args.quick else 300
    X, y, beta_true = lasso_problem(n, p, nnz=40, sigma=0.1,
                                    dtype=np.float32)

    # ---- level 1: the session front door (per-shard tile kernels) ------
    # f32 serving precision: a 1e-8 relative gap is unreachable in f32 and
    # would burn max_iter per step — demo at the f32-appropriate tolerance
    sess = LassoSession.fit(X, mesh=mesh,
                            config=PathConfig(solver_tol=2e-5, max_iter=600))
    print(f"X: {n}x{p} sharded column-wise → "
          f"{p // f} features/shard; screen backend "
          f"{sess.backend_name} (session fused fit passes: "
          f"{sess.fit_passes})")
    t0 = time.perf_counter()
    res = sess.path(y, num_lambdas=5, lo_frac=0.3)
    t_path = time.perf_counter() - t0
    for s in res.stats:
        print(f"  session path λ={s.lam:7.2f}: discarded {s.n_discarded:6d}"
              f"/{p} kept {s.n_kept:5d} iters {s.solver_iters}")
    print(f"session 5-point path on the mesh: {t_path:.2f}s "
          f"(per-shard tile screens, replicated reduced solves)")

    # the batched front door shards queries over the mesh's query axis
    Yb = np.stack([y] * (2 * q)).astype(np.float32)
    res_b = sess.path(Yb, num_lambdas=3, lo_frac=0.3)
    print(f"batched path B={Yb.shape[0]} (query-sharded over {q} shard"
          f"{'s' if q > 1 else ''}): masks {res_b.masks.shape}")

    # ---- level 2: the explicit shard_map collectives ------------------
    Xd, yd = D.shard_problem(mesh, X, y)
    lmax_d, matvec_d, screen_d, sup_d = D.make_dist_ops(mesh)
    lm = float(lmax_d(Xd, yd))
    print(f"λ_max = {lm:.3f}  (one scalar pmax)")

    corr = X.T @ y
    istar = int(np.argmax(np.abs(corr)))
    v1max = jnp.asarray(np.sign(corr[istar]) * X[:, istar])
    beta0 = jax.device_put(jnp.zeros(p, jnp.float32),
                           D.beta_sharding(mesh))

    # basic (λmax-state) screening is tight near λmax; the sequential rule
    # handles small λ (see quickstart.py for the full-path behaviour)
    lam = 0.8 * lm
    t0 = time.perf_counter()
    mask, scores = D.dist_edpp_screen(mesh, Xd, yd, lam, lm, beta0, lm,
                                      v1max)
    mask.block_until_ready()
    t_screen = time.perf_counter() - t0
    n_disc = int(np.asarray(mask).sum())
    print(f"EDPP at λ={lam:.2f}: discarded {n_disc}/{p} features "
          f"in {t_screen*1e3:.1f} ms (screening is comm-free)")

    # verify against the single-device reference rule
    st = DualState.at_lambda_max(jnp.asarray(X), jnp.asarray(y))
    ref = np.asarray(edpp_mask(jnp.asarray(X), jnp.asarray(y), lam, st))
    assert np.array_equal(np.asarray(mask), ref), "distributed == local"
    print("distributed mask == single-device mask ✓")

    lam = 0.3 * lm                       # solve deeper into the path
    L = D.dist_power_iteration(mesh, Xd) * 1.05
    t0 = time.perf_counter()
    beta = D.dist_fista(mesh, Xd, yd, lam, beta0, L, iters=fista_iters,
                        overlap="chunked")
    beta.block_until_ready()
    print(f"distributed FISTA ({fista_iters} iters, chunked-overlap psum): "
          f"{time.perf_counter()-t0:.2f}s")
    bh = np.asarray(beta)
    print(f"recovered support: {int((np.abs(bh) > 1e-4).sum())} features "
          f"(true: {int((beta_true != 0).sum())})")


if __name__ == "__main__":
    main()
