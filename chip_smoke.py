"""Smoke test of the served Lasso path on a TPU, at a deployment's size.

The deployment is the paper's MNIST image dictionary: 784 × 50000 f32
columns (≈157 MB on the device), generated from ``--seed`` by
``repro.data.QueryStream``. The script drives what ``repro.launch.serve``
drives, through the same entry points:

* ``LassoSession.fit`` once (rule ``edpp``, strategy ``fista``);
* 16 queries through the continuous-batching ``serve_loop`` at
  ``b_max=8``, each answered with a 16-point λ-path;
* one short group-lasso path on the same dictionary (``groups=10``,
  ``group_fista``).

It then checks, on the chip:

* every served mask is bit-identical to a direct ``session.path`` call;
* for two queries, the masks equal the ``jnp`` backend's under
  ``Precision.HIGHEST``, β is within ``benchmarks.common.beta_err_tol`` of
  an unscreened (``rule="none"``) path computed at ``Precision.HIGHEST``,
  and no discarded feature is nonzero in that reference (the same for the
  group path);
* no serve error, no unconverged query, no warning raised from the
  library, and the ``pallas`` backends for screens and solves.

``--chips 4`` runs only the mesh path instead: the same dictionary fitted
on a 2×2 ``("query", "feature")`` mesh, the same 16 queries served, masks
bit-identical and β within tolerance against a one-device session on
``jax.devices()[0]`` in the same process.

Timings printed are set-up (they include compilation), not speed. The
script needs a TPU: without one it exits 1 and prints no result. The last
line of a passing run is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N, P = 784, 50000              # MNIST dictionary (benchmarks/bench_sequential)
GROUP_SIZE = 10
NUM_QUERIES, B_MAX, NUM_LAMBDAS = 16, 8, 16
LO_FRAC, HI_FRAC = 0.1, 0.95   # serve.py's grid: inside the exactness contract
SOLVER_TOL = 1e-6              # serve.py's f32 default
REF_MAX_ITER = 20000           # the unscreened reference solves all p columns


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the 2x2 mesh path and its comparison")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


class Checks:
    """Collects pass/fail lines so one run reports every phase."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"[{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)
        return ok


def _set_up(label: str, t0: float) -> None:
    print(f"set-up: {label} {time.perf_counter() - t0:.3f}s "
          f"(includes compilation; not a speed)", flush=True)


def _serve(sess, stream, np):
    from repro.launch import serve_loop as sl
    executor = sl.SessionExecutor(sess, num_lambdas=NUM_LAMBDAS,
                                  lo_frac=LO_FRAC, hi_frac=HI_FRAC)
    arrivals = sl.stream_arrivals(stream, NUM_QUERIES, dtype=np.float32)
    policy = sl.ServePolicy(b_max=B_MAX, queue_cap=4 * B_MAX)
    return sl.ServeLoop(arrivals, executor, policy=policy).run()


def _check_served(check, report, backend: str, label: str) -> None:
    s = report.summary()
    print(f"{label}: answered {s['n_ok']}/{s['n_queries']} queries in "
          f"{s['n_dispatches']} batches (padded shapes "
          f"{sorted({r.padded_b for r in report.trace})})", flush=True)
    check(s["n_errors"] == 0, f"{label}: serve errors {s['n_errors']}"
          + "".join(f"; q{t.qid}: {t.error}" for t in report.tickets
                    if not t.ok)[:2000])
    check(s["n_unconverged"] == 0,
          f"{label}: unconverged queries {s['n_unconverged']}")
    used = {(st.screen_backend, st.solver_backend)
            for t in report.ok_tickets for st in t.result.stats
            if st.solver_backend}
    check(used == {(backend, backend)},
          f"{label}: (screen, solver) backends used {sorted(used)}")


def _beta_checks(check, label, y, betas, masks, ref, tol_fn, np):
    """β within tolerance of the unscreened reference, and no discarded
    unit nonzero in it."""
    ref_betas = np.asarray(ref.betas[0])
    err = float(np.max(np.abs(np.asarray(betas) - ref_betas)))
    tol = tol_fn(np.asarray(y, np.float64), SOLVER_TOL)
    check(err <= tol, f"{label}: max|β − β_ref| {err:.3e} ≤ {tol:.3e}")
    units = masks.shape[-1]
    live = np.abs(ref_betas).reshape(ref_betas.shape[0], units, -1) \
        .max(axis=-1) > 0
    bad = int(np.sum(np.asarray(masks) & live))
    check(bad == 0, f"{label}: discarded units nonzero in the reference: "
          f"{bad} (of {int(np.sum(masks))} discards)")
    scale = 0.5 * float(np.dot(np.asarray(y, np.float64),
                               np.asarray(y, np.float64)))
    gaps = [s.gap / scale for s in ref.stats]
    print(f"{label}: reference reached the {SOLVER_TOL:g} relative gap at "
          f"{sum(g <= SOLVER_TOL for g in gaps)}/{len(gaps)} λ (worst "
          f"{max(gaps):.3e}, {REF_MAX_ITER} iterations at most)", flush=True)


def run_one_chip(check, jax, np, seed: int) -> None:
    from benchmarks.common import beta_err_tol
    from repro.core import LassoSession, PathConfig, ScreenSpec, SolveSpec
    from repro.data import QueryStream

    stream = QueryStream(n=N, p=P, batch=B_MAX, seed=seed)
    X = stream.dictionary(dtype=np.float32)
    cfg = PathConfig(screen=ScreenSpec(rule="edpp"),
                     solve=SolveSpec(strategy="fista", tol=SOLVER_TOL))
    t0 = time.perf_counter()
    sess = LassoSession.fit(X, config=cfg)
    sess.geometry.col_norms.block_until_ready()
    _set_up("fit", t0)
    print(f"backends: screen {sess.backend_name}", flush=True)
    check(sess.backend_name == "pallas",
          f"screen backend {sess.backend_name!r} is 'pallas'")

    t0 = time.perf_counter()
    report = _serve(sess, stream, np)
    _set_up("serve loop", t0)
    _check_served(check, report, "pallas", "serve")

    t0 = time.perf_counter()
    same = [np.array_equal(np.asarray(sess.path(t.y, t.result.lambdas)
                                      .masks[0]),
                           np.asarray(t.result.masks))
            for t in report.ok_tickets]
    _set_up("direct paths", t0)
    check(len(same) == NUM_QUERIES and all(same),
          f"served masks bit-identical to direct session.path: "
          f"{sum(same)}/{NUM_QUERIES}")

    jnp_cfg = PathConfig(screen=ScreenSpec(rule="edpp", backend="jnp"),
                         solve=SolveSpec(strategy="fista", tol=SOLVER_TOL,
                                         backend="jnp"))
    none_cfg = PathConfig(screen=ScreenSpec(rule="none", backend="jnp"),
                          solve=SolveSpec(strategy="fista", tol=SOLVER_TOL,
                                          backend="jnp",
                                          max_iter=REF_MAX_ITER))
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        for t in report.ok_tickets[:2]:
            lam = t.result.lambdas
            hi = sess.path(t.y, lam, config=jnp_cfg)
            check(np.array_equal(np.asarray(hi.masks[0]),
                                 np.asarray(t.result.masks)),
                  f"q{t.qid}: masks equal the jnp backend's at HIGHEST")
            ref = sess.path(t.y, lam, config=none_cfg)
            _beta_checks(check, f"q{t.qid}", t.y, t.result.betas,
                         t.result.masks, ref, beta_err_tol, np)
    _set_up("HIGHEST references", t0)

    # ---- group lasso on the same dictionary
    y = np.asarray(report.ok_tickets[0].y)
    gcfg = PathConfig(screen=ScreenSpec(rule="edpp"),
                      solve=SolveSpec(strategy="group_fista", tol=SOLVER_TOL))
    t0 = time.perf_counter()
    gsess = LassoSession.fit(X, groups=GROUP_SIZE, config=gcfg)
    gres = gsess.path(y, num_lambdas=6, lo_frac=0.3, hi_frac=HI_FRAC)
    _set_up("group fit + path", t0)
    gused = {(s.screen_backend, s.solver_backend) for s in gres.stats
             if s.solver_backend}
    print(f"backends: group screen {gsess.backend_name}, "
          f"(screen, solver) used {sorted(gused)}", flush=True)
    check(gsess.backend_name == "pallas" and gused == {("pallas", "pallas")},
          "group path ran the pallas backends")
    check(bool(np.all(gres.query_converged)), "group path converged")
    gnone = PathConfig(screen=ScreenSpec(rule="none", backend="jnp"),
                       solve=SolveSpec(strategy="group_fista",
                                       tol=SOLVER_TOL, backend="jnp",
                                       max_iter=REF_MAX_ITER))
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        gref = gsess.path(y, gres.lambdas[0], config=gnone)
    _set_up("group HIGHEST reference", t0)
    _beta_checks(check, "group", y, gres.betas[0], gres.masks[0], gref,
                 beta_err_tol, np)


def run_four_chips(check, jax, np, seed: int) -> None:
    from benchmarks.common import beta_err_tol
    from repro.core import LassoSession, PathConfig, ScreenSpec, SolveSpec
    from repro.core.distributed import make_mesh
    from repro.data import QueryStream

    stream = QueryStream(n=N, p=P, batch=B_MAX, seed=seed)
    X = stream.dictionary(dtype=np.float32)
    cfg = PathConfig(screen=ScreenSpec(rule="edpp"),
                     solve=SolveSpec(strategy="fista", tol=SOLVER_TOL))
    mesh = make_mesh((2, 2), ("query", "feature"), devices=jax.devices()[:4])
    t0 = time.perf_counter()
    msess = LassoSession.fit(X, mesh=mesh, config=cfg)
    _set_up("mesh fit", t0)
    print(f"backends: mesh screen {msess.backend_name}", flush=True)
    check(msess.backend_name == "shard:pallas",
          f"mesh screen backend {msess.backend_name!r} is 'shard:pallas'")
    t0 = time.perf_counter()
    mrep = _serve(msess, stream, np)
    _set_up("mesh serve loop", t0)
    _check_served(check, mrep, "shard:pallas", "mesh serve")

    t0 = time.perf_counter()
    one = LassoSession.fit(jax.device_put(X, jax.devices()[0]), config=cfg)
    orep = _serve(one, stream, np)
    _set_up("one-device fit + serve loop", t0)
    _check_served(check, orep, "pallas", "one-device serve")

    pairs = list(zip(mrep.ok_tickets, orep.ok_tickets))
    same = [a.qid == b.qid and np.array_equal(np.asarray(a.result.masks),
                                              np.asarray(b.result.masks))
            for a, b in pairs]
    check(len(same) == NUM_QUERIES and all(same),
          f"mesh masks bit-identical to one-device: {sum(same)}/"
          f"{NUM_QUERIES}")
    errs = [(float(np.max(np.abs(np.asarray(a.result.betas)
                                 - np.asarray(b.result.betas)))),
             beta_err_tol(np.asarray(a.y, np.float64), SOLVER_TOL))
            for a, b in pairs]
    worst = max(errs, key=lambda e: e[0] / e[1], default=(np.nan, np.nan))
    check(len(errs) == NUM_QUERIES and all(e <= tol for e, tol in errs),
          f"mesh β within tolerance of one-device (worst {worst[0]:.3e} "
          f"≤ {worst[1]:.3e})")


def main(argv=None) -> int:
    args = _parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: no src/repro next to this script; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    import jax
    import numpy as np
    from repro.launch.cli import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"devices", file=sys.stderr)
        return 1
    use_compile_cache()
    print(f"device_kind {dev.device_kind!r}, {len(devices)} devices, jax "
          f"{jax.__version__}", flush=True)

    check = Checks()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        run = run_four_chips if args.chips == 4 else run_one_chip
        run(check, jax, np, args.seed)
    for w in caught[:10]:
        print(f"warning: {w.filename}:{w.lineno}: {w.message}")
    # the library's fallback warnings point (stacklevel) into this checkout
    ours = [w for w in caught if str(w.filename).startswith(str(ROOT))]
    check(not ours, f"warnings raised from this repository: {len(ours)}")

    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
