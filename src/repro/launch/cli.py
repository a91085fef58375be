"""Shared CLI wiring for the launch drivers (solve.py, serve.py).

The two drivers used to copy-paste the same flag blocks (problem shape,
engine/backend selection, precision, seed). This module is the one place
they are defined:

  * :func:`add_problem_args`   — ``--n --p --nnz --corr --seed``
  * :func:`add_engine_args`    — ``--rule --solver --backend
                                 --solver-backend``
  * :func:`add_x64_arg`        — ``--x64 / --no-x64`` (default off: the
                                 library runs f32, and the compiled
                                 kernels refuse float64)
  * :func:`setup_jax`          — applies the x64 choice BEFORE any jax
                                 import touches arrays and turns on the
                                 compile cache (call it first in ``main``)
  * :func:`use_compile_cache`  — JAX's persistent compilation cache at a
                                 fixed path
  * :func:`path_config`        — a :class:`repro.core.PathConfig` from the
                                 parsed flags (imports repro.core, so only
                                 call it after :func:`setup_jax`)
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path

#: The compile cache's default home: fixed, inside the checkout, and listed
#: in .gitignore. A path that moved between runs would never hit.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def add_problem_args(ap: argparse.ArgumentParser, *, n: int, p: int,
                     nnz: int, corr: float = 0.0, seed: int = 0) -> None:
    """Synthetic problem shape flags (paper §4.1.2 recipe, eq. 74)."""
    ap.add_argument("--n", type=int, default=n)
    ap.add_argument("--p", type=int, default=p)
    ap.add_argument("--nnz", type=int, default=nnz)
    ap.add_argument("--corr", type=float, default=corr)
    ap.add_argument("--seed", type=int, default=seed)


def add_engine_args(ap: argparse.ArgumentParser, *, rule: str = "edpp",
                    solver: str = "fista") -> None:
    """Screen/solve spec flags, shared verbatim by solve and serve."""
    ap.add_argument("--rule", default=rule,
                    help="screening rule (edpp|dpp|gap|gap_cut|edpp_cut|"
                         "strong|none|...; *_cut composes the sphere with "
                         "the λ_max feasibility half-space in the same "
                         "fused pass)")
    ap.add_argument("--solver", default=solver,
                    help="any registered solver strategy (fista|cd|...)")
    ap.add_argument("--backend", default=None,
                    help="screening backend: pallas|interpret|jnp "
                         "(default: auto / REPRO_SCREEN_BACKEND)")
    ap.add_argument("--solver-backend", default=None,
                    help="pallas|interpret|jnp (default: auto / "
                         "REPRO_SOLVER_BACKEND)")
    ap.add_argument("--screen-dtype", choices=("float32", "bfloat16"),
                    default="float32",
                    help="dtype of the X copy the screens stream: bfloat16 "
                         "halves screen HBM bytes for every rule — spheres, "
                         "gap, dome, and the *_cut composites (per-piece "
                         "margins); masks stay bit-identical via the "
                         "margin-aware f32 fallback (solves are untouched)")
    ap.add_argument("--solve-dtype", choices=("float32", "bfloat16"),
                    default="float32",
                    help="dtype of the FISTA iteration matvec stream: "
                         "bfloat16 near-halves solver HBM bytes while every "
                         "duality-gap certificate and the final polish stay "
                         "f32-exact (docs/solvers.md#mixed-precision-solves; "
                         "non-fista solvers fall back to float32)")


def add_serve_args(ap: argparse.ArgumentParser, *, b_max: int = 8,
                   deadline_ms: float = 20.0, queue_cap: int = 64) -> None:
    """Continuous-batching policy flags (launch/serve_loop.ServePolicy).

    ``--batch-size`` is kept as an alias of ``--b-max``: the old fixed
    micro-batch size is exactly the fill target of the new loop.
    """
    ap.add_argument("--b-max", "--batch-size", dest="b_max", type=int,
                    default=b_max,
                    help="fill target B_max: dispatch as soon as this many "
                         "queries are queued (alias --batch-size)")
    ap.add_argument("--deadline-ms", type=float, default=deadline_ms,
                    help="admission deadline: a partial batch dispatches "
                         "once its oldest query has waited this long")
    ap.add_argument("--queue-cap", type=int, default=queue_cap,
                    help="bounded admission queue; a full queue pushes "
                         "back on the arrival source")
    ap.add_argument("--max-in-flight", type=int, default=2,
                    help="pipelined dispatch window (batch k+1 forms while "
                         "batch k computes)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="offered load in queries/sec (0 = every query "
                         "arrives at t=0, the steady-state bench shape)")
    ap.add_argument("--mode", choices=("continuous", "fixed", "compare"),
                    default="continuous",
                    help="continuous batching, the legacy fixed-B server, "
                         "or a timed compare of both (--quick implies "
                         "compare)")


def add_mesh_arg(ap: argparse.ArgumentParser) -> None:
    """``--mesh QxF``: run the session on a 2D (queries × features) mesh.

    Q shards query batches (data parallel), F shards dictionary columns
    (the screens run per-shard tile kernels under shard_map). Q·F must
    not exceed the visible device count; on CPU combine with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to fake
    devices.
    """
    ap.add_argument("--mesh", default=None, metavar="QxF",
                    help="2D device mesh 'QxF' (e.g. 2x4): Q query shards "
                         "× F feature shards (default: no mesh, single "
                         "device)")


def make_mesh(args):
    """The jax Mesh for ``--mesh QxF`` (None when the flag is absent).

    Imports jax — only call after :func:`setup_jax`.
    """
    spec = getattr(args, "mesh", None)
    if spec is None:
        return None
    import jax
    from repro.core import distributed
    try:
        q, f = (int(t) for t in spec.lower().split("x"))
    except ValueError:
        raise SystemExit(f"--mesh expects 'QxF' (e.g. 2x4), got {spec!r}")
    if q < 1 or f < 1:
        raise SystemExit(f"--mesh axes must be ≥ 1, got {spec!r}")
    n_dev = len(jax.devices())
    if q * f > n_dev:
        raise SystemExit(
            f"--mesh {spec} needs {q * f} devices but only {n_dev} are "
            f"visible (on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={q * f})")
    return distributed.make_mesh((q, f), ("query", "feature"))


def add_x64_arg(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--x64", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="float64 paths with the jnp backends (the compiled "
                         "pallas kernels take f32/bf16 only)")


def use_compile_cache() -> None:
    """Keep compiled programs in JAX's persistent cache across processes.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here; otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


def setup_jax(args) -> None:
    """Apply ``--x64`` before any jax array exists and turn on the compile
    cache. Call first in main()."""
    import jax
    jax.config.update("jax_enable_x64", bool(args.x64))
    use_compile_cache()


def path_config(args, *, solver_tol: float | None = None, **extra):
    """Build the session PathConfig from the shared flags.

    Imports repro.core — only call after :func:`setup_jax`. ``extra`` is
    merged as legacy flat keywords (e.g. ``checkpoint_fn=...``).
    """
    from repro.core import PathConfig, ScreenSpec, SolveSpec
    solve_kw = {"strategy": args.solver, "backend": args.solver_backend,
                "solve_dtype": getattr(args, "solve_dtype", "float32")}
    if solver_tol is not None:
        solve_kw["tol"] = solver_tol
    return PathConfig(
        screen=ScreenSpec(rule=args.rule,
                          backend=getattr(args, "backend", None),
                          screen_dtype=getattr(args, "screen_dtype",
                                               "float32")),
        solve=SolveSpec(**solve_kw), **extra)
