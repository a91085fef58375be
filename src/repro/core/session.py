"""LassoSession: ONE front door for every Lasso path workload.

The paper's geometry splits cleanly into a **fit-once** part (everything
that depends on the dictionary X alone: ‖x_j‖², the column norms, the
group spectral norms, the Lipschitz machinery) and a **query-many** part
(|Xᵀy|, λ_max, the dual trajectory of one response vector). PR 3 built
that split internally (:class:`~repro.core.engine.DictionaryGeometry` +
batched workspaces) but the public API still exposed five parallel entry
points (``lasso_path``, ``lasso_path_batched``, ``group_lasso_path``, the
``dist_*`` suite, serve's hand-wiring) with twin configs that each re-fit
and re-plumb that state. This module is the redesign:

    sess = LassoSession.fit(X, config=PathConfig(
        screen=ScreenSpec(rule="edpp"),
        solve=SolveSpec(strategy="fista", tol=1e-8)))
    res  = sess.path(y)         # (n,)   -> a batch of one, B = 1
    res  = sess.path(Y)         # (B, n) -> B queries in one path run
    one  = res.squeeze()        # drop the batch axis of a B = 1 result

Every query runs one shape, (B, ·): ``path`` promotes a single y (n,) to
(1, n) at its edge, and every layer below it sees a batch. Dispatch is
purely structural — ``fit(..., groups=m)`` picks the group engines,
``fit(..., mesh=mesh)`` places the dictionary column-sharded over the
mesh's feature axes (a 2D ``Mesh(('query', 'feature'))`` additionally
shards query batches) and
resolves the screen backend to the PER-SHARD dispatcher
:func:`repro.core.distributed.sharded_backend` — the same Pallas/jnp tile
kernels as the single-chip engines, run on each local block under
``shard_map`` (``session.backend_name == "shard:<tile>"``). Reduced solves
run the tile's solver kernels whole on every device, on replicated
gathered buckets (solver backend ``"shard:<tile>"`` too), so mesh masks
are bit-identical to the unsharded engine's (docs/distributed.md).
Group mesh sessions remain GSPMD + ``jnp`` (partial support: any other
backend raises). Every call returns the same unified
:class:`~repro.core.path.PathResult` with a leading batch axis.

The session owns, across every ``path`` call:

  * the fitted dictionary geometry per backend (the fused workspace pass
    over X runs EXACTLY once per session — ``session.fit_passes``;
    per-query attach is one matvec pass, ``geometry.query_passes``);
  * the resolved screen/solver backends;
  * the per-bucket Lipschitz eigenpair cache shared by every
    :class:`~repro.core.solver.SolverEngine` the session builds (the kept
    sets drift slowly between queries of one dictionary, so cached
    eigenvectors stay excellent warm starts);
  * the optional mesh placement.

Configs are declarative specs on the problem object (the hybrid
safe-strong framing of Zeng et al. 2017; the GAP-safe rules of Fercoq et
al. 2015 are one ``ScreenSpec(rule="gap")`` away): :class:`ScreenSpec`
(rule + backend + the hybrid strong-rule toggle) and :class:`SolveSpec`
(strategy + backend + tol/cadence) compose into ONE :class:`PathConfig`,
validated at construction. The old flat keyword form
(``PathConfig(rule="edpp", solver_tol=1e-9)``) keeps working — legacy
names route into the specs — and ``GroupPathConfig`` is a deprecated
factory for group defaults. The old entry points live on as deprecation
shims in :mod:`repro.core.path` that build a session internally and
reproduce the old masks bit-for-bit. See docs/api.md.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from . import screening as scr
from . import tracing
from .engine import (
    DictionaryGeometry,
    GroupDictionaryGeometry,
    GroupScreeningEngine,
    ScreeningEngine,
    resolve_backend,
)
from .path import (
    PathResult,
    _group_kkt_violations,
    _kkt_violations,
    _path_driver,
    lambda_grid,
)
from .solver import SOLVERS, SolverEngine, resolve_solver_backend

# Every rule the engines dispatch (core/screening.py RULES + the non-sphere
# tests). The group engine supports the {edpp, strong, none} subset.
KNOWN_RULES = tuple(scr.RULES) + ("safe", "dome", "none")
GROUP_RULES = ("edpp", "strong", "none")
LASSO_ONLY_SOLVERS = ("cd",)


def _check_group_rule(cfg: "PathConfig") -> None:
    """The group engine implements only the GROUP_RULES subset; anything
    else would silently run group-EDPP under the wrong name. A solver of
    the plain Lasso would solve another problem than the one screened."""
    if cfg.screen.rule not in GROUP_RULES:
        raise ValueError(
            f"group sessions support rules {GROUP_RULES}, got "
            f"{cfg.screen.rule!r}")
    if cfg.solve.strategy in LASSO_ONLY_SOLVERS:
        raise ValueError(
            f"group sessions solve the group Lasso; the {cfg.solve.strategy!r}"
            f" strategy solves the plain Lasso")
    if cfg.screen.screen_dtype != "float32":
        # the group kernel's ‖X_gᵀc‖ score has no margin bound yet, so a
        # silent bf16 run could mis-discard — fail loudly instead
        raise ValueError(
            "group sessions support screen_dtype='float32' only, got "
            f"{cfg.screen.screen_dtype!r}")


def _check_backend(name, what: str) -> None:
    if name is None or isinstance(name, ops.ScreenBackend):
        return
    if name not in ops.BACKENDS:
        raise ValueError(
            f"unknown {what} backend {name!r}; available: "
            f"{tuple(ops.BACKENDS)}")


@dataclasses.dataclass(frozen=True)
class ScreenSpec:
    """Declarative screening choice: which rule, where it runs, how it is
    backstopped. Validated at construction.

    ``strong=True`` turns on the **hybrid safe+strong** screen (Zeng et
    al. 2017): the heuristic strong-rule discards are OR-ed into the safe
    rule's each step (one extra streaming pass over X) and the KKT
    violation loop is forced on as the exactness backstop — tighter
    screening deep in the path without giving up the safe contract.
    """

    rule: str = "edpp"            # edpp|dpp|imp1|imp2|seq_safe|gap|*_cut|safe|dome|strong|none
    backend: str | ops.ScreenBackend | None = None  # None = auto-detect
    sequential: bool = True       # False = "basic" variants (state at λmax)
    strong: bool = False          # hybrid safe+strong toggle (see above)
    eps: float = scr.EPS_DEFAULT
    paranoid: bool = False        # run the KKT loop even for safe rules
    kkt_tol: float = 1e-4
    max_kkt_rounds: int = 10
    # dtype of the X copy the screening passes stream: "bfloat16" halves the
    # HBM bytes per screen while the margin-aware fallback keeps the masks
    # bit-identical to float32 (docs/kernels.md). The solve path is
    # untouched either way.
    screen_dtype: str = "float32"

    def __post_init__(self):
        if self.rule not in KNOWN_RULES:
            raise ValueError(f"unknown screening rule {self.rule!r}; "
                             f"available: {KNOWN_RULES}")
        _check_backend(self.backend, "screening")
        if self.eps < 0:
            raise ValueError(f"eps must be ≥ 0, got {self.eps}")
        if self.kkt_tol <= 0:
            raise ValueError(f"kkt_tol must be > 0, got {self.kkt_tol}")
        if self.max_kkt_rounds < 0:
            raise ValueError("max_kkt_rounds must be ≥ 0")
        if self.screen_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"screen_dtype must be 'float32' or 'bfloat16', got "
                f"{self.screen_dtype!r}")


@dataclasses.dataclass(frozen=True)
class SolveSpec:
    """Declarative solver choice for the reduced problems. Validated at
    construction against the live ``SOLVERS`` registry.

    ``strategy=None`` resolves per problem: ``fista`` for the Lasso,
    ``group_fista`` when the session is fitted with ``groups=m`` (where
    ``fista`` also means ``group_fista``, and ``cd``, a plain-Lasso
    solver, is refused).
    ``bucket_min=None`` resolves to 32 features / 16 groups.

    ``solve_dtype="bfloat16"`` streams the FISTA iteration matvecs through
    the session's cached bf16 dictionary copy (shared with the bf16 screen
    path — fitted once) while every duality-gap certificate and the final
    polish stay f32, so ``beta_err_tol`` and the KKT backstop are
    unchanged (docs/solvers.md#mixed-precision-solves). Strategies without
    a certified low-precision phase warn once and solve in f32.
    """

    strategy: str | None = None
    backend: str | ops.ScreenBackend | None = None  # None = auto-detect
    tol: float = 1e-8             # relative duality-gap stop
    max_iter: int = 5000
    gap_check_cadence: int = 10   # duality-gap check every k iterations
    bucket_min: int | None = None
    solve_dtype: str = "float32"  # dtype of the solver's X iteration stream

    def __post_init__(self):
        if self.strategy is not None and self.strategy not in SOLVERS:
            raise ValueError(f"unknown solver strategy {self.strategy!r}; "
                             f"available: {tuple(SOLVERS)}")
        _check_backend(self.backend, "solver")
        if not self.tol > 0:
            raise ValueError(f"tol must be > 0, got {self.tol}")
        if self.max_iter < 1:
            raise ValueError("max_iter must be ≥ 1")
        if self.gap_check_cadence < 1:
            raise ValueError("gap_check_cadence must be ≥ 1")
        if self.bucket_min is not None and self.bucket_min < 1:
            raise ValueError("bucket_min must be ≥ 1")
        if self.solve_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"solve_dtype must be 'float32' or 'bfloat16', got "
                f"{self.solve_dtype!r}")

    def resolved_strategy(self, m: int = 1) -> str:
        if m > 1 and self.strategy in (None, "fista"):
            # block FISTA is FISTA's group form: `fista` soft-thresholds
            # each feature and would solve the plain Lasso
            return "group_fista"
        return self.strategy or "fista"


# Legacy flat keyword → (spec field) routing. The old PathConfig and
# GroupPathConfig fields all keep working as keyword arguments.
_SCREEN_KW = {
    "rule": "rule", "backend": "backend", "sequential": "sequential",
    "eps": "eps", "paranoid": "paranoid", "kkt_tol": "kkt_tol",
    "max_kkt_rounds": "max_kkt_rounds", "hybrid_strong": "strong",
    "screen_dtype": "screen_dtype",
}
_SOLVE_KW = {
    "solver": "strategy", "solver_backend": "backend", "solver_tol": "tol",
    "max_iter": "max_iter", "gap_check_cadence": "gap_check_cadence",
    "bucket_min": "bucket_min", "solve_dtype": "solve_dtype",
}


@dataclasses.dataclass(frozen=True, init=False)
class PathConfig:
    """THE path configuration: a :class:`ScreenSpec` + a :class:`SolveSpec`
    (+ an optional per-step checkpoint hook), validated at construction.

    Two equivalent spellings::

        PathConfig(screen=ScreenSpec(rule="edpp", backend="pallas"),
                   solve=SolveSpec(strategy="cd", tol=1e-9))
        PathConfig(rule="edpp", backend="pallas", solver="cd",
                   solver_tol=1e-9)                  # legacy flat keywords

    The flat keywords are the old ``PathConfig``/``GroupPathConfig``
    fields; they route into the specs (``solver``→``solve.strategy``,
    ``solver_tol``→``solve.tol``, ``hybrid_strong``→``screen.strong``, …)
    and read back through properties, so existing call sites keep working
    unchanged. Group paths need no twin config any more — group defaults
    (``group_fista``, group buckets) resolve from the session's
    ``groups=m`` at fit time.
    """

    screen: ScreenSpec
    solve: SolveSpec
    checkpoint_fn: Callable | None  # called with (k, lam, beta) per step

    def __init__(self, screen: ScreenSpec | None = None,
                 solve: SolveSpec | None = None,
                 checkpoint_fn: Callable | None = None, **legacy):
        screen = screen if screen is not None else ScreenSpec()
        solve = solve if solve is not None else SolveSpec()
        if not isinstance(screen, ScreenSpec):
            raise TypeError(f"screen must be a ScreenSpec, got {screen!r}")
        if not isinstance(solve, SolveSpec):
            raise TypeError(f"solve must be a SolveSpec, got {solve!r}")
        s_kw = {}
        v_kw = {}
        for k, v in legacy.items():
            if k in _SCREEN_KW:
                s_kw[_SCREEN_KW[k]] = v
            elif k in _SOLVE_KW:
                v_kw[_SOLVE_KW[k]] = v
            else:
                raise TypeError(f"PathConfig got an unknown field {k!r}")
        if s_kw:
            screen = dataclasses.replace(screen, **s_kw)
        if v_kw:
            solve = dataclasses.replace(solve, **v_kw)
        object.__setattr__(self, "screen", screen)
        object.__setattr__(self, "solve", solve)
        object.__setattr__(self, "checkpoint_fn", checkpoint_fn)

    # ---- legacy flat accessors (the path driver and old call sites) -----
    @property
    def rule(self) -> str:
        return self.screen.rule

    @property
    def backend(self):
        return self.screen.backend

    @property
    def sequential(self) -> bool:
        return self.screen.sequential

    @property
    def hybrid_strong(self) -> bool:
        return self.screen.strong

    @property
    def eps(self) -> float:
        return self.screen.eps

    @property
    def paranoid(self) -> bool:
        return self.screen.paranoid

    @property
    def kkt_tol(self) -> float:
        return self.screen.kkt_tol

    @property
    def max_kkt_rounds(self) -> int:
        return self.screen.max_kkt_rounds

    @property
    def screen_dtype(self) -> str:
        return self.screen.screen_dtype

    @property
    def solver(self) -> str:
        return self.solve.strategy or "fista"

    @property
    def solver_backend(self):
        return self.solve.backend

    @property
    def solver_tol(self) -> float:
        return self.solve.tol

    @property
    def max_iter(self) -> int:
        return self.solve.max_iter

    @property
    def gap_check_cadence(self) -> int:
        return self.solve.gap_check_cadence

    @property
    def bucket_min(self) -> int | None:
        return self.solve.bucket_min

    @property
    def solve_dtype(self) -> str:
        return self.solve.solve_dtype


def GroupPathConfig(**kw) -> PathConfig:
    """DEPRECATED: the group twin config folded into :class:`PathConfig`.

    Returns a PathConfig with the old group defaults
    (``solver="group_fista"``, ``bucket_min=16`` groups). New code should
    pass a plain PathConfig to ``LassoSession.fit(X, groups=m)`` — group
    defaults resolve from ``groups`` automatically.
    """
    warnings.warn(
        "repro.core.GroupPathConfig is deprecated; use PathConfig with "
        "LassoSession.fit(X, groups=m) (see docs/api.md)",
        DeprecationWarning, stacklevel=2)
    kw.setdefault("solver", "group_fista")
    kw.setdefault("bucket_min", 16)
    return PathConfig(**kw)


def _full_precision(entry):
    """Run a session entry point with every f32 matmul at full precision.

    On TPU the default f32 matmul is one bf16 pass (≈2⁻⁹ relative error on
    a batched dot, measured on a v5e). That breaks the two things the path
    rests on: screens decide at 1 − 1e-6 (their dots, the dual point θ
    built from Xβ) and FISTA stalls far above a 1e-6 relative gap when its
    gradient carries that error. Every jitted program the session traces —
    XLA dots and the Pallas kernel bodies alike — is traced under
    ``HIGHEST``; on CPU this changes nothing."""
    @functools.wraps(entry)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return entry(*args, **kwargs)
    return wrapped


class LassoSession:
    """A fitted dictionary + resolved engine choices; query it many times.

    Construct with :meth:`fit` (the ``__init__`` is not public API)::

        sess = LassoSession.fit(X)                  # fused fit pass, ONCE
        res  = sess.path(y, lambdas)                # one query: B = 1
        res  = sess.path(Y)                         # (B, n): B queries
        grp  = LassoSession.fit(X, groups=m)        # group Lasso
        dist = LassoSession.fit(X, mesh=mesh)       # column-sharded X

    Every result is the unified :class:`~repro.core.path.PathResult` with
    a leading batch axis (``squeeze()`` for B = 1). ``path`` accepts a
    per-call ``config=`` override — geometry and the Lipschitz cache stay
    shared, so A/B-ing rules or solvers against one fitted dictionary is
    free of re-fits (what benchmarks/common.py does).
    """

    def __init__(self, *a, **k):
        raise TypeError("LassoSession is constructed with "
                        "LassoSession.fit(X, ...)")

    # ------------------------------------------------------------------ fit
    @classmethod
    @_full_precision
    def fit(cls, X, *, groups: int | None = None, mesh=None,
            config: PathConfig | None = None,
            geometry=None) -> "LassoSession":
        """Fit the dictionary side of the problem, once.

        ``groups=m`` switches every subsequent ``path`` call to the group
        drivers (contiguous groups of size m). ``mesh`` places X
        column-sharded over the mesh's feature axes (batched queries shard
        over a ``query`` axis when present) and resolves the configured
        screen backend per-shard (``sharded_backend``; explicit
        ``backend="pallas"`` etc. is honoured, not silently downgraded).
        Group mesh sessions are the remaining partial-support case: they
        run GSPMD with ``jnp`` and raise on any other explicit backend.
        Pass ``geometry`` (a prefitted :class:`DictionaryGeometry`) to
        adopt an existing fit instead of running one.
        """
        cfg = config if config is not None else PathConfig()
        if not isinstance(cfg, PathConfig):
            raise TypeError(
                f"config must be a PathConfig, got {type(cfg).__name__} "
                "(the old GroupPathConfig is now a PathConfig factory)")
        m = 1 if groups is None else int(groups)
        if m < 1:
            raise ValueError(f"groups must be ≥ 1, got {groups}")
        if m > 1:
            _check_group_rule(cfg)
        if mesh is not None and geometry is not None:
            raise ValueError(
                "mesh= and geometry= cannot be combined: an adopted "
                "geometry was fitted off-mesh, so its X would silently "
                "bypass the column-sharded placement")

        if mesh is not None:
            from . import distributed as dist
            mesh = dist.auto_mesh(mesh)
        self = object.__new__(cls)
        self.config = cfg
        self.groups = m
        self.mesh = mesh
        self._shard_backends: dict[str, ops.ScreenBackend] = {}
        if mesh is not None:
            if m > 1:
                # partial support: no sharded group kernel yet — the group
                # path stays GSPMD+jnp, and anything else must fail loudly
                # rather than silently downgrade
                for what, b in (("screening", cfg.screen.backend),
                                ("solver", cfg.solve.backend)):
                    name = b.name if isinstance(b, ops.ScreenBackend) else b
                    if name is not None and name != "jnp":
                        raise ValueError(
                            f"group mesh sessions run GSPMD with the jnp "
                            f"backend (sharded group kernels are not "
                            f"supported yet); got {what} backend {name!r}")
            X = dist.place_dictionary(mesh, X)
        self.X = jnp.asarray(X)
        if self.X.ndim != 2:
            raise ValueError(f"X must be (n, p), got shape {self.X.shape}")
        if self.X.shape[1] % m:
            raise ValueError(f"p={self.X.shape[1]} is not divisible by "
                             f"groups={m}")
        self._geometries: dict[str, object] = {}
        self._eig_cache: dict[int, object] = {}
        self._eig_stats = {"warm": 0, "cold": 0}
        self._version = 0
        if geometry is not None:
            if m > 1:
                raise ValueError("geometry= adoption is for the plain "
                                 "Lasso (groups=None)")
            self.X = geometry.X
            self._geometries[geometry.backend.name] = geometry
            self._default_backend = geometry.backend.name
            self._version = int(getattr(geometry, "version", 0))
        else:
            self._default_backend = self._backend_name(cfg.screen.backend)
            self._geometry(self._default_backend)   # the one fused fit pass
        return self

    def _resolve_for_session(self, backend) -> ops.ScreenBackend:
        """Resolve a configured backend to the instance this session runs.

        Off-mesh this is plain :func:`resolve_backend`. On a Lasso mesh the
        configured tile backend — including an explicit ``"pallas"`` — is
        wrapped in the per-shard dispatcher
        :func:`repro.core.distributed.sharded_backend` (cached per tile),
        so an explicit choice is honoured rather than silently downgraded.
        Group mesh sessions stay GSPMD + ``jnp`` and raise on anything
        else (per-call overrides included).
        """
        if self.mesh is None or (isinstance(backend, ops.ScreenBackend)
                                 and backend.name.startswith("shard:")):
            return resolve_backend(backend)
        if self.groups > 1:
            inst = resolve_backend(backend or "jnp")
            if inst.name != "jnp":
                raise ValueError(
                    f"group mesh sessions run GSPMD with the jnp backend "
                    f"(sharded group kernels are not supported yet); got "
                    f"backend {inst.name!r}")
            return inst
        from . import distributed as dist
        if isinstance(backend, str) and backend.startswith("shard:"):
            backend = backend[len("shard:"):]
        tile = resolve_backend(backend)
        cached = self._shard_backends.get(tile.name)
        if cached is None:
            cached = dist.sharded_backend(self.mesh, tile)
            self._shard_backends[tile.name] = cached
        return cached

    def _backend_name(self, backend) -> str:
        return self._resolve_for_session(backend).name

    def _geometry(self, backend=None):
        """The fitted geometry for a backend (built on first use, cached)."""
        b = backend if backend is not None else self._default_backend
        inst = self._resolve_for_session(b)
        geom = self._geometries.get(inst.name)
        if geom is None:
            if self.groups > 1:
                geom = GroupDictionaryGeometry(self.X, self.groups, inst)
            else:
                geom = DictionaryGeometry(self.X, inst)
            # a lazily-fitted backend joins at the session's CURRENT
            # dictionary version (self.X is already the edited X)
            geom.version = self._version
            self._geometries[inst.name] = geom
        return geom

    # ---------------------------------------------------------- properties
    @property
    def shape(self) -> tuple[int, int]:
        return self.X.shape

    @property
    def geometry(self):
        """The default-backend fitted geometry (Dictionary- or
        GroupDictionaryGeometry)."""
        return self._geometries[self._default_backend]

    @property
    def backend_name(self) -> str:
        return self._default_backend

    @property
    def fit_passes(self) -> int:
        """Fused workspace passes over X this session has run — exactly one
        per (backend, session), however many ``path`` calls were made."""
        return sum(g.fit_passes for g in self._geometries.values())

    @property
    def query_passes(self) -> int:
        """Cheap per-query |XᵀY| attach passes (one per ``path`` call)."""
        return sum(g.query_passes for g in self._geometries.values())

    @property
    def version(self) -> int:
        """The dictionary version: 0 at ``fit``, +1 per ``update``.

        Recorded per step in ``PathStepStats.geometry_version`` so serve
        traces and benches can attribute results to the dictionary they
        were computed against."""
        return self._version

    @property
    def eig_cache_stats(self) -> dict:
        """Warm/cold Lipschitz power-iteration starts across this
        session's solves (``{"warm": int, "cold": int}``) — the
        accounting that shows eigenpair carry across ``update`` versions
        (warm starts keep hitting after an edit; ``reset_solver_cache``
        forces the next solves cold)."""
        return dict(self._eig_stats)

    # ----------------------------------------------------------------- path
    @_full_precision
    def path(self, Y, lambdas=None, *, num_lambdas: int = 100,
             lo_frac: float = 0.05, hi_frac: float = 1.0,
             config: PathConfig | None = None) -> PathResult:
        """Solve the λ-path(s) for one query or a batch, with screening.

        ``Y`` is one query (n,) or a batch (B, n). This is the only place
        that knows the difference: a single query is promoted to a batch
        of one, (1, n), and runs the same driver as any batch (one fused
        screen over X per grid step for the whole batch). A session fitted
        with ``groups=m`` uses the group engines; a session fitted with
        ``mesh`` runs on the placed (column-sharded) dictionary.

        ``lambdas`` is a decreasing grid — (K,) shared, (B, K) per-query —
        or None for the paper's grid over each query's own λ_max
        (``lambda_grid(λ_max, num_lambdas, lo_frac, hi_frac)``). Returns
        the unified :class:`PathResult`, leading batch axis always present
        (B = 1 for a single query; ``squeeze()`` drops it). For a single
        query ``config.checkpoint_fn`` is called as ``(k, float λ, β (p,))``,
        for a batch as ``(k, λ (B,), β (B, p))``.
        """
        cfg = config if config is not None else self.config
        if not isinstance(cfg, PathConfig):
            raise TypeError(f"config must be a PathConfig, got "
                            f"{type(cfg).__name__}")
        Y = jnp.asarray(Y)
        if Y.ndim not in (1, 2):
            raise ValueError(
                f"queries must be (n,) or (B, n), got shape {Y.shape}")
        if Y.shape[-1] != self.X.shape[0]:
            raise ValueError(
                f"query length {Y.shape[-1]} != dictionary rows "
                f"{self.X.shape[0]}")
        if Y.ndim == 1:
            Y = Y[None, :]
            if cfg.checkpoint_fn is not None:
                cfg = PathConfig(cfg.screen, cfg.solve,
                                 _single_query_checkpoint(cfg.checkpoint_fn))
        if self.mesh is not None:
            from . import distributed as dist
            Y = dist.place_queries(self.mesh, Y)
        grid_kw = dict(num=num_lambdas, lo_frac=lo_frac, hi_frac=hi_frac)
        if self.groups > 1:
            _check_group_rule(cfg)     # per-call overrides validate too
            return self._group_path(Y, lambdas, cfg, grid_kw)
        return self._lasso_path(Y, lambdas, cfg, grid_kw)

    def reset_solver_cache(self) -> None:
        """Drop the warm-started per-bucket Lipschitz eigenpairs.

        ``SolverEngine.lipschitz`` warm-starts power iteration from the
        eigenvector cached for the bucket size and refreshes the cache on
        every solve, so the FISTA step size L — and therefore the solver's
        last-bit iterates — is a function of the session's whole call
        history, not just of the current query. That is fine for serving
        (L is an upper bound either way; solutions agree to solver
        tolerance), but it breaks byte-exact replay: two ``path`` calls
        with identical inputs can differ in the last float, and rules
        whose geometry amplifies solver noise (GAP's ρ = √(2·gap)/λ turns
        an ulp-level β change into ~√ulp of radius) can flip a
        threshold-straddling mask bit between the calls. Call this before
        each run that must be bitwise reproducible — e.g. both arms of a
        precision A/B — so every arm starts from the same deterministic
        cold cache (power iteration is seeded).
        """
        self._eig_cache.clear()

    # ------------------------------------------------------------- update
    @_full_precision
    def update(self, add=None, drop=None, *, workspaces=()):
        """Edit the fitted dictionary in place: drop columns, append new
        ones, keep every cache that stays valid warm.

        Layout (core/update.py): added columns first *recycle* the
        dropped slots in ascending drop order, leftover adds append at
        the end, leftover drops compact the survivors left (``drop``
        indices refer to the CURRENT version's columns). A balanced edit
        (``len(drop) == add.shape[1]``, the churn-workload common case)
        therefore moves no columns at all — every array is patched in
        place over the edited slots only. Per backend-fitted geometry,
        survivors carry their column norms, reduced-precision screen
        copies and quantisation error bounds; only the added block pays
        fresh (n, p_add) passes — see ``DictionaryGeometry.apply_update``.
        The
        per-bucket Lipschitz eigenpairs stay cached as warm power-
        iteration starts (``v0``) for the next solves; λ_max for each
        live workspace in ``workspaces`` recomputes from the touched
        candidates only, rescanning in full only when that query's
        argmax column was dropped.

        Exactness: after ``update`` + ``reset_solver_cache()``, ``path``
        masks are bit-identical to a cold ``fit`` on the edited X and β
        agrees within ``beta_err_tol`` (the oracle-refit contract,
        docs/api.md#incremental-updates). Without the eig-cache reset,
        solutions still agree to solver tolerance — warm Lipschitz
        starts only move last-bit iterates.

        Buffer ownership: the FIRST update copies the fitted arrays (the
        fit-time X may alias a caller-held jax array), so references you
        hold from before it stay valid. Every LATER update **donates**
        the geometry's buffers to the in-place patch — ``session.X`` /
        geometry arrays captured before that update are invalidated
        (reading them raises jax's deleted-array error). Re-read them
        from the session after updating; ``np.asarray`` copies taken
        earlier are unaffected.

        On a mesh session the edited dictionary is re-placed column-
        sharded (``place_dictionary``); the edited column count must
        stay divisible by the mesh's feature-axis size — pad ``add``
        with zero columns to a shard-divisible count if needed (zero
        columns are inert: norm 0, never selected).

        Returns an :class:`~repro.core.update.UpdateReport`.
        """
        from .update import UpdateReport, make_plan, update_workspace
        if self.groups > 1:
            raise NotImplementedError(
                "session.update is plain-Lasso only: group geometries "
                "cache per-group spectral norms that a column edit "
                "invalidates wholesale — refit instead")
        plan, X_add = make_plan(self.X.shape[1], add, drop)
        if X_add is not None and X_add.shape[0] != self.X.shape[0]:
            raise ValueError(
                f"add must have n={self.X.shape[0]} rows, got "
                f"{X_add.shape[0]}")

        place_x = place_col = None
        if self.mesh is not None:
            from . import distributed as dist
            fsize = int(np.prod([self.mesh.shape[a]
                                 for a in dist.feature_axes(self.mesh)],
                                initial=1))
            if plan.p_new % fsize:
                raise ValueError(
                    f"edited p={plan.p_new} is not divisible by the "
                    f"mesh's feature axis size {fsize}; pad add= with "
                    f"zero columns to a shard-divisible count")
            mesh = self.mesh
            place_x = lambda a: jax.device_put(a, dist.x_sharding(mesh))
            place_col = lambda a: jax.device_put(a, dist.beta_sharding(mesh))

        if X_add is not None:
            # ONE host→device transfer shared by every geometry and live
            # workspace (jnp.asarray is a no-op on device arrays)
            X_add = jnp.asarray(X_add, self.geometry.X.dtype)

        for geom in self._geometries.values():
            geom.apply_update(plan, X_add,
                              place_x=place_x, place_col=place_col)
        self._version += 1
        self.X = self.geometry.X

        n_rescans = 0
        ws_list = list(workspaces)
        for ws in ws_list:
            n_rescans += update_workspace(ws, plan, X_add)
        return UpdateReport(
            version=self._version, p=plan.p_new, n_add=plan.n_add,
            n_drop=plan.n_drop,
            geometries_updated=len(self._geometries),
            eig_buckets_carried=len(self._eig_cache),
            workspaces_updated=len(ws_list), argmax_rescans=n_rescans)

    # ------------------------------------------------------------- drivers
    def _solver_engine(self, y, cfg: PathConfig) -> SolverEngine:
        backend = cfg.solve.backend
        if self.mesh is not None:
            from . import distributed as dist
            if self.groups > 1 and backend is None:
                backend = "jnp"
            elif self.groups == 1:
                # the tile's solver kernels, run whole on every device
                # under shard_map (sharded_backend)
                backend = self._resolve_for_session(
                    resolve_solver_backend(backend))
            # Reduced solves run on replicated gathered buckets; keep y
            # off the query sharding so the tiles see whole arrays.
            y = jax.device_put(y, dist.replicated(self.mesh))
        return SolverEngine(
            y, solver=cfg.solve.resolved_strategy(self.groups),
            backend=backend, tol=cfg.solve.tol, max_iter=cfg.solve.max_iter,
            gap_check_cadence=cfg.solve.gap_check_cadence,
            eig_cache=self._eig_cache, eig_stats=self._eig_stats,
            solve_dtype=cfg.solve.solve_dtype)

    def _lo_gather(self, cfg: PathConfig):
        """The driver's ``lo_gather`` hook: reduce the session's cached
        bf16 dictionary copy (the SAME copy the bf16 screen path streams —
        fitted once per geometry) onto a solve bucket, together with the
        per-bucket dot-error and column-norm bounds the solver's certified
        bf16 phase needs. None unless ``solve_dtype="bfloat16"`` on a
        plain (non-group) Lasso session."""
        if cfg.solve.solve_dtype != "bfloat16" or self.groups > 1:
            return None
        geom = self._geometry(cfg.screen.backend)
        X_lo = geom.screen_copy(jnp.bfloat16)
        col_err = geom.screen_err(jnp.bfloat16)
        col_norms = geom.col_norms

        def lo_gather(idx, valid, bucket):
            from .path import _gather_cols
            # valid is {0,1} so the bf16 cast is exact; multiplying in f32
            # would silently promote the gathered bucket back to f32.
            Xr_lo = _gather_cols(X_lo, idx, valid.astype(X_lo.dtype),
                                 bucket)
            err = jnp.max(jnp.take(col_err, idx, mode="clip") * valid)
            cn = jnp.max(jnp.take(col_norms, idx, mode="clip") * valid)
            return Xr_lo, err, cn

        return lo_gather

    def _reshard(self):
        """The bucket placement hook for ``_path_driver``: on a mesh, pin
        every gathered reduced bucket Xr replicated so the per-step fitted
        values Xr·β (and the solver kernels) are mesh-shape independent —
        the root of the bit-identical mask contract. Off-mesh: None."""
        if self.mesh is None:
            return None
        from . import distributed as dist
        rep = dist.replicated(self.mesh)
        return lambda a: jax.device_put(a, rep)

    def _need_kkt(self, cfg: PathConfig) -> bool:
        rule = cfg.screen.rule
        heuristic = (rule in scr.HEURISTIC_RULES if self.groups == 1
                     else rule == "strong")
        hybrid = cfg.screen.strong and rule not in ("strong", "none")
        return heuristic or hybrid or cfg.screen.paranoid

    def _lasso_path(self, Y, lambdas, cfg, grid_kw) -> PathResult:
        with tracing.span("path.prologue"):
            eng = ScreeningEngine(self.X, Y, eps=cfg.screen.eps,
                                  geometry=self._geometry(cfg.screen.backend),
                                  screen_dtype=cfg.screen.screen_dtype)
            lambdas = _batch_grids(lambdas, eng.lam_max, grid_kw)
            solver = self._solver_engine(Y, cfg)
        X = self.X

        def kkt_fn(beta_full, lam, discard, fitted=None):
            return _kkt_violations(X, Y, beta_full, lam, discard,
                                   cfg.screen.kkt_tol, fitted)

        return _path_driver(
            X, Y, lambdas, cfg, m=1, screen_engine=eng,
            solver_engine=solver, need_kkt=self._need_kkt(cfg),
            kkt_fn=kkt_fn, reshard=self._reshard(),
            lo_gather=self._lo_gather(cfg))

    def _group_path(self, Y, lambdas, cfg, grid_kw) -> PathResult:
        """B group paths against one fitted dictionary, batched end to
        end as the Lasso's are: per λ step one group screen over X for the
        whole batch, the kept groups union-bucketed, one block-FISTA solve
        with per-query validity and convergence freezing, per-query KKT
        rounds and trivial regions."""
        m = self.groups
        with tracing.span("path.prologue"):
            eng = GroupScreeningEngine(
                self.X, Y, m, eps=cfg.screen.eps,
                geometry=self._geometry(cfg.screen.backend))
            lambdas = _batch_grids(lambdas, eng.lam_max, grid_kw)
            solver = self._solver_engine(Y, cfg)
        X = self.X

        def kkt_fn(beta_full, lam, discard, fitted=None):
            return _group_kkt_violations(X, Y, beta_full, lam, discard, m,
                                         cfg.screen.kkt_tol, fitted)

        return _path_driver(
            X, Y, lambdas, cfg, m=m, screen_engine=eng,
            solver_engine=solver, need_kkt=self._need_kkt(cfg),
            kkt_fn=kkt_fn, reshard=self._reshard())


def _batch_grids(lambdas, lam_max, grid_kw) -> np.ndarray:
    """The (B, K) grids of a path: the paper's grid over each query's own
    λ_max (B,) when ``lambdas`` is None, a shared (K,) grid broadcast, or
    per-query (B, K) grids as given."""
    if lambdas is None:
        return np.stack([lambda_grid(float(lm), **grid_kw)
                         for lm in lam_max])
    lambdas = np.asarray(lambdas, dtype=np.float64)
    return np.broadcast_to(lambdas, (len(lam_max), lambdas.shape[-1])).copy()


def _single_query_checkpoint(fn):
    """Adapt a checkpoint hook to a single query's call, ``(k, float λ,
    β (p,))``, from the driver's batch of one, ``(k, λ (1,), β (1, p))``."""
    return lambda k, lam, beta: fn(k, float(lam[0]), beta[0])
