"""Distributed (multi-chip / multi-pod) EDPP screening + Lasso solving.

The paper's motivating regime (§1) is "we may not even be able to load the
data matrix into main memory". On a TPU pod the natural layout is a 2D
``Mesh(('query', 'feature'))``: X ∈ R^{N×p} with columns split over the
feature axes, query batches split over the ``query`` axis, y and all
dual-geometry N-vectors replicated along the feature axes. Then:

  * screening scores  |x_jᵀo| + ρ‖x_j‖   — fully local, zero communication;
  * λ_max / ‖Xᵀr‖_∞                        — one scalar `pmax`;
  * residual  r = y − Xβ                   — one N-vector `psum` per solver
    iteration over the FEATURE axes only (the only recurring collective,
    overlappable — see `dist_fista(..., overlap=True)`).

Multi-query batching shards the batch over the ``query`` axis (when B
divides it; replicated otherwise): features stay column-sharded, and the
recurring collective becomes ONE (B_local, N)-block `psum` per query shard
instead of B separate N-vector psums (`dist_edpp_screen_batched`,
`dist_fista_batched`) — collective launch overhead amortised 1/B. A 1D
mesh without a ``query`` axis keeps the old layout exactly (all axes are
feature axes, queries replicated).

Per-shard tile work dispatches through the SAME ``kernels.ops.BACKENDS``
registry as the single-chip engines: every op takes ``backend=`` ("pallas"
| "interpret" | "jnp" | a ScreenBackend | None = auto) and calls the
resolved backend's ``screen_matvec`` / ``edpp_screen_scores`` /
``fista_step`` on its LOCAL (N, p/shards) block, reducing with the single
psum noted above. ``sharded_backend`` packages that dispatch as a
ScreenBackend (name ``"shard:<tile>"``) that
``LassoSession.fit(X, mesh=...)`` drops into the unsharded engines.

Everything here is written with `shard_map` for explicit collective control
(the GSPMD/pjit auto-sharded version is `pjit_screen`). ``check_vma=False``
throughout: a ``pallas_call`` has no replication rule under shard_map.

Meshes have ``Auto`` axes (:func:`make_mesh`, :func:`auto_mesh`): the
engines index, gather and reduce feature-sharded arrays with plain jnp and
leave the collectives to GSPMD, which an ``Explicit`` axis (the default of
``jax.make_mesh``) refuses.

The same code paths lower on the production meshes of launch/mesh.py —
`launch/dryrun.py` compiles them at (16,16) and (2,16,16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops
from .engine import resolve_backend
from .screening import EPS_DEFAULT
from .solver import resolve_solver_backend

#: Mesh axis carrying data-parallel query batches. Every OTHER axis is a
#: feature (model-parallel) axis — a mesh without this axis is pure
#: feature sharding (the pre-2D layout, still fully supported).
QUERY_AXIS = "query"


def make_mesh(shape, names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (see the module doc)."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``: the same devices and axis names,
    so a mesh built by plain ``jax.make_mesh`` is accepted as well."""
    auto = (AxisType.Auto,) * len(mesh.axis_names)
    if tuple(mesh.axis_types) == auto:
        return mesh
    return Mesh(mesh.devices, mesh.axis_names, axis_types=auto)


def query_axes(mesh: Mesh) -> tuple[str, ...]:
    """The mesh's query (data-parallel) axes: () or (``QUERY_AXIS``,)."""
    return tuple(a for a in mesh.axis_names if a == QUERY_AXIS)


def feature_axes(mesh: Mesh) -> tuple[str, ...]:
    """All non-query mesh axes, flattened into one logical feature axis."""
    return tuple(a for a in mesh.axis_names if a != QUERY_AXIS)


def query_size(mesh: Mesh) -> int:
    """Number of devices along the query axis (1 if the mesh has none)."""
    return int(np.prod([mesh.shape[a] for a in query_axes(mesh)], initial=1))


def _fspec(mesh: Mesh):
    """Feature axes as a PartitionSpec entry (None = replicate when a
    degenerate mesh has only a query axis)."""
    f = feature_axes(mesh)
    return f if f else None


def _qspec(mesh: Mesh, b: int):
    """Query axes as a spec entry for a batch of ``b`` — None (replicate)
    unless the mesh has a query axis that divides b."""
    q = query_axes(mesh)
    return q if q and b % query_size(mesh) == 0 else None


def _psum(x, axes):
    return jax.lax.psum(x, axes) if axes else x


def _pmax(x, axes):
    return jax.lax.pmax(x, axes) if axes else x


def x_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(None, _fspec(mesh)))


def beta_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(_fspec(mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_problem(mesh: Mesh, X, y):
    """Place (X, y) on the mesh: X column-sharded, y replicated."""
    X = jax.device_put(jnp.asarray(X), x_sharding(mesh))
    y = jax.device_put(jnp.asarray(y), replicated(mesh))
    return X, y


def place_dictionary(mesh: Mesh, X):
    """Column-shard a dictionary over the mesh's feature axes.

    The fit-time placement of ``LassoSession.fit(X, mesh=mesh)``: the
    session's engines then dispatch per-shard tile kernels through
    ``sharded_backend`` (screens) and run reduced solves on replicated
    gathered buckets."""
    return jax.device_put(jnp.asarray(X), x_sharding(mesh))


def place_queries(mesh: Mesh, Y):
    """Place a query batch Y (B, n) on the mesh's 2D layout: its leading
    axis shards over the ``query`` axis when B divides it, and replicates
    otherwise (a batch of one included)."""
    Y = jnp.asarray(Y)
    spec = P(_qspec(mesh, Y.shape[0]), None)
    return jax.device_put(Y, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Per-shard backend dispatch: the ops.BACKENDS registry under shard_map
# ---------------------------------------------------------------------------

def sharded_backend(mesh: Mesh, tile=None) -> ops.ScreenBackend:
    """A :class:`~repro.kernels.ops.ScreenBackend` that runs ``tile``'s
    kernels per feature shard under ``shard_map``.

    The screening ops (``matvec``, ``fused_scores``) call the tile
    backend's kernel on the LOCAL (N, p/shards) block — zero communication;
    per-column scores are feature-local, and :func:`kernels.ops.
    resolve_tiles` shrinks the kernel tiles to the local block so a narrow
    shard doesn't pay full-tile padding. Outputs stay feature-sharded
    (batched centres additionally shard over the query axis when B divides
    it). The solver ops run the tile's kernel whole on every device: the
    path driver's reduced buckets are gathered REPLICATED, and a Mosaic
    kernel in a program that spans several devices must sit inside
    ``shard_map`` (the compiler cannot partition it), so they are wrapped
    with replicated specs.

    ``tile`` is a backend name, a ScreenBackend, or None (auto-detect:
    ``REPRO_SCREEN_BACKEND`` → ``INTERPRET=1`` → platform default). The
    result is what ``LassoSession.fit(X, mesh=...)`` resolves its engines
    to — ``session.backend_name == "shard:<tile>"``.

    Mixed precision and cut rules need nothing special here: the engine
    hands this backend a bf16 screen copy / a stacked ``[centre; ĝ]``
    right-hand side exactly as it would a plain f32 centre, the narrow
    f32 fallback's column gather runs on the (feature-sharded) full-
    precision X, and the ``*_cut`` combines are plain O(p) jnp on the
    feature-sharded dots — mask parity across mesh shapes is pinned by
    ``tests/test_distributed.py::test_sharded_bf16_and_cut_mask_parity``.
    """
    tile = resolve_backend(tile)
    f = _fspec(mesh)
    wrapped: dict = {}

    def _shmap(key, fn, in_specs, out_specs):
        w = wrapped.get(key)
        if w is None:
            w = shard_map(fn, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
            wrapped[key] = w
        return w

    def matvec(X, centre):
        centre = jnp.asarray(centre)
        if centre.ndim == 1:
            w = _shmap(("mv", 1), tile.matvec, (P(None, f), P()), P(f))
            return w(X, centre)
        q = _qspec(mesh, centre.shape[0])
        w = _shmap(("mv", 2, q), tile.matvec,
                   (P(None, f), P(q, None)), P(q, f))
        return w(X, centre)

    def fused_scores(X, centre, rho):
        centre = jnp.asarray(centre)
        rho = jnp.asarray(rho)
        if centre.ndim == 1:
            w = _shmap(("fs", 1), tile.fused_scores,
                       (P(None, f), P(), P()), (P(f), P(f)))
            return w(X, centre, rho)
        q = _qspec(mesh, centre.shape[0])
        rho_b = jnp.broadcast_to(rho, centre.shape[:1])
        # sumsq is query-independent — identical on every query shard, so
        # its out_spec mentions only the feature axes (check_vma=False
        # takes the local copy)
        w = _shmap(("fs", 2, q), tile.fused_scores,
                   (P(None, f), P(q, None), P(q)), (P(q, f), P(f)))
        return w(X, centre, rho_b)

    def replicated(fn):
        """``fn`` run whole on every device. Array keywords (``valid=``)
        become operands; the others (``sweeps=``) stay static."""
        if fn is None:
            return None

        def call(*args, **kw):
            ops_kw = {k: v for k, v in kw.items()
                      if isinstance(v, (jax.Array, np.ndarray))}
            static = tuple(sorted((k, v) for k, v in kw.items()
                                  if k not in ops_kw))
            n, names = len(args), tuple(ops_kw)

            def local(*a):
                return fn(*a[:n], **dict(zip(names, a[n:])), **dict(static))

            w = _shmap(("rep", fn, n, names, static), local, P(), P())
            return w(*args, *ops_kw.values())
        return call

    return ops.ScreenBackend(
        name=f"shard:{tile.name}",
        matvec=matvec,
        fused_scores=fused_scores,
        # group shards would have to respect group boundaries — group mesh
        # sessions stay on the GSPMD jnp path (see LassoSession.fit)
        group_scores=tile.group_scores,
        fista_step=replicated(tile.fista_step),
        cd_gram_sweep=replicated(tile.cd_gram_sweep),
        prox_step=replicated(tile.prox_step),
    )


# ---------------------------------------------------------------------------
# shard_map building blocks
# ---------------------------------------------------------------------------

def make_dist_ops(mesh: Mesh, backend=None):
    """Build the distributed op suite for a mesh. Every op is jit-compatible
    and lowers to SPMD with the collectives noted in its docstring.

    ``backend`` routes the per-shard tile work ("pallas" | "interpret" |
    "jnp" | ScreenBackend | None = auto): the local matvec of every
    reduction runs the resolved backend's ``screen_matvec`` kernel on the
    shard's (N, p/shards) block."""
    axes = feature_axes(mesh)
    tile = resolve_backend(backend)
    xspec = P(None, _fspec(mesh))
    bspec = P(_fspec(mesh))
    rspec = P()

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(xspec, rspec), out_specs=rspec,
        check_vma=False,
    )
    def lambda_max_d(Xb, y):
        """λ_max = max_j |x_jᵀy|. Collectives: one scalar pmax."""
        return _pmax(jnp.max(jnp.abs(tile.matvec(Xb, y))), axes)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(xspec, bspec, rspec), out_specs=rspec,
        check_vma=False,
    )
    def matvec_d(Xb, bb, y):
        """r = y − Xβ. Collectives: one N-vector psum."""
        return y - _psum(Xb @ bb, axes)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(xspec, rspec, rspec, rspec), out_specs=(bspec, bspec),
        check_vma=False,
    )
    def screen_scores_d(Xb, centre, rho, eps):
        """EDPP scores + discard mask per local feature block. Zero comms.
        One fused backend pass over the block (edpp_screen_scores) — same
        arithmetic as the engine's single-chip screen."""
        scores, _ = tile.fused_scores(Xb, centre, rho)
        return scores, scores < 1.0 - eps

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(xspec, rspec), out_specs=rspec,
        check_vma=False,
    )
    def sup_corr_d(Xb, r):
        """‖Xᵀr‖_∞ (for λ_max-style reductions and dual scaling)."""
        return _pmax(jnp.max(jnp.abs(tile.matvec(Xb, r))), axes)

    return lambda_max_d, matvec_d, screen_scores_d, sup_corr_d


def dist_edpp_screen(mesh: Mesh, X, y, lam_next, lam_prev, beta_prev,
                     lam_max_val, v1_at_lmax, eps: float = EPS_DEFAULT,
                     backend=None):
    """Full sequential-EDPP screen on the mesh (Corollary 17).

    All the dual geometry (θ, v₁, v₂⊥ — N-vectors) is computed replicated;
    the per-feature test is one local fused ``edpp_screen_scores`` pass of
    the resolved ``backend`` per shard. `v1_at_lmax` is sign(x*ᵀy)x*
    (eq. 17), computed once at path start.

    Returns (discard_mask [p, sharded], scores [p, sharded]).
    """
    _, matvec_d, screen_scores_d, _ = make_dist_ops(mesh, backend)
    r = matvec_d(X, beta_prev, y)                    # psum
    theta = r / lam_prev
    at_max = lam_prev >= lam_max_val * (1.0 - 1e-12)
    v1 = jnp.where(at_max, v1_at_lmax, y / lam_prev - theta)
    v2 = y / lam_next - theta
    vp = v2 - (jnp.dot(v1, v2) / (jnp.sum(jnp.square(v1)) + 1e-30)) * v1
    centre = theta + 0.5 * vp
    rho = 0.5 * jnp.linalg.norm(vp)
    scores, mask = screen_scores_d(
        X, centre, jnp.asarray(rho), jnp.asarray(eps, X.dtype))
    return mask, scores


def dist_edpp_screen_cached(mesh: Mesh, X, y, lam_next, lam_prev,
                            beta_prev, lam_max_val, v1_at_lmax, col_norms,
                            eps: float = EPS_DEFAULT, backend=None):
    """Sequential EDPP with cached column norms (they are λ-independent):
    one X pass for the residual + one backend ``screen_matvec`` pass per
    shard for the scores (§Perf cached_norms)."""
    f = _fspec(mesh)
    tile = resolve_backend(backend)
    _, matvec_d, _, _ = make_dist_ops(mesh, backend)
    r = matvec_d(X, beta_prev, y)
    theta = r / lam_prev
    at_max = lam_prev >= lam_max_val * (1.0 - 1e-12)
    v1 = jnp.where(at_max, v1_at_lmax, y / lam_prev - theta)
    v2 = y / lam_next - theta
    vp = v2 - (jnp.dot(v1, v2) / (jnp.sum(jnp.square(v1)) + 1e-30)) * v1
    centre = theta + 0.5 * vp
    rho = 0.5 * jnp.linalg.norm(vp)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, f), P(), P(), P(f), P()),
        out_specs=(P(f), P(f)),
        check_vma=False,
    )
    def score_d(Xb, centre, rho, norms_b, eps_):
        scores = jnp.abs(tile.matvec(Xb, centre)) + rho * norms_b
        return scores, scores < 1.0 - eps_

    return score_d(X, centre, jnp.asarray(rho),
                   col_norms, jnp.asarray(eps, X.dtype))


def dist_edpp_screen_sparse(mesh: Mesh, X, X_active, y, lam_next, lam_prev,
                            beta_active, lam_max_val, v1_at_lmax, col_norms,
                            eps: float = EPS_DEFAULT, backend=None):
    """Beyond-paper screening: the residual r = y − Xβ only needs the ACTIVE
    columns (β is sparse after the previous screen+solve), so the residual
    matvec runs over the gathered active block X_active (n, p_active ≪ p)
    while the score pass streams the full X once through the backend's
    ``screen_matvec``. Total ≈ 1 + p_a/p passes (§Perf sparse_residual;
    also the fused-Pallas-kernel data movement)."""
    axes = feature_axes(mesh)
    f = _fspec(mesh)
    tile = resolve_backend(backend)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(None, f), P(f), P()),
        out_specs=P(), check_vma=False,
    )
    def sparse_matvec(Xa_b, ba_b, y):
        return y - _psum(Xa_b @ ba_b, axes)

    r = sparse_matvec(X_active, beta_active, y)
    theta = r / lam_prev
    at_max = lam_prev >= lam_max_val * (1.0 - 1e-12)
    v1 = jnp.where(at_max, v1_at_lmax, y / lam_prev - theta)
    v2 = y / lam_next - theta
    vp = v2 - (jnp.dot(v1, v2) / (jnp.sum(jnp.square(v1)) + 1e-30)) * v1
    centre = theta + 0.5 * vp
    rho = 0.5 * jnp.linalg.norm(vp)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, f), P(), P(), P(f), P()),
        out_specs=(P(f), P(f)),
        check_vma=False,
    )
    def score_d(Xb, centre, rho, norms_b, eps_):
        scores = jnp.abs(tile.matvec(Xb, centre)) + rho * norms_b
        return scores, scores < 1.0 - eps_

    return score_d(X, centre, jnp.asarray(rho),
                   col_norms, jnp.asarray(eps, X.dtype))


# ---------------------------------------------------------------------------
# Batched multi-query variants: one fitted dictionary, B response vectors.
# Features stay column-sharded over the feature axes; the batch shards over
# the mesh's `query` axis when B divides it (replicated otherwise), so the
# recurring collective becomes ONE psum of a (B_local, N) block per query
# shard instead of B per-query N-vector psums — same bytes, 1/B the
# collective launches (latency amortised across the batch), and the 2D
# mesh adds data parallelism on top.
# ---------------------------------------------------------------------------

def dist_edpp_screen_batched(mesh: Mesh, X, Y, lam_next, lam_prev,
                             beta_prev, lam_max_val, v1_at_lmax, col_norms,
                             eps: float = EPS_DEFAULT, backend=None):
    """Sequential EDPP for B queries on the mesh, cached column norms.

    Y (B, N) query-sharded (or replicated), beta_prev (B, p) column-sharded
    on its feature axis, lam_next/lam_prev/lam_max_val (B,), v1_at_lmax
    (B, N). Exactly two X passes for the WHOLE batch: one batched residual
    psum + one batched backend ``screen_matvec`` pass per shard (mirror of
    the fused batched kernel).

    Returns (discard_mask (B, p) sharded, scores (B, p) sharded).
    """
    axes = feature_axes(mesh)
    f = _fspec(mesh)
    q = _qspec(mesh, Y.shape[0])
    tile = resolve_backend(backend)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, f), P(q, f), P(q, None)), out_specs=P(q, None),
        check_vma=False,
    )
    def matvec_b(Xb, bb, Y):
        """R = Y − βXᵀ for the batch: ONE (B_local, N) psum over the
        feature axes per query shard."""
        return Y - _psum(bb @ Xb.T, axes)

    R = matvec_b(X, beta_prev, Y)              # (B, N) query-sharded
    lam_prev = jnp.asarray(lam_prev)[:, None]
    lam_next = jnp.asarray(lam_next)[:, None]
    theta = R / lam_prev
    at_max = jnp.asarray(lam_prev >= lam_max_val[:, None] * (1.0 - 1e-12))
    v1 = jnp.where(at_max, v1_at_lmax, Y / lam_prev - theta)
    v2 = Y / lam_next - theta
    coef = jnp.sum(v1 * v2, axis=-1) / (
        jnp.sum(jnp.square(v1), axis=-1) + 1e-30)
    vp = v2 - coef[:, None] * v1
    centre = theta + 0.5 * vp
    rho = 0.5 * jnp.linalg.norm(vp, axis=-1)         # (B,)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, f), P(q, None), P(q), P(f), P()),
        out_specs=(P(q, f), P(q, f)),
        check_vma=False,
    )
    def score_b(Xb, centre, rho, norms_b, eps_):
        """Batched local scores: zero comms, the backend's batched matvec
        kernel on the (B_local, N)×(N, p_local) block + ρ‖x_j‖ per query."""
        scores = jnp.abs(tile.matvec(Xb, centre)) \
            + rho[:, None] * norms_b[None, :]
        return scores, scores < 1.0 - eps_

    scores, mask = score_b(X, centre, rho, col_norms,
                           jnp.asarray(eps, X.dtype))
    return mask, scores


def dist_fista_batched(mesh: Mesh, X, Y, lam, beta0, lipschitz, *,
                       iters: int = 200, solver_backend=None):
    """Feature- (and query-) sharded FISTA over B queries, fixed iteration
    count.

    Per iteration ONE psum of the (B_local, N) fitted block per query
    shard replaces the B per-query N-vector psums of a query loop; the
    per-shard gradient + soft-threshold + momentum runs the backend's
    fused ``fista_step`` kernel (batch-polymorphic) on the local
    (N, p/shards) block with per-query λ (B,).
    """
    axes = feature_axes(mesh)
    f = _fspec(mesh)
    q = _qspec(mesh, Y.shape[0])
    backend = resolve_solver_backend(solver_backend)
    jnp_b = resolve_solver_backend("jnp")
    fista_op = backend.fista_step or jnp_b.fista_step
    step = 1.0 / jnp.maximum(lipschitz, 1e-12)
    lam = jnp.broadcast_to(jnp.asarray(lam, X.dtype), Y.shape[:1])

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, f), P(q, None), P(q, f), P(q, f), P(), P(q)),
        out_specs=(P(q, f), P(q, f), P()),
        check_vma=False,
    )
    def one_iter(Xb, Y, beta_b, z_b, t, lam):
        XZ = _psum(z_b @ Xb.T, axes)      # (B_local, N): one collective
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        mom = (t - 1.0) / t_new
        # fused backend kernel: gradient matvec over the local block +
        # prox + momentum in one pass (r = Xz − y)
        beta_new, z_new = fista_op(Xb, XZ - Y, z_b, beta_b, step, lam, mom)
        return beta_new, z_new, t_new

    def scan_body(carry, _):
        beta, z, t = carry
        beta, z, t = one_iter(X, Y, beta, z, t, lam)
        return (beta, z, t), None

    t0 = jnp.asarray(1.0, X.dtype)
    (beta, _, _), _ = jax.lax.scan(scan_body, (beta0, beta0, t0), None,
                                   length=iters)
    return beta


def dist_power_iteration(mesh: Mesh, X, iters: int = 30, backend=None):
    """‖X‖₂² via distributed power iteration (one psum per iter); the
    w = Xᵀu half-step runs the resolved backend's ``screen_matvec`` kernel
    on the local feature block."""
    axes = feature_axes(mesh)
    f = _fspec(mesh)
    tile = resolve_backend(backend)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(None, f), P(f)),
        out_specs=(P(f), P()),
        check_vma=False,
    )
    def body_sm(Xb, vb):
        u = _psum(Xb @ vb, axes)                     # (N,) replicated
        w = tile.matvec(Xb, u).astype(X.dtype)       # local block of XᵀXv
        nrm = jnp.sqrt(_psum(jnp.sum(jnp.square(w)), axes))
        return w / (nrm + 1e-30), nrm

    p = X.shape[1]
    v = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (p,), dtype=X.dtype)
        / np.sqrt(p),
        beta_sharding(mesh),
    )

    def body(_, carry):
        v, _ = carry
        return body_sm(X, v)

    v, _ = jax.lax.fori_loop(0, iters, body, (v, jnp.asarray(0.0, X.dtype)))

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(None, f), P(f)), out_specs=P(),
        check_vma=False,
    )
    def rayleigh(Xb, vb):
        u = _psum(Xb @ vb, axes)
        return jnp.sum(jnp.square(u))

    return rayleigh(X, v)


def dist_fista(mesh: Mesh, X, y, lam, beta0, lipschitz, *,
               iters: int = 200, overlap: str = "none", n_chunks: int = 4,
               solver_backend=None):
    """Feature-sharded FISTA, fixed iteration count (jit/scan-friendly).

    Per iteration: 1 psum of an N-vector (the fitted values), local matvecs
    otherwise; the per-shard soft-threshold + momentum update dispatches
    through the SolverEngine's backend registry (``solver_backend`` =
    "pallas" | "interpret" | "jnp" | None → ``REPRO_SOLVER_BACKEND`` /
    auto) — the same fused ``prox_step`` arithmetic as the single-chip
    solver, so sharded and single-chip iterates agree on each local block
    (mirror of ``engine.block_scores`` on the screening side).

    Collective-overlap modes (§Perf hillclimb):

    * ``"none"``    — synchronous reference: one full-N psum per iteration;
      the whole local tail (gradient matvec + prox + momentum) is the
      backend's fused ``fista_step`` kernel on the local block.
    * ``"chunked"`` — **exact** overlap: split the sample axis into
      ``n_chunks``; issue one psum per chunk and compute each chunk's
      gradient partial ``X_cᵀ(Xz_c − y_c)`` as soon as its psum lands, so
      the latency-hiding scheduler overlaps chunk c's collective with chunk
      c−1's local matvec. Identical math to "none".
    * ``"stale"``   — one-iteration-stale fitted values (gradient computed
      from the previous iterate's psum). Hides the collective entirely but
      **breaks FISTA's momentum contraction** — measured to oscillate rather
      than converge past ~1e-2 (refuted hypothesis, logged in §Perf).
      Kept for the record; do not use in production.
    """
    axes = feature_axes(mesh)
    f = _fspec(mesh)
    backend = resolve_solver_backend(solver_backend)
    jnp_b = resolve_solver_backend("jnp")
    prox_op = backend.prox_step or jnp_b.prox_step
    fista_op = backend.fista_step or jnp_b.fista_step
    step = 1.0 / jnp.maximum(lipschitz, 1e-12)
    n = X.shape[0]
    assert overlap in ("none", "chunked", "stale")
    chunk = -(-n // n_chunks) if overlap == "chunked" else n

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, f), P(), P(f), P(f), P(), P(None)),
        out_specs=(P(f), P(f), P(), P(None)),
        check_vma=False,
    )
    def one_iter(Xb, y, beta_b, z_b, t, Xz_prev):
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        mom = (t - 1.0) / t_new
        if overlap == "stale":
            Xz = Xz_prev
            Xz_next = _psum(Xb @ z_b, axes)
            g = Xb.T @ (Xz - y)
        elif overlap == "chunked":
            # Per-chunk psum; gradient partials consume each chunk as it
            # lands → collectives overlap with local compute. Exact.
            parts = []
            for c in range(n_chunks):
                lo = c * chunk
                hi = min(n, lo + chunk)
                Xc = jax.lax.slice_in_dim(Xb, lo, hi, axis=0)
                yc = jax.lax.slice_in_dim(y, lo, hi, axis=0)
                fit_c = _psum(Xc @ z_b, axes)
                parts.append(Xc.T @ (fit_c - yc))
            g = functools.reduce(jnp.add, parts)
            Xz_next = Xz_prev
        else:
            # synchronous: one psum, then the backend's fused fista_step
            # kernel does gradient + prox + momentum on the local block
            Xz = _psum(Xb @ z_b, axes)
            beta_new, z_new = fista_op(Xb, Xz - y, z_b, beta_b,
                                       step, lam, mom)
            return beta_new, z_new, t_new, Xz
        beta_new, z_new = prox_op(z_b, g, beta_b, step, lam, mom)
        return beta_new, z_new, t_new, Xz_next

    def scan_body(carry, _):
        beta, z, t, Xz = carry
        beta, z, t, Xz = one_iter(X, y, beta, z, t, Xz)
        return (beta, z, t, Xz), None

    Xz0 = jnp.zeros_like(y)
    if overlap == "stale":
        _, matvec_d, _, _ = make_dist_ops(mesh)
        Xz0 = y - matvec_d(X, beta0, y)               # X·β₀
    t0 = jnp.asarray(1.0, X.dtype)
    (beta, _, _, _), _ = jax.lax.scan(
        scan_body, (beta0, beta0, t0, Xz0), None, length=iters)
    return beta


# ---------------------------------------------------------------------------
# GSPMD / pjit variant (auto-sharded) — baseline for §Perf comparisons
# ---------------------------------------------------------------------------

def pjit_screen(mesh: Mesh):
    """EDPP screen as plain jnp under jit: GSPMD inserts the collectives.
    Used as the paper-faithful distribution baseline in §Perf."""
    from .screening import edpp_mask, DualState

    def fn(X, y, lam_next, theta, lam_prev, v1):
        state = DualState(theta=theta, lam=lam_prev, v1=v1,
                          at_lmax=jnp.asarray(False))
        return edpp_mask(X, y, lam_next, state)

    return jax.jit(
        fn,
        in_shardings=(x_sharding(mesh), replicated(mesh), replicated(mesh),
                      replicated(mesh), replicated(mesh), replicated(mesh)),
        out_shardings=beta_sharding(mesh),
    )
