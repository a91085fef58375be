"""The plain reference of a served group-Lasso answer, independent of the
program.

The configuration's features fall in G = p / m equal contiguous groups of
m (``session["groups"]``). A served answer to a query y is a lambda grid,
and at each grid point a discard mask over the G groups and a coefficient
vector beta over the p features. It is right when

* the grid is the mix's: ``num_lambdas`` points equally spaced in
  lambda / lambda_max over [lo_frac, hi_frac], with the group
  lambda_max = max_g ||X_g' y|| / sqrt(m) (the paper's eq. 55);
* beta, with every discarded group forced to zero, solves the group Lasso
  min 1/2 ||y - X beta||^2 + lambda sqrt(m) sum_g ||beta_g|| over ALL G
  groups to the configuration's relative duality gap. The dual point is
  the residual scaled into {theta : ||X_g' theta|| <= sqrt(m) for every
  g} (the paper's eqs. 50-53), discarded groups included, so a discard of
  a group the optimum needs shows as a gap.

:func:`certify` computes both numbers in float64 on the host. The harness
finds this module by the configuration's ``reference`` key and calls its
``certify`` (and the control its ``reference_path``) by name. It sees only
the data (made by the benchmark from the seed) and the served answers.

:func:`reference_path` is a plain group-Lasso path (sequential group EDPP
of the paper's Corollary 21 with each group's exact spectral norm, then
block FISTA on the kept groups) in ``jax.numpy`` with every product at a
chosen precision. At ``"high"`` it is the control that the comparison has
to refuse.
"""

import functools
import json
from pathlib import Path

import numpy as np

from bench.reference import _matmul

HERE = Path(__file__).resolve()


def certify(X64, ys, answers, grid, session) -> dict:
    """Worst relative grid error and worst relative full-problem group
    duality gap over served paths, in float64. ``ys`` (Q, n); ``answers`` Q
    tuples (lambdas (K,), betas (K, p), masks (K, G))."""
    m = int(session["groups"])
    ys = np.asarray(ys, np.float64)
    n, p = X64.shape
    G = p // m
    sqm = np.sqrt(m)
    lam_max = np.max(np.linalg.norm((ys @ X64).reshape(len(ys), G, m),
                                    axis=2), axis=1) / sqm       # (Q,)
    fracs = np.linspace(grid["hi_frac"], grid["lo_frac"],
                        grid["num_lambdas"])
    lam_err, cols, lams, yk = [], [], [], []
    for y, lm, (lambdas, betas, masks) in zip(ys, lam_max, answers):
        lambdas = np.asarray(lambdas, np.float64)
        masks = np.asarray(masks, bool)
        if lambdas.shape != fracs.shape or masks.shape[-1] != G:
            return {"lam_err": float("inf"), "gap": float("inf")}
        lam_err.append(np.max(np.abs(lambdas - fracs * lm)) / lm)
        discard = np.repeat(masks, m, axis=1)                  # (K, p)
        cols.append(np.where(discard, 0.0, np.asarray(betas, np.float64)))
        lams.append(lambdas)
        yk.append(np.broadcast_to(y, (len(lambdas), n)))
    if not cols:
        return {"lam_err": None, "gap": None}
    B = np.concatenate(cols)                                    # (QK, p)
    lam = np.concatenate(lams)
    Yk = np.concatenate(yk)                                     # (QK, n)
    nz = np.flatnonzero(np.any(B != 0.0, axis=0))
    R = Yk - B[:, nz] @ X64[:, nz].T                            # (QK, n)
    gcorr = np.linalg.norm((R @ X64).reshape(len(R), G, m), axis=2)
    s = np.minimum(1.0, lam * sqm / np.maximum(gcorr.max(axis=1), 1e-300))
    yy = np.sum(Yk * Yk, axis=1)
    gnorms = np.linalg.norm(B.reshape(len(B), G, m), axis=2)
    primal = (0.5 * np.sum(R * R, axis=1)
              + lam * sqm * np.sum(gnorms, axis=1))
    dual = 0.5 * yy - 0.5 * np.sum((s[:, None] * R - Yk) ** 2, axis=1)
    gap = (primal - dual) / (0.5 * yy)
    return {"lam_err": float(max(lam_err)), "gap": float(np.max(gap))}


# ---------------------------------------------------------------------------
# the plain reference path (the control runs it at "high")
# ---------------------------------------------------------------------------

def configured_groups() -> int:
    """The group size m of the configurations that name this module as
    their reference (``reference_path``'s default)."""
    sizes = set()
    for path in (HERE.parent / "configs").glob("*.json"):
        config = json.loads(path.read_text())
        if Path(config.get("reference", "")).name == HERE.name:
            sizes.add(int(config["session"]["groups"]))
    if len(sizes) != 1:
        raise ValueError(f"the configurations naming {HERE.name} give "
                         f"group sizes {sorted(sizes)}; pass m")
    return sizes.pop()


def spectral_norms(X, m: int) -> np.ndarray:
    """||X_g||_2 of every group, in float64 on the host: the square root of
    the largest eigenvalue of its m x m Gram matrix."""
    X64 = np.asarray(X, np.float64)
    Xg = X64.reshape(X64.shape[0], -1, m)                       # (n, G, m)
    grams = np.einsum("ngi,ngj->gij", Xg, Xg)
    return np.sqrt(np.maximum(np.linalg.eigvalsh(grams)[:, -1], 0.0))


def _bucket(count: int) -> int:
    """Columns of a solve: the kept count rounded up to a power of two, so
    that the solver compiles once per size and not once per count."""
    return 1 << max(4, (int(count) - 1).bit_length())


@functools.lru_cache(maxsize=None)
def _fns(precision: str, tol: float, max_iter: int, eps: float, m: int):
    import jax
    import jax.numpy as jnp
    sqm = float(np.sqrt(m))

    def mm(a, b):
        return _matmul(a, b, precision)

    def group_norms(v):                                         # (B, G)
        return jnp.sqrt(jnp.sum(jnp.square(v.reshape(v.shape[0], -1, m)),
                                axis=2))

    @jax.jit
    def screen(X, y, lam, theta0, v1, spec):
        # sequential group EDPP (Corollary 21): discard g when
        # ||X_g'(theta0 + v2perp / 2)|| < sqrt(m) - ||v2perp|| ||X_g|| / 2
        v2 = y / lam[:, None] - theta0
        coef = jnp.sum(v1 * v2, axis=1) / jnp.maximum(
            jnp.sum(v1 * v1, axis=1), 1e-30)
        v2p = v2 - coef[:, None] * v1
        score = group_norms(mm(theta0 + 0.5 * v2p, X))
        radius = 0.5 * jnp.sqrt(jnp.sum(v2p * v2p, axis=1))
        return score < (sqm - eps) - radius[:, None] * spec[None, :]

    def gap_rel(X, y, beta, lam):
        r = y - mm(beta, X.T)
        s = jnp.minimum(1.0, lam * sqm / jnp.maximum(
            jnp.max(group_norms(mm(r, X)), axis=1), 1e-30))
        yy = jnp.sum(y * y, axis=1)
        primal = (0.5 * jnp.sum(r * r, axis=1)
                  + lam * sqm * jnp.sum(group_norms(beta), axis=1))
        dual = 0.5 * yy - 0.5 * jnp.sum((s[:, None] * r - y) ** 2, axis=1)
        return (primal - dual) / (0.5 * yy)

    @jax.jit
    def solve(Xr, y, lam, beta0, keep):
        """Block FISTA on the gathered kept groups ``Xr`` (zero columns
        where a group is padding); ``keep`` (B, b) holds each query's own
        groups. Returns beta, the relative gap and the dual point."""
        mask = jnp.max(keep, axis=0)
        v = mask / jnp.maximum(jnp.linalg.norm(mask), 1e-30)

        def power(_, v):
            w = jnp.matmul(Xr, v, precision=jax.lax.Precision.HIGHEST)
            u = jnp.matmul(w, Xr, precision=jax.lax.Precision.HIGHEST)
            return u / jnp.maximum(jnp.linalg.norm(u), 1e-30)

        v = jax.lax.fori_loop(0, 100, power, v)
        w = jnp.matmul(Xr, v, precision=jax.lax.Precision.HIGHEST)
        L = 1.01 * jnp.sum(w * w) + 1e-12

        def prox(u, t):
            ug = u.reshape(u.shape[0], -1, m)
            nrm = jnp.sqrt(jnp.sum(ug * ug, axis=2, keepdims=True))
            return (jnp.maximum(0.0, 1.0 - t[:, None, None] * sqm
                                / jnp.maximum(nrm, 1e-30))
                    * ug).reshape(u.shape)

        def body(st):
            beta, z, t, it, _ = st
            grad = mm(mm(z, Xr.T) - y, Xr)
            nb = prox(z - grad / L, lam / L) * keep
            nt = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            nz = nb + ((t - 1.0) / nt) * (nb - beta)
            g = jax.lax.cond(it % 10 == 9,
                             lambda: jnp.max(gap_rel(Xr, y, nb, lam)),
                             lambda: jnp.float32(jnp.inf))
            return nb, nz, nt, it + 1, g

        def cond(st):
            return (st[3] < max_iter) & (st[4] > tol)

        init = (beta0, beta0, jnp.ones(()), jnp.zeros((), jnp.int32),
                jnp.max(gap_rel(Xr, y, beta0, lam)))
        beta, _, _, _, _ = jax.lax.while_loop(cond, body, init)
        theta = (y - mm(beta, Xr.T)) / lam[:, None]
        return beta, gap_rel(Xr, y, beta, lam), theta

    return screen, solve


def reference_path(X, Y, grid, *, precision: str, tol: float,
                   max_iter: int = 20000, eps: float = 1e-6, m=None):
    """Plain group-Lasso paths for a batch Y (B, n) on dictionary X (n, p)
    in groups of ``m`` (default: :func:`configured_groups`), every product
    at ``precision``. Returns host arrays: lambdas (B, K), betas (B, K, p),
    masks (B, K, G), converged (B,)."""
    import jax.numpy as jnp
    m = configured_groups() if m is None else int(m)
    spec = jnp.asarray(spectral_norms(X, m), jnp.float32)
    X = jnp.asarray(X, jnp.float32)
    Y = jnp.asarray(Y, jnp.float32)
    B, (n, p) = Y.shape[0], X.shape
    G = p // m
    screen, solve = _fns(precision, tol, max_iter, eps, m)
    c0 = _matmul(Y, X, precision).reshape(B, G, m)
    gnorm = jnp.sqrt(jnp.sum(c0 * c0, axis=2)) / np.sqrt(m)     # (B, G)
    lam_max = jnp.max(gnorm, axis=1)
    g_star = np.asarray(jnp.argmax(gnorm, axis=1))
    # the ray at lambda_max (eq. 59): X_* X_*' y of each query's top group
    Xs = jnp.stack([X[:, g * m:(g + 1) * m] for g in g_star])   # (B, n, m)
    v1 = jnp.sum(Xs * jnp.sum(Xs * Y[:, :, None], axis=1)[:, None, :],
                 axis=2)
    theta0 = Y / lam_max[:, None]
    fracs = np.linspace(grid["hi_frac"], grid["lo_frac"], grid["num_lambdas"])
    beta = np.zeros((B, p), np.float32)
    lambdas, betas, masks = [], [], []
    converged = np.ones((B,), bool)
    for frac in fracs:
        lam = lam_max * frac
        discard = np.asarray(screen(X, Y, lam, theta0, v1, spec))  # (B, G)
        kept = np.flatnonzero(~discard.all(axis=0))
        size = _bucket(kept.size) * m
        cols = np.zeros((size,), np.int64)
        cols[:kept.size * m] = (kept[:, None] * m + np.arange(m)).ravel()
        keep = np.zeros((B, size), np.float32)
        keep[:, :kept.size * m] = np.repeat(~discard[:, kept], m, axis=1)
        Xr = jnp.asarray(X[:, cols]) * jnp.asarray(keep.max(axis=0))
        beta_r, g, theta = solve(Xr, Y, lam, jnp.asarray(beta[:, cols] * keep),
                                 jnp.asarray(keep))
        beta = np.zeros((B, p), np.float32)
        beta[:, cols[:kept.size * m]] = np.asarray(beta_r)[:, :kept.size * m]
        converged &= (np.asarray(g) <= tol) | (frac >= 1.0)
        lambdas.append(np.asarray(lam, np.float64))
        betas.append(beta.astype(np.float64))
        masks.append(discard)
        v1 = Y / lam[:, None] - theta
        theta0 = theta
    return (np.stack(lambdas, 1), np.stack(betas, 1), np.stack(masks, 1),
            converged)
