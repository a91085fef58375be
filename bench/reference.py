"""The plain reference of the served answer, independent of the program.

A served answer to a query y is a lambda grid, and at each grid point a
discard mask and a coefficient vector beta. It is right when

* the grid is the mix's: ``num_lambdas`` points equally spaced in
  lambda / lambda_max over [lo_frac, hi_frac], lambda_max = max_j |x_j' y|
  (the paper's grid);
* beta, with every discarded feature forced to zero, solves the Lasso
  min 1/2 ||y - X beta||^2 + lambda ||beta||_1 over ALL p features to the
  configuration's relative duality gap. The dual point is the residual
  scaled to be feasible for every feature, discarded ones included, so a
  discard of a feature the optimum needs shows as a gap.

:func:`certify` computes both numbers in float64 on the host. The harness
finds this module by the configuration's ``reference`` key and calls its
``certify`` (and the control its ``reference_path``) by name. It sees only
the data (made by the benchmark from the seed) and the served answers.

:func:`reference_path` is a plain Lasso path (sequential EDPP of the paper's
Theorem 16, then FISTA on the kept features) in ``jax.numpy`` with every
product at a chosen precision. At ``"high"`` (three bfloat16 passes, the step
below the configuration's ``"highest"``) it is the control that the
comparison has to refuse.
"""

import functools

import numpy as np


def certify(X64, ys, answers, grid, session) -> dict:
    """Worst relative grid error and worst relative full-problem duality gap
    over served paths, in float64. ``ys`` (Q, n); ``answers`` Q tuples
    (lambdas (K,), betas (K, p), masks (K, p)). ``session`` is the
    configuration's ``session``; the plain Lasso needs none of it."""
    ys = np.asarray(ys, np.float64)
    lam_max = np.max(np.abs(ys @ X64), axis=1)                  # (Q,)
    fracs = np.linspace(grid["hi_frac"], grid["lo_frac"],
                        grid["num_lambdas"])
    lam_err, cols, lams, yk = [], [], [], []
    for y, lm, (lambdas, betas, masks) in zip(ys, lam_max, answers):
        lambdas = np.asarray(lambdas, np.float64)
        if lambdas.shape != fracs.shape:
            return {"lam_err": float("inf"), "gap": float("inf")}
        lam_err.append(np.max(np.abs(lambdas - fracs * lm)) / lm)
        cols.append(np.where(np.asarray(masks, bool), 0.0,
                             np.asarray(betas, np.float64)))   # (K, p)
        lams.append(lambdas)
        yk.append(np.broadcast_to(y, (len(lambdas), len(y))))
    if not cols:
        return {"lam_err": None, "gap": None}
    B = np.concatenate(cols)                                    # (QK, p)
    lam = np.concatenate(lams)
    Yk = np.concatenate(yk)                                     # (QK, n)
    nz = np.flatnonzero(np.any(B != 0.0, axis=0))
    R = Yk - B[:, nz] @ X64[:, nz].T                            # (QK, n)
    corr = np.max(np.abs(R @ X64), axis=1)
    s = np.minimum(1.0, lam / np.maximum(corr, 1e-300))
    yy = np.sum(Yk * Yk, axis=1)
    primal = 0.5 * np.sum(R * R, axis=1) + lam * np.sum(np.abs(B), axis=1)
    dual = 0.5 * yy - 0.5 * np.sum((s[:, None] * R - Yk) ** 2, axis=1)
    gap = (primal - dual) / (0.5 * yy)
    return {"lam_err": float(max(lam_err)), "gap": float(np.max(gap))}


# ---------------------------------------------------------------------------
# the plain reference path (the control runs it at "high")
# ---------------------------------------------------------------------------

def _matmul(a, b, precision):
    """a @ b in float32. ``"highest"``: full float32 products. ``"high"``:
    each operand split into a bfloat16 head and tail and the three largest
    of the four products summed, as the TPU's three-pass mode does; written
    out so that the CPU computes the same thing. ``"bfloat16"``: one pass
    over operands rounded to bfloat16, accumulated in float32."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(a, b, precision=hi)
    if precision == "bfloat16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"precision must be 'highest', 'high' or "
                         f"'bfloat16', got {precision!r}")

    def split(v):
        head = v.astype(jnp.bfloat16).astype(jnp.float32)
        return head, (v - head).astype(jnp.bfloat16).astype(jnp.float32)

    a1, a2 = split(a)
    b1, b2 = split(b)
    return (jnp.matmul(a1, b1, precision=hi) + jnp.matmul(a1, b2, precision=hi)
            + jnp.matmul(a2, b1, precision=hi))


@functools.lru_cache(maxsize=None)
def _path_fn(precision: str, tol: float, max_iter: int, eps: float):
    import jax
    import jax.numpy as jnp

    def mm(a, b):
        return _matmul(a, b, precision)

    def gap_rel(X, y, beta, lam, keep):
        r = y - mm(beta, X.T)                                   # (B, n)
        c = mm(r, X) * keep                                     # (B, p)
        s = jnp.minimum(1.0, lam / jnp.maximum(
            jnp.max(jnp.abs(c), axis=1), 1e-30))
        yy = jnp.sum(y * y, axis=1)
        primal = 0.5 * jnp.sum(r * r, axis=1) + lam * jnp.sum(
            jnp.abs(beta), axis=1)
        dual = 0.5 * yy - 0.5 * jnp.sum((s[:, None] * r - y) ** 2, axis=1)
        return (primal - dual) / (0.5 * yy)

    def fista(X, y, lam, beta0, keep, L):
        def prox(v, t):
            return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)

        def body(st):
            beta, z, t, it, _ = st
            grad = mm(mm(z, X.T) - y, X)
            nb = prox(z - grad / L, (lam / L)[:, None]) * keep
            nt = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            nz = nb + ((t - 1.0) / nt) * (nb - beta)
            g = jax.lax.cond(it % 10 == 9,
                             lambda: jnp.max(gap_rel(X, y, nb, lam, keep)),
                             lambda: jnp.float32(jnp.inf))
            return nb, nz, nt, it + 1, g

        def cond(st):
            return (st[3] < max_iter) & (st[4] > tol)

        init = (beta0, beta0, jnp.ones(()), jnp.zeros((), jnp.int32),
                jnp.max(gap_rel(X, y, beta0, lam, keep)))
        beta, _, _, it, _ = jax.lax.while_loop(cond, body, init)
        return beta, it, gap_rel(X, y, beta, lam, keep)

    def lipschitz(X, keep):
        """||X_S||_2^2 of the kept columns (the union over the batch), by
        power iteration at full precision."""
        mask = jnp.max(keep, axis=0)
        v = mask / jnp.maximum(jnp.linalg.norm(mask), 1e-30)

        def body(_, v):
            w = jnp.matmul(X, v * mask, precision=jax.lax.Precision.HIGHEST)
            u = jnp.matmul(w, X, precision=jax.lax.Precision.HIGHEST) * mask
            return u / jnp.maximum(jnp.linalg.norm(u), 1e-30)

        v = jax.lax.fori_loop(0, 100, body, v)
        w = jnp.matmul(X, v * mask, precision=jax.lax.Precision.HIGHEST)
        return 1.01 * jnp.sum(w * w) + 1e-12

    @jax.jit
    def step(X, y, lam, theta0, v1, col_norm, beta0):
        # sequential EDPP (Theorem 16): discard j when
        # |x_j'(theta0 + v2perp / 2)| < 1 - ||v2perp|| ||x_j|| / 2
        v2 = y / lam[:, None] - theta0
        coef = jnp.sum(v1 * v2, axis=1) / jnp.maximum(
            jnp.sum(v1 * v1, axis=1), 1e-30)
        v2p = v2 - coef[:, None] * v1
        score = jnp.abs(mm(theta0 + 0.5 * v2p, X))
        radius = 0.5 * jnp.sqrt(jnp.sum(v2p * v2p, axis=1))
        discard = score < (1.0 - eps) - radius[:, None] * col_norm[None, :]
        keep = (~discard).astype(X.dtype)
        beta, it, g = fista(X, y, lam, beta0 * keep, keep,
                            lipschitz(X, keep))
        theta = (y - mm(beta, X.T)) / lam[:, None]
        return beta, discard, theta, it, g

    return step


def reference_path(X, Y, grid, *, precision: str, tol: float,
                   max_iter: int = 20000, eps: float = 1e-6):
    """Plain Lasso paths for a batch Y (B, n) on dictionary X (n, p), every
    product at ``precision``. Returns host arrays: lambdas (B, K), betas
    (B, K, p), masks (B, K, p), converged (B,)."""
    import jax
    import jax.numpy as jnp
    X = jnp.asarray(X, jnp.float32)
    Y = jnp.asarray(Y, jnp.float32)
    B, p = Y.shape[0], X.shape[1]
    c0 = _matmul(Y, X, precision)                               # (B, p)
    lam_max = jnp.max(jnp.abs(c0), axis=1)
    j_star = jnp.argmax(jnp.abs(c0), axis=1)
    fracs = np.linspace(grid["hi_frac"], grid["lo_frac"], grid["num_lambdas"])
    col_norm = jnp.sqrt(jnp.sum(X * X, axis=0))
    step = _path_fn(precision, tol, max_iter, eps)
    theta0 = Y / lam_max[:, None]
    x_star = jnp.take(X, j_star, axis=1).T                      # (B, n)
    v1 = jnp.sign(jnp.take_along_axis(c0, j_star[:, None], 1)) * x_star
    beta = jnp.zeros((B, p), jnp.float32)
    lambdas, betas, masks = [], [], []
    converged = np.ones((B,), bool)
    for frac in fracs:
        lam = lam_max * frac
        beta, discard, theta, _, g = step(X, Y, lam, theta0, v1, col_norm,
                                          beta)
        converged &= (np.asarray(g) <= tol) | (frac >= 1.0)
        lambdas.append(np.asarray(lam, np.float64))
        betas.append(np.asarray(beta, np.float64))
        masks.append(np.asarray(discard))
        v1 = Y / lam[:, None] - theta
        theta0 = theta
    return (np.stack(lambdas, 1), np.stack(betas, 1), np.stack(masks, 1),
            converged)
