"""Pallas TPU kernels for the SolverEngine's device-resident iterations.

Two kernels, mirroring the screening kernels' structure (edpp_screen.py):

``fista_step``
    One fused FISTA iteration tail over column blocks: the gradient matvec
    g = Xᵀr, the soft-threshold and the momentum extrapolation in ONE
    streaming pass over X. Grid = (p_tiles, n_tiles) with the sample axis
    minor so the (Bp, bp) gradient accumulator for a feature tile stays
    resident in VMEM while X streams down the sample axis (same mapping as
    the screening kernel); the finish step applies the prox update without
    the p-sized gradient ever round-tripping to HBM. The n-sized forward
    fit Xz (the iteration's other pass over X) stays with the caller.

``cd_gram_sweep``
    Cyclic coordinate-descent sweeps over a VMEM-resident Gram system
    (G = XᵀX, c = Xᵀy). For the paper's n ≪ p regime the *reduced* problem
    after screening has bucket ≤ n columns, so G is bucket² ≪ n·bucket and
    the whole sweep runs out of VMEM with zero HBM traffic per coordinate.
    The per-coordinate update is expressed in masked vector ops (one-hot
    selects + a dynamic row read of the G ref), VPU-friendly and Mosaic-compilable —
    no scalar gather from the lane dimension.

Batch axis
----------
Both kernels are batch-polymorphic over the *query* operands (see
kernels/ref.py): ``fista_step`` takes r (B, n) + z/beta_old (B, p) and the
B gradients fall out of the SAME single pass over X (the dot grows to
(Bp, bn)×(bn, bp)); ``cd_gram_sweep`` shares one G across the batch and
sweeps all B coordinate systems in lockstep vector ops, with an optional
``valid`` (B, p) mask pinning each query's screened-out columns at zero.
step/lam/mom are scalar-or-(B,). Rank-1 inputs keep the original
single-query arithmetic exactly.

Accumulation follows ref._acc_dtype: f32 for f32/bf16 inputs, f64 is never
downcast (x64 benchmark runs keep solver-grade precision in interpret
mode; the compiled kernels refuse f64, which Mosaic cannot lower). Semantics are DEFINED by ref.fista_step_ref / ref.cd_gram_sweep_ref;
tests/test_kernels.py sweeps shapes/dtypes against them.

bf16 X is a first-class input: under ``SolveSpec(solve_dtype="bfloat16")``
the SolverEngine streams its iteration matvecs (``fista_step`` + the
forward fit) through a bf16 copy of the reduced bucket while β/z and the
accumulators stay f32 — the duality-gap certificates stream the f32 data,
so convergence is certified exactly (docs/solvers.md#mixed-precision-solves).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .edpp_screen import check_compilable, resolve_tiles
from .ref import HIGHEST, _acc_dtype

# VMEM guard for cd_gram_sweep: G is (b, b) f32/f64 and must fit on-chip
# alongside its (Bp, b) vectors. 1024² f32 = 4 MiB ≪ 16 MiB/core.
GRAM_BUCKET_MAX = 1024


def _q2d(v: jax.Array):
    """(p,)|(B, p) query operand → ((B, p), B, squeeze)."""
    if v.ndim == 1:
        return v[None, :], 1, True
    return v, v.shape[0], False


def scalar_cols(b: int, b_pad: int, dtype, *params) -> jax.Array:
    """Per-query scalar-or-(B,) params as the columns of a (Bp, k) array:
    a whole-array VMEM operand whose column slices broadcast against the
    (Bp, ·) query rows without a relayout."""
    cols = [jnp.pad(jnp.broadcast_to(jnp.asarray(s, dtype), (b,)),
                    (0, b_pad)) for s in params]
    return jnp.stack(cols, axis=1)


def _fista_step_kernel(s_ref, r_ref, x_ref, z_ref, b_ref,
                       g_ref, beta_ref, znew_ref, *, n_tiles: int, acc):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    x = x_ref[...].astype(acc)                       # (bn, bp)
    r = r_ref[...].astype(acc)                       # (Bp, bn)
    # MXU: (Bp, bn) @ (bn, bp) -> (Bp, bp) gradient partial
    g_ref[...] += jax.lax.dot_general(
        r, x, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=acc,
    )

    @pl.when(j == n_tiles - 1)
    def _finish():
        s = s_ref[...]                               # (Bp, 3)
        step, lam, mom = s[:, 0:1], s[:, 1:2], s[:, 2:3]
        u = z_ref[...].astype(acc) - step * g_ref[...]
        t = step * lam
        beta_new = jnp.sign(u) * jnp.maximum(jnp.abs(u) - t, 0.0)
        beta_ref[...] = beta_new.astype(beta_ref.dtype)
        znew_ref[...] = (beta_new + mom * (beta_new - b_ref[...].astype(acc))
                         ).astype(znew_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bp", "interpret"))
def fista_step(
    X: jax.Array,
    r: jax.Array,
    z: jax.Array,
    beta_old: jax.Array,
    step,
    lam,
    mom,
    *,
    bn: int | None = None,
    bp: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused FISTA iteration tail (see module doc). Any (N, p); zero padded
    internally — zero rows/columns are exact no-ops for the accumulator and
    fixed points for the prox, so padded solver buffers pass through.
    r may be (B, n) with z/beta_old (B, p): all B iterations share the one
    streaming pass over X.

    Default tiles shrink to the problem (capped at 512): unlike the screens
    this runs once per *inner iteration*, so padding a 30×80 reduced bucket
    to a 512×512 tile would multiply the whole solve's flops.
    """
    check_compilable(interpret, X, r, z, beta_old)
    n, p = X.shape
    bn, bp = resolve_tiles(n, p, bn, bp)
    acc = _acc_dtype(X)
    n_pad = -n % bn
    p_pad = -p % bp
    r2, b, squeeze = _q2d(r)
    b_pad = 0 if b == 1 else -b % 8          # sublane multiple for B > 1
    bq = b + b_pad
    z2 = z[None, :] if squeeze else z
    bo2 = beta_old[None, :] if squeeze else beta_old
    Xp = jnp.pad(X, ((0, n_pad), (0, p_pad)))
    rp = jnp.pad(r2, ((0, b_pad), (0, n_pad)))
    zp = jnp.pad(z2, ((0, b_pad), (0, p_pad)))
    bp_old = jnp.pad(bo2, ((0, b_pad), (0, p_pad)))
    scalars = scalar_cols(b, b_pad, acc, step, lam, mom)
    n_tiles = (n + n_pad) // bn
    p_tiles = (p + p_pad) // bp

    _, beta_new, z_new = pl.pallas_call(
        functools.partial(_fista_step_kernel, n_tiles=n_tiles, acc=acc),
        grid=(p_tiles, n_tiles),
        in_specs=[
            pl.BlockSpec((bq, 3), lambda i, j: (0, 0)),        # scalars
            pl.BlockSpec((bq, bn), lambda i, j: (0, j)),       # residuals
            pl.BlockSpec((bn, bp), lambda i, j: (j, i)),       # X tile
            pl.BlockSpec((bq, bp), lambda i, j: (0, i)),       # z
            pl.BlockSpec((bq, bp), lambda i, j: (0, i)),       # beta_old
        ],
        out_specs=[
            pl.BlockSpec((bq, bp), lambda i, j: (0, i)),       # gradient acc
            pl.BlockSpec((bq, bp), lambda i, j: (0, i)),       # beta_new
            pl.BlockSpec((bq, bp), lambda i, j: (0, i)),       # z_new
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bq, p + p_pad), acc),
            jax.ShapeDtypeStruct((bq, p + p_pad), z.dtype),
            jax.ShapeDtypeStruct((bq, p + p_pad), z.dtype),
        ],
        interpret=interpret,
        name="fista_step",
    )(scalars, rp, Xp, zp, bp_old)
    beta_new = beta_new[:b, :p]
    z_new = z_new[:b, :p]
    if squeeze:
        return beta_new[0], z_new[0]
    return beta_new, z_new


def _cd_gram_kernel(s_ref, g_ref, c_ref, b_ref, v_ref, out_ref, *,
                    p: int, sweeps: int, acc):
    lam = s_ref[...]                                 # (Bp, 1)
    c = c_ref[...].astype(acc)                       # (Bp, p)
    beta0 = b_ref[...].astype(acc)                   # (Bp, p)
    valid = v_ref[...].astype(acc)                   # (Bp, p)
    q0 = jax.lax.dot_general(                        # q = βG (G symmetric)
        beta0, g_ref[...].astype(acc), (((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=acc)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)

    def pick(v, onehot):                             # v[:, j] as (rows, 1)
        return jnp.sum(jnp.where(onehot, v, 0.0), axis=1, keepdims=True)

    def coord(i, carry):
        beta, q = carry
        j = i % p
        onehot = iota == j                                 # (1, p)
        row = g_ref[pl.ds(j, 1), :].astype(acc)            # G_j,: == G_:,j
        gjj = pick(row, onehot)                            # (1, 1)
        bj = pick(beta, onehot)                            # (Bp, 1)
        rho = pick(c, onehot) - pick(q, onehot) + gjj * bj
        bn_ = jnp.where(
            gjj > 0,
            jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0)
            / jnp.maximum(gjj, 1e-30),
            0.0,
        ) * pick(valid, onehot)
        beta = jnp.where(onehot, bn_, beta)
        q = q + row * (bn_ - bj)
        return beta, q

    beta, _ = jax.lax.fori_loop(0, sweeps * p, coord, (beta0, q0))
    out_ref[...] = beta.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sweeps", "interpret"))
def cd_gram_sweep(
    G: jax.Array,
    c: jax.Array,
    beta: jax.Array,
    lam,
    sweeps: int = 1,
    valid: jax.Array | None = None,
    *,
    interpret: bool = False,
) -> jax.Array:
    """``sweeps`` cyclic CD sweeps over the VMEM-resident Gram system.

    Matches ref.cd_gram_sweep_ref. Requires p ≤ GRAM_BUCKET_MAX (the
    SolverEngine's Gram-vs-matvec crossover guards this); p is padded to a
    lane multiple — padded columns have G_jj = 0 and stay at β = 0.
    Batched: c/beta (B, p) share the one (p, p) Gram block; lam is
    scalar-or-(B,); ``valid`` (B, p) pins screened-out columns per query.
    """
    check_compilable(interpret, G, c, beta)
    p = G.shape[0]
    if p > GRAM_BUCKET_MAX:
        raise ValueError(
            f"cd_gram_sweep: p={p} exceeds GRAM_BUCKET_MAX={GRAM_BUCKET_MAX}")
    acc = _acc_dtype(G)
    p_pad = -p % 128
    c2, b, squeeze = _q2d(c)
    beta2 = beta[None, :] if squeeze else beta
    b_pad = 0 if b == 1 else -b % 8
    bq = b + b_pad
    if valid is None:
        valid2 = jnp.ones((b, p), acc)
    else:
        valid2 = valid[None, :] if valid.ndim == 1 else valid
    Gp = jnp.pad(G, ((0, p_pad), (0, p_pad)))
    cp = jnp.pad(c2, ((0, b_pad), (0, p_pad)))
    bp_ = jnp.pad(beta2, ((0, b_pad), (0, p_pad)))
    vp_ = jnp.pad(valid2.astype(acc), ((0, b_pad), (0, p_pad)))
    scalars = scalar_cols(b, b_pad, acc, lam)

    out = pl.pallas_call(
        functools.partial(_cd_gram_kernel, p=p + p_pad, sweeps=sweeps,
                          acc=acc),
        in_specs=[
            pl.BlockSpec((bq, 1), lambda: (0, 0)),    # lam (Bp, 1)
            pl.BlockSpec((p + p_pad, p + p_pad), lambda: (0, 0)),
            pl.BlockSpec((bq, p + p_pad), lambda: (0, 0)),
            pl.BlockSpec((bq, p + p_pad), lambda: (0, 0)),
            pl.BlockSpec((bq, p + p_pad), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, p + p_pad), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((bq, p + p_pad), beta.dtype),
        interpret=interpret,
        name="cd_gram_sweep",
    )(scalars, Gp, cp, bp_, vp_)
    out = out[:b, :p]
    return out[0] if squeeze else out
