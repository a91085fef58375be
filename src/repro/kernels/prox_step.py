"""Pallas TPU kernel: fused FISTA inner update (soft-threshold + momentum).

    u        = z − step·g
    beta_new = S(u, step·λ)                    (soft-threshold)
    z_new    = beta_new + mom·(beta_new − beta_old)

Unfused, this is 5 elementwise HBM round-trips over p-vectors; fused it is a
single read of (z, g, beta_old) and a single write of (beta_new, z_new) —
pure VPU work, trivially memory-bound, so fusion is the whole win.

Batch axis: z/g/beta_old may be (B, p) blocks (B queries through one fused
pass), with step/λ/mom each scalar-or-(B,). Rank-1 inputs keep the original
single-query arithmetic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .edpp_screen import check_compilable
from .solver_step import scalar_cols


def _prox_kernel(s_ref, z_ref, g_ref, b_ref, beta_ref, znew_ref):
    s = s_ref[...]                                    # (Bp, 3)
    step, lam, mom = s[:, 0:1], s[:, 1:2], s[:, 2:3]
    u = z_ref[...] - step * g_ref[...]
    t = step * lam
    beta_new = jnp.sign(u) * jnp.maximum(jnp.abs(u) - t, 0.0)
    beta_ref[...] = beta_new
    znew_ref[...] = beta_new + mom * (beta_new - b_ref[...])


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def prox_step(
    z: jax.Array,
    g: jax.Array,
    beta_old: jax.Array,
    step,
    lam,
    mom,
    *,
    bp: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused FISTA update over p-vectors (any length; zero padded).
    z/g/beta_old may carry a leading batch axis (B, p); step/lam/mom are
    then scalar-or-(B,) per-query parameters."""
    check_compilable(interpret, z, g, beta_old)
    squeeze = z.ndim == 1
    z2 = z[None, :] if squeeze else z
    g2 = g[None, :] if squeeze else g
    bo2 = beta_old[None, :] if squeeze else beta_old
    b, p = z2.shape
    b_pad = 0 if b == 1 else -b % 8
    bq = b + b_pad
    p_pad = -p % bp
    zp = jnp.pad(z2, ((0, b_pad), (0, p_pad)))
    gp = jnp.pad(g2, ((0, b_pad), (0, p_pad)))
    bp_old = jnp.pad(bo2, ((0, b_pad), (0, p_pad)))
    scalars = scalar_cols(b, b_pad, z.dtype, step, lam, mom)
    p_tiles = (p + p_pad) // bp

    beta_new, z_new = pl.pallas_call(
        _prox_kernel,
        grid=(p_tiles,),
        in_specs=[
            pl.BlockSpec((bq, 3), lambda i: (0, 0)),    # scalars (Bp, 3)
            pl.BlockSpec((bq, bp), lambda i: (0, i)),
            pl.BlockSpec((bq, bp), lambda i: (0, i)),
            pl.BlockSpec((bq, bp), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((bq, bp), lambda i: (0, i)),
            pl.BlockSpec((bq, bp), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bq, p + p_pad), z.dtype),
            jax.ShapeDtypeStruct((bq, p + p_pad), z.dtype),
        ],
        interpret=interpret,
        name="prox_step",
    )(scalars, zp, gp, bp_old)
    beta_new = beta_new[:b, :p]
    z_new = z_new[:b, :p]
    if squeeze:
        return beta_new[0], z_new[0]
    return beta_new, z_new
