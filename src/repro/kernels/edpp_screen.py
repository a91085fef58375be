"""Pallas TPU kernel: fused EDPP screening pass.

The screening hot loop evaluates, for every feature column x_j of X ∈ R^{N×p},

    scores[j] = |x_jᵀ·o| + ρ·‖x_j‖₂          (Theorem 16: discard iff < 1)

This is a memory-bound streaming op: X is read exactly once from HBM, and the
matvec, the column sum-of-squares, and the score combine are fused into that
single pass (a naive jnp implementation reads X twice — once for Xᵀo, once for
the norms — and materialises two p-vectors in between).

Batch axis
----------
``o`` may be a (B, n) block of B query centres (one fitted dictionary, B
response vectors). The kernel then computes all B score rows in the SAME
single pass over X: the per-tile dot grows from (1, bn)×(bn, bp) to
(Bp, bn)×(bn, bp) — still one MXU contraction — so HBM traffic over X is
amortised 1/B per query. ρ becomes per-query (scalar-or-(B,)). B = 1 takes
the exact original code shape ((1, bn) centre block), so single-query
results are unchanged.

TPU mapping
-----------
* Grid = (p_tiles, n_tiles); the sample axis n is the *minor* grid dim, so the
  (Bp, bp)-shaped accumulators for a feature tile stay resident in VMEM while
  we stream X tile-by-tile down the sample axis.
* X tile (bn, bp) with bp a multiple of 128 (lane dim) and bn a multiple of 8
  (sublane dim); the (Bp, bn)×(bn, bp) dot hits the MXU, the
  square/accumulate runs on the VPU. Batched centres are padded to a sublane
  multiple (Bp = 8⌈B/8⌉ for B > 1).
* Accumulation is f32 regardless of input dtype (bf16 X supported): a
  bf16 X tile halves the streamed bytes — the dominant cost — while the
  MXU contraction and the VMEM accumulators stay f32, so the only error
  vs an f32 pass is the input quantisation itself. The engine's
  margin-aware fallback (docs/kernels.md) turns that into f32-exact
  masks; the kernel itself just honours the dtype it is handed.

VMEM budget (defaults bn=512, bp=512, f32, B=64): X tile 1 MiB + o tile
128 KiB + accumulators 3·128 KiB ≈ 1.5 MiB ≪ 16 MiB/core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import HIGHEST


def resolve_tiles(n: int, p: int, bn: int | None = None,
                  bp: int | None = None) -> tuple[int, int]:
    """Default kernel tiles, shrunk to the (local) problem and capped at 512.

    ``bn`` rounds up to a sublane multiple (16 covers f32 and bf16), ``bp``
    to the 128-lane dim. The shrink matters under ``shard_map``: a feature
    shard sees only its local (n, p/shards) block, and padding a 64-column
    shard to a 512-wide tile would multiply the kernel's flops 8×. Explicit
    ``bn``/``bp`` pass through unchanged (perf experiments).
    """
    if bn is None:
        bn = min(512, -(-n // 16) * 16)
    if bp is None:
        bp = min(512, -(-p // 128) * 128)
    return bn, bp


def check_compilable(interpret: bool, *arrays) -> None:
    """Refuse float64 operands for a compiled (Mosaic) kernel, which has no
    f64 support. Interpret mode runs f64 as given (x64 CPU runs)."""
    if interpret:
        return
    wide = [a.dtype for a in arrays if a.dtype == jnp.float64]
    if wide:
        raise TypeError(
            "the compiled pallas kernels take float32 or bfloat16 operands, "
            "got float64: run without jax_enable_x64, cast the inputs, or "
            "use the 'jnp' backend")


def _dot(o, x):
    """(Bp, bn) @ (bn, bp) in full f32 on the MXU. The screen decisions
    compare these dots against 1 − ε with ε = 1e-6, so the default TPU
    precision (one bf16 pass, ~2⁻⁹ relative error) is not safe here:
    ``HIGHEST`` lowers to Mosaic's fp32 contraction."""
    return jax.lax.dot_general(
        o, x, (((1,), (0,)), ((), ())),
        precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _centre_block(centre: jax.Array, n_pad: int):
    """Lift a (n,)|(B, n) centre to a sublane-padded (Bp, n+n_pad) block.

    Returns (block, B, squeeze): B is the true batch size, squeeze marks a
    rank-1 input whose outputs must drop the batch axis again.
    """
    squeeze = centre.ndim == 1
    c2 = centre[None, :] if squeeze else centre
    b = c2.shape[0]
    b_pad = 0 if b == 1 else -b % 8           # sublane multiple for B > 1
    block = jnp.pad(c2, ((0, b_pad), (0, n_pad)))
    return block, b, squeeze


def _screen_kernel(o_ref, rho_ref, x_ref, dot_ref, ss_ref, scores_ref, *,
                   n_tiles: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    x = x_ref[...]                                    # (bn, bp)
    o = o_ref[...].astype(jnp.float32)                # (Bp, bn)
    x32 = x.astype(jnp.float32)
    # MXU: (Bp, bn) @ (bn, bp) -> (Bp, bp), full f32 (see _dot)
    dot_ref[...] += _dot(o, x32)
    # VPU: running column sum-of-squares (query-independent: one row)
    ss_ref[...] += jnp.sum(x32 * x32, axis=0, keepdims=True)

    @pl.when(j == n_tiles - 1)
    def _finish():
        rho = rho_ref[...]                            # (Bp, 1)
        scores_ref[...] = jnp.abs(dot_ref[...]) + rho * jnp.sqrt(ss_ref[...])


@functools.partial(jax.jit, static_argnames=("bn", "bp", "interpret"))
@jax.named_scope("screen")
def edpp_screen_scores(
    X: jax.Array,
    centre: jax.Array,
    rho,
    *,
    bn: int | None = None,
    bp: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused scores[j] = |x_jᵀ·centre| + rho·‖x_j‖ and sumsq[j] = ‖x_j‖².

    Inputs of any (N, p); zero-padded internally to tile multiples (zero rows
    and columns are exact no-ops for both accumulators). ``centre`` may be
    (n,) or (B, n) — the batched call still reads X exactly once; ``rho`` is
    then scalar-or-(B,). ``sumsq`` is always (p,) (dictionary geometry).
    Tiles default to :func:`resolve_tiles` (shrink-to-problem, 512 cap) so
    shard-local blocks under ``shard_map`` don't pay full-tile padding.
    """
    check_compilable(interpret, X, centre, jnp.asarray(rho))
    n, p = X.shape
    bn, bp = resolve_tiles(n, p, bn, bp)
    n_pad = -n % bn
    p_pad = -p % bp
    Xp = jnp.pad(X, ((0, n_pad), (0, p_pad)))
    op, b, squeeze = _centre_block(centre, n_pad)
    bq = op.shape[0]
    rho_arr = jnp.pad(
        jnp.broadcast_to(jnp.asarray(rho, jnp.float32), (b,)),
        (0, bq - b))[:, None]

    n_tiles = (n + n_pad) // bn
    p_tiles = (p + p_pad) // bp

    dot, ss, scores = pl.pallas_call(
        functools.partial(_screen_kernel, n_tiles=n_tiles),
        grid=(p_tiles, n_tiles),
        in_specs=[
            pl.BlockSpec((bq, bn), lambda i, j: (0, j)),       # centres
            pl.BlockSpec((bq, 1), lambda i, j: (0, 0)),        # rho (Bp, 1)
            pl.BlockSpec((bn, bp), lambda i, j: (j, i)),       # X tile
        ],
        out_specs=[
            pl.BlockSpec((bq, bp), lambda i, j: (0, i)),       # dot acc
            pl.BlockSpec((1, bp), lambda i, j: (0, i)),        # sumsq acc
            pl.BlockSpec((bq, bp), lambda i, j: (0, i)),       # scores
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bq, p + p_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, p + p_pad), jnp.float32),
            jax.ShapeDtypeStruct((bq, p + p_pad), jnp.float32),
        ],
        interpret=interpret,
        name="edpp_screen_scores",
    )(op, rho_arr, Xp)
    scores = scores[:b, :p]
    return (scores[0] if squeeze else scores), ss[0, :p]


def _matvec_kernel(o_ref, x_ref, dot_ref, *, n_tiles: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dot_ref[...] = jnp.zeros_like(dot_ref)

    x32 = x_ref[...].astype(jnp.float32)
    o = o_ref[...].astype(jnp.float32)
    dot_ref[...] += _dot(o, x32)


@functools.partial(jax.jit, static_argnames=("bn", "bp", "interpret"))
@jax.named_scope("screen")
def screen_matvec(
    X: jax.Array,
    centre: jax.Array,
    *,
    bn: int | None = None,
    bp: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """dot[j] = x_jᵀ·centre — the per-step screening matvec when column norms
    are cached across the λ-path (X is fixed along the path). ``centre`` may
    be (B, n): one pass over X yields all B correlation rows (B, p). Tiles
    default to :func:`resolve_tiles` (shard-local blocks stay unpadded)."""
    check_compilable(interpret, X, centre)
    n, p = X.shape
    bn, bp = resolve_tiles(n, p, bn, bp)
    n_pad = -n % bn
    p_pad = -p % bp
    Xp = jnp.pad(X, ((0, n_pad), (0, p_pad)))
    op, b, squeeze = _centre_block(centre, n_pad)
    bq = op.shape[0]
    n_tiles = (n + n_pad) // bn
    p_tiles = (p + p_pad) // bp

    dot = pl.pallas_call(
        functools.partial(_matvec_kernel, n_tiles=n_tiles),
        grid=(p_tiles, n_tiles),
        in_specs=[
            pl.BlockSpec((bq, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bn, bp), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((bq, bp), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((bq, p + p_pad), jnp.float32),
        interpret=interpret,
        name="screen_matvec",
    )(op, Xp)
    dot = dot[:b, :p]
    return dot[0] if squeeze else dot
