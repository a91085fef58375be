"""Group-EDPP screening scores (Corollary 21 LHS) on the screening kernel.

For contiguous groups of size m:  gscores[g] = ‖X_gᵀ·o‖₂.

The one HBM pass over X is the ``screen_matvec`` kernel (edpp_screen.py),
whose tiles are lane-aligned for every p. The per-group reduction (reshape
the p-vector of dots to (p/m, m), square, sum, sqrt) runs on that vector
afterwards. Fusing it into the kernel's last sample tile would need a
feature tile that is a multiple of both 128 lanes and 128·m features (so
that the (1, bp/m) output tile is lane-aligned too) — 1280 columns for
m = 10, and no tile at all for the m in the thousands that wide groups
use. The unfused reduction re-reads a p-vector of dots, 4·p bytes against
the kernel's 4·n·p, so it costs 1/n of the pass. It runs under the
device scope ``group_reduce``, so a trace names it beside the
``screen_matvec`` custom call.

``centre`` may be a (B, n) batch: the one pass over X yields all B rows of
dots (``screen_matvec`` takes the batch) and the reduction gives (B, G).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .edpp_screen import screen_matvec


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
@jax.named_scope("screen")
def group_screen_scores(
    X: jax.Array,
    centre: jax.Array,
    m: int,
    *,
    interpret: bool = False,
) -> jax.Array:
    """gscores[g] = ‖X_gᵀ·centre‖ for contiguous equal groups of size m
    (any m that divides p); (G,) for a (n,) centre, (B, G) for (B, n)."""
    p = X.shape[1]
    if p % m:
        raise ValueError(f"p={p} is not divisible by the group size m={m}")
    dot = screen_matvec(X, centre, interpret=interpret)
    with jax.named_scope("group_reduce"):
        groups = dot.reshape(*dot.shape[:-1], -1, m)
        return jnp.sqrt(jnp.sum(jnp.square(groups), axis=-1))
