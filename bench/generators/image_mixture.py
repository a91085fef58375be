"""Image-like dictionary: nonnegative columns from a seeded low-rank mixture.

Stands in for a set of grey-scale images of one domain (the paper's image
protocol, section 4): each image mixes a few of ``rank`` shared nonnegative
stroke atoms, whose pixels gather at the centre of the canvas
(``atom_density`` at the centre, falling off over ``spread``), plus
pixel noise on the strokes; values are clipped to [0, 1] as pixel intensities are. Shared
atoms make the columns coherent, as the images of one domain are. Queries
are held-out images drawn from the same atoms, never columns of X.
"""

import jax
import jax.numpy as jnp


def _images(key, atoms, count, params):
    k_pick, k_weight, k_noise = jax.random.split(key, 3)
    rank = atoms.shape[1]
    # each image mixes `atoms_per_image` distinct atoms with exponential weights
    order = jnp.argsort(jax.random.uniform(k_pick, (count, rank)), axis=1)
    chosen = order[:, : params["atoms_per_image"]]
    weights = jax.random.exponential(k_weight, chosen.shape, jnp.float32)
    H = jnp.zeros((count, rank), jnp.float32).at[
        jnp.arange(count)[:, None], chosen].set(weights)
    clean = jnp.matmul(H, atoms.T, precision=jax.lax.Precision.HIGHEST)
    # pixel noise on the strokes only: the background stays exactly 0
    noise = params["noise"] * jax.random.normal(k_noise, clean.shape)
    return jnp.clip(clean + noise * (clean > 0), 0.0, 1.0)   # (count, n)


def dictionary(key, params):
    """Returns (X (n, p) float32 on the device, atoms)."""
    k_atoms, k_support, k_cols = jax.random.split(key, 3)
    n, p, rank = params["n"], params["p"], params["rank"]
    # strokes gather at the centre of the side x side canvas, as digits do:
    # a pixel's chance to lie on an atom falls off as a Gaussian of width
    # `spread` (in canvas sides) around the centre
    side = int(round(n ** 0.5))
    c = (jnp.arange(side, dtype=jnp.float32) - (side - 1) / 2) / side
    d2 = (c[:, None] ** 2 + c[None, :] ** 2).reshape(-1)
    prob = params["atom_density"] * jnp.exp(-d2 / (2 * params["spread"] ** 2))
    support = jax.random.bernoulli(k_support, prob[:, None], (n, rank))
    atoms = jax.random.uniform(k_atoms, (n, rank), jnp.float32) * support
    X = _images(k_cols, atoms, p, params).T
    return X, atoms


def queries(key, params, X, atoms, count):
    """Held-out images of the same domain: (count, n) float32."""
    return _images(key, atoms, count, params)
