"""Gaussian design in equal contiguous groups, group-sparse responses.

The paper's group Lasso protocol (section 4.2): X with i.i.d. N(0, 1)
entries, its p features split into p / m equal contiguous groups of m, and
responses y = X beta + sigma * eps with beta group-sparse. Each query draws
``active_groups`` distinct groups; their coefficients are U(-1, 1) and
every other coefficient is 0.
"""

import jax
import jax.numpy as jnp


def dictionary(key, params):
    """Returns (X (n, p) float32 on the device, None): the design holds
    everything the queries need."""
    X = jax.random.normal(key, (params["n"], params["p"]), jnp.float32)
    return X, None


def queries(key, params, X, state, count):
    """Responses (count, n) float32 of group-sparse coefficient vectors."""
    k_groups, k_coef, k_noise = jax.random.split(key, 3)
    n, p = X.shape
    m, active = params["groups"], params["active_groups"]
    # `active` distinct groups per query: the top of a uniform draw per group
    _, chosen = jax.lax.top_k(jax.random.uniform(k_groups, (count, p // m)),
                              active)                           # (count, a)
    cols = (chosen[:, :, None] * m + jnp.arange(m)).reshape(count, -1)
    coef = jax.random.uniform(k_coef, cols.shape, jnp.float32, -1.0, 1.0)
    Xs = jnp.take(X, cols, axis=1)                              # (n, count, a*m)
    clean = jnp.einsum("nqc,qc->qn", Xs, coef,
                       precision=jax.lax.Precision.HIGHEST)
    noise = params["sigma"] * jax.random.normal(k_noise, (count, n))
    return clean + noise
