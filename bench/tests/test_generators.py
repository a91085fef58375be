"""The data generators: what the configurations say they make."""

import jax
import numpy as np
import pytest

from bench import harness

GEN = harness.BENCH / "generators"


@pytest.mark.parametrize("seed", [0, 1])
def test_image_columns_are_nonnegative_coherent_images(seed):
    gen = harness.load_module(GEN / "image_mixture.py")
    params = {"n": 784, "p": 600, "rank": 20, "atoms_per_image": 3,
              "atom_density": 0.4, "spread": 0.2, "noise": 0.1}
    X, atoms = gen.dictionary(jax.random.key(seed), params)
    X = np.asarray(X, np.float64)
    assert X.shape == (784, 600)
    assert X.min() >= 0.0 and X.max() <= 1.0
    assert 0.6 < np.mean(X == 0.0) < 0.95          # a dark background
    U = X / np.linalg.norm(X, axis=0)
    cos = (U.T @ U)[np.triu_indices(600, 1)]
    assert cos.min() >= 0.0 and cos.mean() > 0.2   # coherent columns
    Y = np.asarray(gen.queries(jax.random.key(seed + 7), params, None,
                               atoms, 4), np.float64)
    assert Y.shape == (4, 784) and Y.min() >= 0.0
    # held out: no query is a column of X
    assert np.min(np.abs(Y[:, :, None] - X[None]).max(axis=1)) > 1e-3
