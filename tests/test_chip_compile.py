"""Compile-only tests of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so these tests catch what interpret mode
cannot: block shapes off the (8, 128) tiling, operands in a memory space
the kernel may not load from, primitives Mosaic cannot lower. Nothing runs;
each test asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``). Shapes are the served deployment's: the MNIST image
dictionary 784 × 50000, a 784 × 512 reduced bucket for the solver step, a
1024-column Gram block; the group dictionary 250 × 200000 in groups of 10;
and a mesh session's solver ops on the 2×2 mesh.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU compiler's library, and every
test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, SingleDeviceSharding

from repro.kernels import (cd_gram_sweep, edpp_screen_scores, fista_step,
                           group_screen_scores, prox_step, screen_matvec)

N, P = 784, 50000
BUCKET = 512
GRAM_P = 1024
GROUP_N, GROUP_P = 250, 200000


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with JAX's persistent compilation cache off (a
    compile for a described chip is written to the cache but cannot be
    read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _query(one_chip, batch, width):
    """A (width,) query operand for B = 1, (B, width) otherwise, and the
    matching per-query scalar."""
    q = (width,) if batch == 1 else (batch, width)
    s = () if batch == 1 else (batch,)
    return (jax.ShapeDtypeStruct(q, jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_edpp_screen_scores_compiles(one_chip, dtype, batch):
    X = jax.ShapeDtypeStruct((N, P), dtype, sharding=one_chip)
    centre, rho = _query(one_chip, batch, N)
    _compile(lambda X, c, r: edpp_screen_scores(X, c, r), X, centre, rho)


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_screen_matvec_compiles(one_chip, dtype, batch):
    X = jax.ShapeDtypeStruct((N, P), dtype, sharding=one_chip)
    centre, _ = _query(one_chip, batch, N)
    _compile(lambda X, c: screen_matvec(X, c), X, centre)


@pytest.mark.parametrize("batch", [1, 8])
def test_fista_step_compiles(one_chip, batch):
    X = jax.ShapeDtypeStruct((N, BUCKET), jnp.float32, sharding=one_chip)
    r, s = _query(one_chip, batch, N)
    z, _ = _query(one_chip, batch, BUCKET)
    _compile(lambda X, r, z, b, st, lam, mom:
             fista_step(X, r, z, b, st, lam, mom), X, r, z, z, s, s, s)


@pytest.mark.parametrize("batch", [1, 8])
def test_prox_step_compiles(one_chip, batch):
    z, s = _query(one_chip, batch, P)
    _compile(lambda z, g, b, st, lam, mom: prox_step(z, g, b, st, lam, mom),
             z, z, z, s, s, s)


@pytest.mark.parametrize("batch", [1, 8])
def test_cd_gram_sweep_compiles(one_chip, batch):
    G = jax.ShapeDtypeStruct((GRAM_P, GRAM_P), jnp.float32, sharding=one_chip)
    c, lam = _query(one_chip, batch, GRAM_P)
    _compile(lambda G, c, b, lam, v: cd_gram_sweep(G, c, b, lam, valid=v),
             G, c, c, lam, c)


@pytest.mark.parametrize("m", [8, 5, 10])
def test_group_screen_scores_compiles(one_chip, m):
    X = jax.ShapeDtypeStruct((N, P), jnp.float32, sharding=one_chip)
    centre, _ = _query(one_chip, 1, N)
    _compile(lambda X, c: group_screen_scores(X, c, m), X, centre)


def test_batched_group_screen_scores_compiles(one_chip):
    # the group deployment's shape: 250 × 200000 in groups of 10, B = 8
    X = jax.ShapeDtypeStruct((GROUP_N, GROUP_P), jnp.float32,
                             sharding=one_chip)
    centre, _ = _query(one_chip, 8, GROUP_N)
    _compile(lambda X, c: group_screen_scores(X, c, 10), X, centre)


@pytest.mark.parametrize("op", ["fista_step", "cd_gram_sweep", "prox_step"])
def test_sharded_solver_ops_compile_on_a_mesh(topo, op):
    """A mesh session's reduced solves run the tile's solver kernels on
    replicated buckets; on four chips the program spans four devices, and
    the compiler refuses a Mosaic kernel there unless it sits in a
    shard_map."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core import distributed as D

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("query", "feature"),
                axis_types=(AxisType.Auto,) * 2)
    backend = D.sharded_backend(mesh, "pallas")
    rep = NamedSharding(mesh, PartitionSpec())
    B = 8

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rep)

    fn, shapes = {
        "fista_step": (backend.fista_step,
                       (s(N, BUCKET), s(B, N), s(B, BUCKET), s(B, BUCKET),
                        s(B), s(B), s(B))),
        "cd_gram_sweep": (
            lambda G, c, b, lam, v: backend.cd_gram_sweep(G, c, b, lam,
                                                          valid=v),
            (s(BUCKET, BUCKET), s(B, BUCKET), s(B, BUCKET), s(B),
             s(B, BUCKET))),
        "prox_step": (backend.prox_step,
                      (s(B, P), s(B, P), s(B, P), s(B), s(B), s(B))),
    }[op]
    _compile(fn, *shapes)
