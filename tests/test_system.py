"""End-to-end behaviour tests for the paper's system.

1. Full λ-path model selection run with EDPP — the paper's headline
   workflow — checked for exactness + actual screening.
2. A real (tiny) LM training run through the production train_step on a
   1-device mesh: loss must decrease.
3. The screening→prune bridge: group-EDPP discards inactive FFN neurons of
   a trained tiny model (the framework integration of DESIGN §5).
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.core import (GroupPathConfig, PathConfig, group_lambda_max,
                        group_lasso_path, lambda_grid, lambda_max,
                        lasso_path)
from repro.core.distributed import make_mesh
from repro.data import SyntheticLM, device_batch
from repro.optim import adamw
from repro.train import steps as ST


def test_lasso_model_selection_end_to_end(rng):
    """25-point λ grid, sequential EDPP, exactness vs unscreened."""
    r = np.random.default_rng(42)
    n, p = 60, 600
    X = r.standard_normal((n, p))
    beta = np.zeros(p)
    beta[r.choice(p, 15, replace=False)] = r.uniform(-1, 1, 15)
    y = X @ beta + 0.1 * r.standard_normal(n)

    lmax = float(lambda_max(jnp.asarray(X, jnp.float32),
                            jnp.asarray(y, jnp.float32)))
    grid = lambda_grid(lmax, num=25)
    ref = lasso_path(X, y, grid, PathConfig(rule="none", solver_tol=1e-9))
    res = lasso_path(X, y, grid, PathConfig(rule="edpp", solver_tol=1e-9))
    np.testing.assert_allclose(res.betas, ref.betas, atol=5e-4)
    # screening must fire substantially on the sparse end of the path
    assert res.stats[3].n_discarded > 0.5 * p
    # and the screened path must be cheaper in solver work
    assert (sum(s.solver_iters * s.n_kept for s in res.stats)
            < sum(s.solver_iters * p for s in ref.stats))


def test_train_loop_loss_decreases():
    """Production train_step (jitted, sharded, AdamW) on a 1-device mesh."""
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = configs.get_tiny("yi-9b")
    tc = ST.TrainConfig(opt=adamw.OptConfig(lr=5e-3, warmup_steps=5,
                                            total_steps=60))
    state, state_sh = ST.init_state(jax.random.PRNGKey(0), cfg, tc, mesh)
    src = SyntheticLM(vocab=cfg.vocab, seq=32, global_batch=4)
    batch0 = device_batch(mesh, src.host_batch(0))
    bsh = {k: v.sharding for k, v in batch0.items()}
    step = ST.make_train_step(cfg, tc, mesh, state_sh, bsh)

    losses = []
    for i in range(30):
        # fixed batch → loss must drop steadily (memorisation)
        state, metrics = step(state, batch0)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::6]
    assert np.isfinite(losses).all()


def test_group_edpp_prunes_ffn_neurons():
    """The bridge experiment: regress a layer's output onto its FFN neuron
    activations (groups = neurons) and let group-EDPP screen inactive ones
    along the path — structured pruning with safety guarantees."""
    r = np.random.default_rng(7)
    n_tokens, n_neurons, m = 80, 64, 2   # m: (in, out) pair per neuron
    acts = r.standard_normal((n_tokens, n_neurons * m))
    w = np.zeros(n_neurons * m)
    important = r.choice(n_neurons, 6, replace=False)
    for g in important:
        w[g * m:(g + 1) * m] = r.uniform(0.5, 1.0, m)
    target = acts @ w + 0.05 * r.standard_normal(n_tokens)

    lmax = float(group_lambda_max(jnp.asarray(acts, jnp.float32),
                                  jnp.asarray(target, jnp.float32), m))
    grid = lambda_grid(lmax, num=10, lo_frac=0.2)
    res = group_lasso_path(acts, target, m, grid,
                           GroupPathConfig(rule="edpp", solver_tol=1e-10))
    # the screened path discards most inactive neuron-groups...
    assert res.stats[2].n_discarded > n_neurons * 0.4
    # ...and never kills an important neuron
    final = res.betas[-1].reshape(n_neurons, m)
    gnorm = np.linalg.norm(final, axis=1)
    assert np.all(gnorm[important] > 1e-6)


def test_serve_streams_100_queries_continuous(subproc):
    """launch/serve.py end-to-end (ISSUE 4 → ISSUE 6): ≥100 synthetic
    queries from the deterministic QueryStream through the continuous-
    batching serve loop, reporting p50/p99 latency and queries/sec, with a
    bounded set of padded batch shapes (pow-2 capped at b_max — no
    per-fill-level recompiles)."""
    out = subproc(
        "from repro.launch.serve import main\n"
        "main(['--n', '30', '--p', '64', '--b-max', '8',\n"
        "      '--num-queries', '104', '--num-lambdas', '4',\n"
        "      '--solver-tol', '1e-5', '--mode', 'continuous'])\n",
        devices=1, timeout=560)
    assert "served 104/104 queries" in out
    assert "queries/sec" in out
    assert "latency p50" in out and "p99" in out
    # bounded program variants: 104 = 13×8 eager queries form full fill
    # batches only → exactly one padded batch shape
    import re
    m = re.search(r"padded batch shapes \[([0-9, ]+)\]", out)
    assert m and len(m.group(1).split(",")) <= 2, out
    assert "errors 0" in out
