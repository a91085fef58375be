"""Group-EDPP structured pruning of a trained LM's FFN neurons — the
framework bridge between the paper's technique and the architecture zoo
(DESIGN §5.1).

Recipe:
  1. train a tiny LM for a few steps (production train_step);
  2. collect FFN hidden activations H ∈ R^{tokens × d_ff} of one layer and
     the layer's output contribution t = H·W_out (per output dim, we fit the
     pooled target);
  3. group Lasso over neuron groups (each neuron's activation column),
     solved along a λ path with group-EDPP screening (Cor. 21) — safely
     discarding neurons whose optimal weight is exactly zero;
  4. report the neuron-sparsity/reconstruction trade-off curve.

    PYTHONPATH=src python examples/prune_ffn.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.common import dense_lm
from repro.core import (GroupPathConfig, group_lambda_max, group_lasso_path,
                        lambda_grid)
from repro.core.distributed import make_mesh
from repro.data import SyntheticLM, device_batch
from repro.models import model as M
from repro.models.layers import ffn_forward, rmsnorm
from repro.optim import adamw
from repro.train import steps as ST


def main():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = dense_lm("prunable", n_layers=2, d_model=128, n_heads=4,
                   n_kv_heads=4, d_head=32, d_ff=256, vocab=4000)
    tc = ST.TrainConfig(opt=adamw.OptConfig(lr=3e-3, warmup_steps=5,
                                            total_steps=60))
    state, state_sh = ST.init_state(jax.random.PRNGKey(0), cfg, tc, mesh)
    src = SyntheticLM(vocab=cfg.vocab, seq=64, global_batch=4)
    b0 = device_batch(mesh, src.host_batch(0))
    bsh = {k: v.sharding for k, v in b0.items()}
    step = ST.make_train_step(cfg, tc, mesh, state_sh, bsh)
    for i in range(30):
        state, metrics = step(state, device_batch(mesh, src.host_batch(i)))
    print(f"trained tiny LM to loss {float(metrics['loss']):.3f}")

    # --- extract layer-0 FFN hidden activations on a probe batch ---------
    params = state.params
    batch = src.host_batch(99)
    x = jnp.take(params["embed"], jnp.asarray(batch["tokens"]), axis=0)
    lp = jax.tree.map(lambda a: a[0], params["segments"][0])["b0"]
    blk = cfg.segments[0].blocks[0]
    from repro.models.model import _block_forward
    # hidden pre-activations of the FFN: recompute the block's FFN input
    h2 = rmsnorm(lp["norm2"], x)
    w_in, w_gate = lp["ffn"]["w_in"], lp["ffn"]["w_gate"]
    hidden = jax.nn.silu(h2 @ w_gate) * (h2 @ w_in)       # (B,S,d_ff)
    target = hidden @ lp["ffn"]["w_out"]                  # (B,S,d)

    tokens = hidden.reshape(-1, cfg.segments[0].blocks[0].ffn.d_ff)
    tgt = np.asarray(target.reshape(-1, cfg.d_model))
    # pool the multi-output regression to a single response (first PC proxy)
    y = tgt @ (tgt.std(0) / np.linalg.norm(tgt.std(0)))
    H = np.asarray(tokens, np.float64)
    y = np.asarray(y, np.float64)

    m = 1                                    # group = one neuron column
    lmax = float(group_lambda_max(jnp.asarray(H), jnp.asarray(y), m))
    grid = lambda_grid(lmax, num=20, lo_frac=0.02)
    res = group_lasso_path(H, y, m, grid,
                           GroupPathConfig(rule="edpp", solver_tol=1e-10))

    print("\n  λ/λmax   neurons kept   screened-out   recon-R²")
    for k in [2, 6, 10, 14, 19]:
        beta = res.betas[k]
        kept = int((np.abs(beta) > 1e-9).sum())
        pred = H @ beta
        r2 = 1 - ((y - pred) ** 2).sum() / ((y - y.mean()) ** 2).sum()
        print(f"  {grid[k]/lmax:6.2f}   {kept:12d}   "
              f"{res.stats[k].n_discarded:11d}   {r2:8.3f}")
    print("\ngroup-EDPP screened the inactive neurons SAFELY — kept set is "
          "exactly the group-lasso support at each λ.")


if __name__ == "__main__":
    main()
