"""End-to-end training driver: a ~100M-parameter LM through the production
stack — sharded train_step, AdamW, deterministic data pipeline, atomic
checkpointing, elastic restart.

    PYTHONPATH=src python examples/train_lm.py --steps 30          # demo
    PYTHONPATH=src python examples/train_lm.py --steps 300 --seq 512

A few hundred steps at the full size is a multi-hour CPU run (it is a real
100M model); the default demo settings show the same code path in minutes.
On TPU the identical script runs on the production mesh (--mesh 16x16).
"""

import argparse
import os
import time

import jax
import numpy as np

from repro.configs.common import dense_lm
from repro.checkpoint import latest_step, restore, save
from repro.core.distributed import make_mesh
from repro.data import SyntheticLM, device_batch
from repro.optim import adamw
from repro.train import steps as ST


def lm_100m(seq_vocab=32000):
    """~103M params: 12L, d=640, 10 heads, d_ff=2560, tied embeddings."""
    return dense_lm("lm-100m", n_layers=12, d_model=640, n_heads=10,
                    n_kv_heads=10, d_head=64, d_ff=2560, vocab=seq_vocab)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tiny", action="store_true",
                    help="4L/d256 variant for smoke runs")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()

    dshape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(dshape, ("data", "model")[: len(dshape)])

    if args.tiny:
        cfg = dense_lm("lm-tiny", n_layers=4, d_model=256, n_heads=4,
                       n_kv_heads=4, d_head=64, d_ff=1024, vocab=8000)
    else:
        cfg = lm_100m()
    tc = ST.TrainConfig(opt=adamw.OptConfig(
        lr=3e-4, warmup_steps=20, total_steps=max(args.steps, 100)))

    state, state_sh = ST.init_state(jax.random.PRNGKey(0), cfg, tc, mesh)
    nparams = sum(np.prod(x.shape, dtype=np.float64)
                  for x in jax.tree.leaves(state.params))
    print(f"model {cfg.name}: {nparams/1e6:.1f}M params, mesh {dshape}")

    src = SyntheticLM(vocab=cfg.vocab, seq=args.seq, global_batch=args.batch)
    batch0 = device_batch(mesh, src.host_batch(0))
    bsh = {k: v.sharding for k, v in batch0.items()}
    step_fn = ST.make_train_step(cfg, tc, mesh, state_sh, bsh)

    start = 0
    last = latest_step(args.ckpt_dir)
    if last is not None:
        print(f"resuming from checkpoint step {last}")
        state, _ = restore(args.ckpt_dir, last, state, shardings=state_sh)
        start = last

    t_tokens = 0
    t0 = time.perf_counter()
    for i in range(start, args.steps):
        batch = device_batch(mesh, src.host_batch(i))
        state, metrics = step_fn(state, batch)
        t_tokens += args.batch * args.seq
        if i % 5 == 0 or i == args.steps - 1:
            dt = time.perf_counter() - t0
            print(f"step {i:4d}  loss {float(metrics['loss']):7.4f}"
                  f"  lr {float(metrics['lr']):.2e}"
                  f"  {t_tokens/max(dt,1e-9):,.0f} tok/s")
        if (i + 1) % args.ckpt_every == 0 or i == args.steps - 1:
            save(args.ckpt_dir, i + 1, state)
    print("done; checkpoints in", args.ckpt_dir)


if __name__ == "__main__":
    main()
