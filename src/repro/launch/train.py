"""Production training entrypoint.

    PYTHONPATH=src python -m repro.launch.train --arch yi-9b --tiny \
        --steps 20 --seq 64 --batch 4 --mesh 1x1

Any assigned architecture is selectable with --arch (deliverable f); --tiny
swaps in the reduced config for CPU runs. On a pod, --mesh 16x16 with the
full config is the real run; checkpointing + elastic restart come from
repro.checkpoint / repro.runtime.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.checkpoint import latest_step, restore, save
from repro.core.distributed import make_mesh
from repro.data import SyntheticLM, device_batch
from repro.optim import adamw
from repro.train import steps as ST


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCHS))
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()

    shape = tuple(int(x) for x in args.mesh.split("x"))
    names = ("pod", "data", "model")[-len(shape):]
    mesh = make_mesh(shape, names)

    cfg = configs.get_tiny(args.arch) if args.tiny \
        else configs.get_config(args.arch)
    tc = ST.TrainConfig(accum_steps=args.accum, opt=adamw.OptConfig(
        lr=args.lr, warmup_steps=max(args.steps // 10, 2),
        total_steps=max(args.steps, 100)))

    state, state_sh = ST.init_state(jax.random.PRNGKey(0), cfg, tc, mesh)
    n = sum(np.prod(x.shape, dtype=np.float64)
            for x in jax.tree.leaves(state.params))
    print(f"{cfg.name}: {n/1e6:.1f}M params on mesh {shape}")

    src = SyntheticLM(vocab=cfg.vocab, seq=args.seq,
                      global_batch=args.batch, frontend=cfg.frontend,
                      d_frame=cfg.d_frame, d_patch=cfg.d_patch,
                      n_img_tokens=cfg.n_img_tokens)
    b0 = device_batch(mesh, src.host_batch(0))
    bsh = {k: v.sharding for k, v in b0.items()}
    step_fn = ST.make_train_step(cfg, tc, mesh, state_sh, bsh)

    start = 0
    if args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            state, _ = restore(args.ckpt_dir, last, state,
                               shardings=state_sh)
            start = last
            print(f"resumed from step {last}")

    t0 = time.perf_counter()
    for i in range(start, args.steps):
        state, metrics = step_fn(state, device_batch(mesh,
                                                     src.host_batch(i)))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(metrics['loss']):7.4f} "
                  f"lr {float(metrics['lr']):.2e}")
        if args.ckpt_dir and ((i + 1) % args.ckpt_every == 0
                              or i == args.steps - 1):
            save(args.ckpt_dir, i + 1, state)
    dt = time.perf_counter() - t0
    print(f"{args.steps - start} steps in {dt:.1f}s "
          f"({(args.steps - start) * args.batch * args.seq / dt:,.0f} tok/s)")


if __name__ == "__main__":
    main()
