"""End-to-end training driver: a small LM trained for a few hundred steps
with the production substrate (prefetch pipeline, async checkpoints,
restart manager) — the same code path launch/train.py uses on a pod.

    PYTHONPATH=src python examples/train_lm.py --steps 200

By default trains a ~10M-param starcoder2-family model on CPU (a 100M
model is a flag away: --dmodel 768 --layers 12 — sized for real hardware).
"""
import argparse
import dataclasses
import time
from pathlib import Path

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, reduced
from repro.data.generators import token_batches
from repro.data.pipeline import PrefetchPipeline
from repro.models import build_model
from repro.train import OptConfig, make_train_step
from repro.train.checkpoint import AsyncCheckpointer
from repro.train.train_step import init_train_state


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dmodel", type=int, default=384)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--ckpt-dir", type=Path,
                    default=Path("/tmp/repro_train_lm"))
    args = ap.parse_args()

    cfg = dataclasses.replace(
        reduced(ARCHS["starcoder2-7b"]),
        d_model=args.dmodel, num_layers=args.layers,
        d_ff=args.dmodel * 4, num_heads=max(args.dmodel // 64, 1),
        num_kv_heads=max(args.dmodel // 256, 1), vocab_size=8192,
    )
    model = build_model(cfg)
    print(f"training {cfg.param_count() / 1e6:.1f}M params, "
          f"{args.steps} steps of {args.batch}x{args.seq} tokens")

    state = init_train_state(model, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(model, OptConfig(
        lr=1e-3, warmup_steps=20, total_steps=args.steps)))
    data = PrefetchPipeline(
        token_batches(cfg.vocab_size, args.batch, args.seq), depth=2)
    ckpt = AsyncCheckpointer(args.ckpt_dir, keep=2)

    t0 = time.time()
    losses = []
    for step in range(args.steps):
        state, metrics = step_fn(state, next(data))
        losses.append(float(metrics["loss"]))
        if (step + 1) % 20 == 0:
            rate = args.batch * args.seq * (step + 1) / (time.time() - t0)
            print(f"step {step + 1:4d} loss={losses[-1]:.4f} "
                  f"({rate:,.0f} tok/s)")
        if (step + 1) % 100 == 0:
            ckpt.save(state, step + 1)
    ckpt.wait()
    data.close()
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"(random = {np.log(cfg.vocab_size):.3f}) in "
          f"{time.time() - t0:.0f}s; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
