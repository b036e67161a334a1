"""End-to-end driver: train a ~100M-param LM for a few hundred steps on CPU.

Uses a scaled-down xlstm-family config (~100M params at full vocab) through
the REAL production path: config → model → data pipeline → fault-tolerant
train loop with async checkpointing — the same code the 512-chip launch uses.

    PYTHONPATH=src python examples/train_lm.py --steps 200 --d-model 256
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.data.pipeline import pipeline_for
from repro.models.api import build_model
from repro.optim.adamw import adamw_init
from repro.train.loop import LoopState, train_loop
from repro.train.step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = ModelConfig(
        name="train-demo", family="dense", n_layers=args.layers,
        d_model=args.d_model, n_heads=max(args.d_model // 64, 1),
        n_kv_heads=max(args.d_model // 128, 1), d_ff=args.d_model * 4,
        vocab_size=args.vocab, dtype="float32",
    )
    model = build_model(cfg)
    print(f"model: {cfg.n_params()/1e6:.1f}M params "
          f"({cfg.n_layers}L x {cfg.d_model}d, vocab {cfg.vocab_size})")

    params = model.init(jax.random.key(0))
    tcfg = TrainConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps,
                       ckpt_every=50, ckpt_dir=args.ckpt_dir)
    step = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))
    pipe = pipeline_for(cfg, ShapeConfig("train", args.seq, args.batch, "train"))
    batches = lambda i: jax.tree.map(jnp.asarray, pipe(i))

    state = LoopState(params=params, opt_state=adamw_init(params), step=0)
    t0 = time.perf_counter()
    state, report = train_loop(state, step, batches, tcfg, max_steps=args.steps)
    dt = time.perf_counter() - t0
    tok_s = args.steps * args.seq * args.batch / dt
    print(f"\ntrained {report.final_step} steps in {dt:.1f}s ({tok_s:,.0f} tok/s)")
    print(f"loss: {report.losses[0]:.3f} -> {report.losses[-1]:.3f} "
          f"(stragglers flagged: {report.stragglers}, restarts: {report.restarts})")
    assert report.losses[-1] < report.losses[0], "loss should decrease"


if __name__ == "__main__":
    import os

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
