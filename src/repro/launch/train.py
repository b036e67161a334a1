"""Production training launcher.

On real hardware this runs under ``jax.distributed`` with one process per
host; in this container it runs the same code on the local device(s):

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \\
        --steps 50 --seq 128 --batch 4

``--smoke`` selects the reduced config; omit on a real pod slice to train
the assigned architecture at full size with the production mesh/shardings.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ParallelConfig, ShapeConfig, TrainConfig
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.data.pipeline import pipeline_for
from repro.launch.mesh import auto_mesh
from repro.models.api import build_model
from repro.optim.adamw import adamw_init
from repro.parallel import sharding as shd
from repro.train.loop import LoopState, train_loop
from repro.train.step import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=ARCH_IDS + list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="auto", help="auto | dxm e.g. 16x16")
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.mesh == "auto":
        n = jax.device_count()
        mesh = auto_mesh((n, 1), ("data", "model"))
    else:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = auto_mesh((d, m), ("data", "model"))
    axes = shd.from_mesh(mesh)
    model = build_model(cfg, axes, ParallelConfig())
    print(f"arch={cfg.name} params={cfg.n_params()/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    tcfg = TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                       total_steps=args.steps, ckpt_every=args.ckpt_every,
                       ckpt_dir=args.ckpt_dir)
    with mesh:
        params = model.init(jax.random.key(0))
        params = jax.device_put(params, shd.tree_named(mesh, model.param_specs()))
        step = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))
        pipe = pipeline_for(cfg, ShapeConfig("train", args.seq, args.batch, "train"))
        batches = lambda i: jax.tree.map(jnp.asarray, pipe(i))
        state = LoopState(params=params, opt_state=adamw_init(params), step=0)
        t0 = time.perf_counter()
        state, report = train_loop(state, step, batches, tcfg, max_steps=args.steps)
    dt = time.perf_counter() - t0
    print(f"\n{report.final_step} steps in {dt:.1f}s "
          f"({args.steps * args.seq * args.batch / dt:,.0f} tok/s); "
          f"loss {report.losses[0]:.3f} -> {report.losses[-1]:.3f}; "
          f"restarts={report.restarts} stragglers={report.stragglers}")


if __name__ == "__main__":
    main()
