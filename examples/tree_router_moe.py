"""The paper's technique on the LM serving hot path: tree-routed MoE.

Trains a small phi3.5-family MoE whose router is a SOFT decision tree
(differentiable), then serves it with the router HARDENED into the paper's
breadth-first branchless encoding and evaluated with speculative pointer
jumping (Procedure 4/5) — per-token classification into E experts, exactly
the paper's image-segmentation problem shape transposed to tokens.

    PYTHONPATH=src python examples/tree_router_moe.py --steps 60
"""

import argparse

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ShapeConfig, TrainConfig
from repro.configs.registry import get_smoke_config
from repro.data.pipeline import pipeline_for
from repro.models.api import build_model
from repro.models.layers import moe as moel
from repro.optim.adamw import adamw_init
from repro.train.step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()

    cfg = get_smoke_config("phi3.5-moe-42b-a6.6b")
    assert cfg.moe.router == "tree"
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    print(f"MoE: {cfg.moe.n_experts} experts, top-{cfg.moe.top_k}, "
          f"router = depth-{cfg.moe.tree_depth()} soft decision tree")

    # --- train with the soft (differentiable) tree router ---
    pipe = pipeline_for(cfg, ShapeConfig("t", 64, 4, "train"))
    step = jax.jit(make_train_step(model, TrainConfig(lr=2e-3, warmup_steps=5,
                                                      total_steps=args.steps)))
    opt = adamw_init(params)
    first = last = None
    for i in range(args.steps):
        batch = jax.tree.map(jnp.asarray, pipe(i))
        params, opt, metrics = step(params, opt, batch)
        if i == 0:
            first = float(metrics["loss"])
        last = float(metrics["loss"])
        if i % 20 == 0:
            print(f"  step {i:3d}  loss {last:.4f}  aux {float(metrics['aux']):.5f}")
    print(f"soft-tree training: loss {first:.3f} -> {last:.3f}")

    # --- serve: harden the tree, route with speculative evaluation ---
    batch = jax.tree.map(jnp.asarray, pipe(999))
    lp0 = jax.tree.map(lambda x: x[0], params["layers"])["moe"]
    e_pad = lp0["wi"].shape[0]
    x = jax.random.normal(jax.random.key(1), (1, 512, cfg.d_model), jnp.float32)
    experts_hard = moel.hard_tree_route(lp0, x, cfg=cfg, e_pad=e_pad)
    probs_soft = moel.router_probs(lp0, x, cfg=cfg, e_pad=e_pad)
    agree = float((jnp.argmax(probs_soft, -1) == experts_hard).mean())
    # NOTE: greedy hard descent equals the soft argmax only where gates are
    # saturated (σ far from 0.5); at temperature 1.0 mid-training some tokens
    # sit near decision boundaries.  As τ→0 agreement → 100 %
    # (property-tested in tests/test_cart_and_forest.py).
    z = x.astype(jnp.float32) @ lp0["router_proj"] - lp0["router_thr"]
    saturated = float((jnp.abs(jax.nn.sigmoid(z) - 0.5) > 0.4).mean())
    print(f"hardened speculative router vs soft argmax agreement: {agree:.1%} "
          f"(gates saturated: {saturated:.1%})")

    counts = np.bincount(np.asarray(experts_hard).ravel(), minlength=cfg.moe.n_experts)
    print(f"expert load (hard routing): {counts.tolist()}")

    # full serving forward with the hard router
    logits, _ = model.forward(params, batch, serve_hard_tree=True)
    print(f"served logits: {logits.shape}, finite={bool(jnp.isfinite(logits).all())}")
    assert agree > 0.5, "hardening should track the learned routing"
    assert len([c for c in counts if c > 0]) >= 2, "router must use several experts"


if __name__ == "__main__":
    import os

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
