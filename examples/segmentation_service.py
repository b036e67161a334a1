"""The paper's own application as an on-line service: real-time image
segmentation with the speculative tree evaluator.

Simulates the paper's procedure-room workload: a stream of 256×256 "images"
(65 536 pixel records each) classified on-line; reports per-image latency
with the speculative kernel — the paper's deterministic-latency argument
(§3.3: "uniform evaluation times needed in deterministic, real-time
applications") shows up as the tight min/max spread.

    PYTHONPATH=src python examples/segmentation_service.py --images 5
"""

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import CartConfig, breadth_first_encode, train_cart, tree_depth
from repro.core.eval_speculative import eval_speculative
from repro.data.segmentation import make_segmentation, replicated_dataset


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=5)
    args = ap.parse_args()

    data = make_segmentation(seed=0)
    root = train_cart(data.x_train, data.y_train, 7,
                      CartConfig(max_depth=12, min_samples_split=8, min_gain=4e-3))
    enc = breadth_first_encode(root)
    d = tree_depth(enc)
    print(f"classifier: N={enc.n_nodes} depth={d} (trained offline, as in the paper)")

    tree_args = (jnp.asarray(enc.attr_idx), jnp.asarray(enc.threshold),
                 jnp.asarray(enc.child), jnp.asarray(enc.class_val))
    classify = jax.jit(lambda r: eval_speculative(
        r, *tree_args, max_depth=d, jumps_per_round=2, use_onehot_matmul=True))

    lat = []
    for i in range(args.images):
        img, _ = replicated_dataset(data, 65_536, seed=i + 1)
        t0 = time.perf_counter()
        classes = np.asarray(classify(jnp.asarray(img)))   # H2D + eval + D2H
        lat.append((time.perf_counter() - t0) * 1e3)
        hist = np.bincount(classes, minlength=7)
        print(f"image {i}: {lat[-1]:7.2f} ms  class histogram {hist.tolist()}")
    a = np.asarray(lat[1:]) if len(lat) > 1 else np.asarray(lat)
    print(f"\nsteady-state latency: mean {a.mean():.2f} ms  "
          f"min {a.min():.2f}  max {a.max():.2f}  "
          f"(spread {(a.max()-a.min())/a.mean()*100:.1f}% — the paper's "
          f"time-uniformity argument)")


if __name__ == "__main__":
    import os

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
