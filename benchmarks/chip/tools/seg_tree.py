#!/usr/bin/env python3
"""Make the node list stored in ``configs/seg_cart.json``: a CART tree of the
paper's shape (N=31 nodes, 16 leaves, depth 11) trained on the synthetic
twin of UCI Image Segmentation.

    python3 benchmarks/chip/tools/seg_tree.py   # prints the node list

The tree grows best first: of all open leaves, the one whose best split
(``repro.core.cart``'s Gini search) gains most is split next, until there
are 16 leaves.  Twin seeds are tried in order from 0; the first whose tree
has the paper's depth 11 is kept (seed 2).  Run once; the benchmark reads
the stored list and never this script.
"""

from __future__ import annotations

import heapq
import itertools
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core.cart import CartConfig, _best_split, _majority  # noqa: E402
from repro.core.tree import BOTTOM, Node, breadth_first_encode  # noqa: E402
from repro.data.segmentation import make_segmentation  # noqa: E402

LEAVES, DEPTH, N_CLASSES = 16, 11, 7
CFG = CartConfig(max_depth=12, min_samples_split=2, min_gain=4e-3)


def best_first(x: np.ndarray, y: np.ndarray) -> Node:
    x, y = np.asarray(x, np.float64), np.asarray(y, np.int64)
    root = Node(class_val=_majority(y, N_CLASSES))
    heap: list = []
    order = itertools.count()   # ties split the older leaf first

    def push(node, idx, d):
        ys = y[idx]
        if d >= CFG.max_depth or idx.size < CFG.min_samples_split or np.all(ys == ys[0]):
            return
        found = _best_split(x[idx], ys, N_CLASSES, CFG)
        if found is not None and found[0] > CFG.min_gain:
            heapq.heappush(heap, (-found[0], next(order), node, idx, d, found))

    push(root, np.arange(y.size), 0)
    leaves = 1
    while heap and leaves < LEAVES:
        *_, node, idx, d, (_, a, thr) = heapq.heappop(heap)
        right = x[idx, a] > thr
        li, ri = idx[~right], idx[right]
        node.attr, node.threshold, node.class_val = a, thr, BOTTOM
        node.left = Node(class_val=_majority(y[li], N_CLASSES))
        node.right = Node(class_val=_majority(y[ri], N_CLASSES))
        leaves += 1
        push(node.left, li, d + 1)
        push(node.right, ri, d + 1)
    return root


def main() -> int:
    for seed in range(16):
        data = make_segmentation(seed=seed)
        root = best_first(data.x_train, data.y_train)
        if root.depth() == DEPTH:
            break
    else:
        raise SystemExit("no twin seed gives the paper's depth")
    enc = breadth_first_encode(root)
    nodes = [[int(enc.attr_idx[i]),
              None if enc.class_val[i] >= 0 else float(enc.threshold[i]),
              int(enc.child[i]), int(enc.class_val[i])] for i in range(enc.n_nodes)]
    print(json.dumps({"twin_seed": seed, "n_nodes": enc.n_nodes, "depth": root.depth(),
                      "trees": [nodes]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
