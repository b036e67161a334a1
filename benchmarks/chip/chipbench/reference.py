"""The plain reference: Procedure 2's descent over stored node lists, in numpy.

A model is a stack of T node tables in the branchless breadth-first layout
of the paper's Procedure 1: node ``i`` tests ``record[attr[i]] >
threshold[i]`` and moves to ``child[i]`` on false, ``child[i] + 1`` on
true; a leaf has threshold +inf and points at itself, so the descent
needs no branch and stops moving once it reaches a leaf.  A forest's class
is the majority of its trees' classes, ties going to the lowest class.

Nothing here imports the program under test.  ``classify(...,
dtype=BF16)`` is the control: the same descent with records and
thresholds rounded to bfloat16, the nearest precision below the float32
that the configurations state.
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

F32 = np.float32
BF16 = ml_dtypes.bfloat16


@dataclasses.dataclass(frozen=True)
class Model:
    """T node tables, each padded to N nodes with unreachable self-loop leaves."""

    attr: np.ndarray        # (T, N) int32
    threshold: np.ndarray   # (T, N) float32, +inf at leaves
    child: np.ndarray       # (T, N) int32, self at leaves
    cls: np.ndarray         # (T, N) int32, -1 at internal nodes
    n_nodes: tuple[int, ...]    # each tree's node count before padding
    n_attrs: int
    n_classes: int

    @property
    def n_trees(self) -> int:
        return int(self.attr.shape[0])

    @property
    def depth(self) -> int:
        """Edges on the longest root-to-leaf path of any tree."""
        return int(node_depths(self).max())


def node_depths(model: Model) -> np.ndarray:
    """(T, N) depth of every node; breadth-first order puts parents first."""
    t, n = model.attr.shape
    depth = np.zeros((t, n), np.int64)
    internal = model.cls < 0
    for i in range(n):
        rows = np.nonzero(internal[:, i])[0]
        c = model.child[rows, i]
        depth[rows, c] = depth[rows, i] + 1
        depth[rows, c + 1] = depth[rows, i] + 1
    return depth


def from_node_lists(trees: list, n_attrs: int, n_classes: int) -> Model:
    """Build a model from per-tree node lists ``[[attr, threshold, child,
    class], ...]``; a leaf's threshold is ``None`` (+inf)."""
    n = max(len(t) for t in trees)
    shape = (len(trees), n)
    attr = np.zeros(shape, np.int32)
    thr = np.full(shape, np.inf, F32)
    child = np.tile(np.arange(n, dtype=np.int32), (len(trees), 1))
    cls = np.zeros(shape, np.int32)
    for k, nodes in enumerate(trees):
        for i, (a, t, c, v) in enumerate(nodes):
            attr[k, i], child[k, i], cls[k, i] = a, c, v
            thr[k, i] = np.inf if t is None else F32(t)
    return Model(attr, thr, child, cls, tuple(len(t) for t in trees), n_attrs, n_classes)


def descend(model: Model, records: np.ndarray, *, dtype=F32,
            block: int = 8192) -> tuple[np.ndarray, np.ndarray]:
    """Every tree's class for every record, and each record's comparisons.

    Returns ``(classes (T, M) int32, comparisons (M,) int64)``: the number
    of internal nodes on the record's path (its leaf's depth), summed over
    the trees.
    """
    # values rounded to ``dtype`` compare in float32 exactly as in ``dtype``
    rec = np.asarray(records, F32).astype(dtype).astype(F32)
    t, n = model.attr.shape
    m, a = rec.shape
    # one flat table of all trees' nodes; a tree's children are offset with it
    off = (np.arange(t, dtype=np.int64) * n)[:, None]
    attr = model.attr.ravel()
    thr = model.threshold.astype(dtype).astype(F32).ravel()
    child = (model.child + off).ravel()
    cls = model.cls.ravel()
    depth = node_depths(model).ravel()
    out = np.empty((t, m), np.int32)
    comps = np.empty((m,), np.int64)
    for s in range(0, m, block):
        flat = rec[s:s + block].ravel()
        b = flat.shape[0] // a
        base = (np.arange(b, dtype=np.int64) * a)[None, :]
        idx = np.repeat(off, b, axis=1)
        for _ in range(model.depth):
            right = flat[base + attr[idx]] > thr[idx]
            idx = child[idx] + right
        out[:, s:s + b] = cls[idx]
        comps[s:s + b] = depth[idx].sum(axis=0)
    return out, comps


def vote(per_tree: np.ndarray, n_classes: int) -> np.ndarray:
    """Majority class per record; a tie goes to the lowest class."""
    t, m = per_tree.shape
    if t == 1:
        return per_tree[0].astype(np.int32)
    counts = np.zeros((m, n_classes), np.int32)
    cols = np.arange(m)
    for k in range(t):
        counts[cols, per_tree[k]] += 1
    return counts.argmax(axis=1).astype(np.int32)


def classify(model: Model, records: np.ndarray, *, dtype=F32) -> tuple[np.ndarray, np.ndarray]:
    """``(classes (M,) int32, comparisons (M,))`` for one request."""
    per_tree, comps = descend(model, records, dtype=dtype)
    return vote(per_tree, model.n_classes), comps
