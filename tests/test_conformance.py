"""Cross-variant conformance suite (ISSUE 10 satellite).

Every registered evaluator — the serial/data-parallel/speculative references,
every tree ``VARIANTS`` entry, every ``FOREST_VARIANTS`` entry (including the
quantized layouts), and the cascade — runs over a shared set of adversarial
fixtures and must be *class-exact* against ``tree_eval_ref`` /
``forest_eval_ref``.  No tolerance anywhere: the paper's encoding is
branchless integer routing, so any numeric drift is a bug, not noise.

Fixture trees: deep, shallow, skewed, degenerate single-leaf, and a tree
where many nodes share one threshold.  Fixture records inject ±inf and NaN
attribute values (NaN compares false on ``v > t`` → routes left) plus rows
that hit thresholds exactly (the ``<=`` / ``>`` tie-break).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    Node,
    breadth_first_encode,
    eval_data_parallel_tree,
    eval_serial,
    eval_speculative_tree,
    majority_vote,
    random_tree,
    tree_depth,
)
from repro.core.forest import EncodedForest
from repro.kernels.tree_eval import eval_cascade
from repro.kernels.tree_eval.ops import (
    FOREST_VARIANTS,
    LANE,
    VARIANTS,
    PackedForest,
    PackedTree,
    _round_up,
    block_m_fits,
    forest_eval_fused,
    forest_votes_fused,
    tree_eval,
)
from repro.kernels.tree_eval.quant import THR_DTYPES, QuantizedForest
from repro.kernels.tree_eval.ref import forest_eval_ref, tree_eval_ref
from repro.kernels.tree_eval.ops import forest_eval_fused_q

N_ATTRS = 7
N_CLASSES = 5
M = 96  # small enough for interpret-mode Pallas, large enough to tile


def _duplicate_threshold_tree() -> Node:
    """Depth-3 full tree where every internal node splits at the same 0.5."""
    def leaf(c):
        return Node(class_val=c)

    def split(attr, left, right):
        return Node(attr=attr, threshold=0.5, left=left, right=right)

    return split(
        0,
        split(1, split(2, leaf(0), leaf(1)), split(3, leaf(2), leaf(3))),
        split(2, split(4, leaf(4), leaf(0)), split(1, leaf(1), leaf(2))),
    )


def _fixture_trees() -> dict[str, Node]:
    return {
        "deep": random_tree(
            n_attrs=N_ATTRS, n_classes=N_CLASSES, max_depth=8, min_depth=6, seed=7
        ),
        "shallow": random_tree(
            n_attrs=N_ATTRS, n_classes=N_CLASSES, max_depth=1, min_depth=1, seed=8
        ),
        "skewed": random_tree(
            n_attrs=N_ATTRS, n_classes=N_CLASSES, max_depth=9, min_depth=2,
            seed=9, balance=0.15,
        ),
        "single_leaf": Node(class_val=3),
        "duplicate_threshold": _duplicate_threshold_tree(),
    }


TREES = {name: breadth_first_encode(root) for name, root in _fixture_trees().items()}
FOREST = EncodedForest(list(TREES.values()))


def _records() -> np.ndarray:
    """(M, A) float32 records with adversarial rows up front."""
    rng = np.random.default_rng(2026)
    rec = rng.normal(size=(M, N_ATTRS)).astype(np.float32)
    # Tie-break rows: attribute exactly equal to the shared 0.5 threshold and
    # to 0.0 (random_tree thresholds are continuous, 0.5 hits the duplicate
    # tree).  v > t must be False on equality → route left, on every path.
    rec[0, :] = 0.5
    rec[1, :] = 0.0
    # ±inf: +inf always routes right past any finite threshold; -inf left.
    rec[2, :] = np.inf
    rec[3, :] = -np.inf
    rec[4, ::2] = np.inf
    rec[4, 1::2] = -np.inf
    # NaN compares false on v > t → must route left like the reference.
    rec[5, :] = np.nan
    rec[6, ::3] = np.nan
    # A mixed row: NaN next to ±inf next to an exact threshold hit.
    rec[7, 0] = np.nan
    rec[7, 1] = np.inf
    rec[7, 2] = -np.inf
    rec[7, 3] = 0.5
    return rec


RECORDS = _records()


def _tree_ref(enc) -> np.ndarray:
    return np.asarray(
        tree_eval_ref(
            jnp.asarray(RECORDS),
            jnp.asarray(enc.attr_idx, jnp.int32),
            jnp.asarray(enc.threshold, jnp.float32),
            jnp.asarray(enc.child, jnp.int32),
            jnp.asarray(enc.class_val, jnp.int32),
            max_depth=max(tree_depth(enc), 1),
        )
    )


TREE_REFS = {name: _tree_ref(enc) for name, enc in TREES.items()}
FOREST_REF = np.asarray(
    forest_eval_ref(
        jnp.asarray(RECORDS),
        jnp.asarray(FOREST.attr_idx, jnp.int32),
        jnp.asarray(FOREST.threshold, jnp.float32),
        jnp.asarray(FOREST.child, jnp.int32),
        jnp.asarray(FOREST.class_val, jnp.int32),
        max_depth=max(int(FOREST.max_depth), 1),
    )
)


def _refused(spec, n_nodes: int) -> bool:
    """A Pallas variant whose tile cannot fit VMEM at this width (the onehot
    jump at N = 512) refuses the call rather than running."""
    return spec.engine == "pallas" and not block_m_fits(
        _round_up(n_nodes, LANE), _round_up(N_ATTRS, LANE), jump_mode=spec.jump_mode)


def _assert_exact(got, want, label: str) -> None:
    got = np.asarray(got)
    assert got.shape == want.shape, f"{label}: shape {got.shape} != {want.shape}"
    assert got.dtype.kind == "i", f"{label}: non-integer class output {got.dtype}"
    if not np.array_equal(got, want):
        bad = np.argwhere(got != want)
        raise AssertionError(
            f"{label}: {bad.shape[0]} mismatches vs reference, first at "
            f"{bad[0].tolist()}: got {got[tuple(bad[0])]} want {want[tuple(bad[0])]}"
        )


# ---------------------------------------------------------------------------
# Core reference evaluators agree with the serial ground truth
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture", sorted(TREES))
def test_eval_serial_conforms(fixture):
    enc = TREES[fixture]
    _assert_exact(eval_serial(enc, RECORDS), TREE_REFS[fixture], f"eval_serial/{fixture}")


@pytest.mark.parametrize("fixture", sorted(TREES))
def test_eval_data_parallel_conforms(fixture):
    enc = TREES[fixture]
    got = eval_data_parallel_tree(enc, RECORDS, max_depth=max(tree_depth(enc), 1))
    _assert_exact(got, TREE_REFS[fixture], f"eval_data_parallel/{fixture}")


@pytest.mark.parametrize("fixture", sorted(TREES))
@pytest.mark.parametrize("jumps", [1, 2, 3])
def test_eval_speculative_conforms(fixture, jumps):
    enc = TREES[fixture]
    got = eval_speculative_tree(
        enc, RECORDS, max_depth=max(tree_depth(enc), 1), jumps_per_round=jumps
    )
    _assert_exact(got, TREE_REFS[fixture], f"eval_speculative/{fixture}/j{jumps}")


# ---------------------------------------------------------------------------
# Every registered tree variant, over every fixture
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture", sorted(TREES))
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_tree_variant_conforms(variant, fixture):
    spec = VARIANTS[variant]
    enc = TREES[fixture]
    call = lambda: spec.fn(jnp.asarray(RECORDS), enc, max_depth=max(tree_depth(enc), 1))  # noqa: E731
    if _refused(spec, enc.n_nodes):
        with pytest.raises(ValueError, match="no record tile fits"):
            call()
        return
    _assert_exact(call(), TREE_REFS[fixture], f"{variant}/{fixture}")


# ---------------------------------------------------------------------------
# Every registered forest variant (f32 and quantized layouts) on the
# mixed-fixture forest — per-tree outputs class-exact against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", sorted(FOREST_VARIANTS))
def test_forest_variant_conforms(variant):
    spec = FOREST_VARIANTS[variant]
    call = lambda: spec.fn(  # noqa: E731
        jnp.asarray(RECORDS), FOREST, max_depth=max(int(FOREST.max_depth), 1)
    )
    if _refused(spec, FOREST.n_nodes):
        with pytest.raises(ValueError, match="no record tile fits"):
            call()
        return
    _assert_exact(call(), FOREST_REF, variant)


@pytest.mark.parametrize("thr_dtype", sorted(THR_DTYPES))
@pytest.mark.parametrize("renumber", [False, True])
def test_quantized_forest_prebuilt_conforms(thr_dtype, renumber):
    """Prebuilt QuantizedForest targets (both dtypes × renumbering) stay exact."""
    qf = QuantizedForest(FOREST, N_ATTRS, thr_dtype=thr_dtype, renumber=renumber)
    for alg in ("speculative", "data_parallel"):
        got = forest_eval_fused_q(jnp.asarray(RECORDS), qf, algorithm=alg)
        _assert_exact(got, FOREST_REF, f"quant/{thr_dtype}/renumber={renumber}/{alg}")


@pytest.mark.parametrize("thr_dtype", sorted(THR_DTYPES))
def test_quantized_forest_split_safe_conforms(thr_dtype):
    """Calibrated (split-safe) rounding must preserve calibration routing.

    NaN/±inf rows stay out of the calibration set (as real feature matrices
    would be cleaned) but are still *evaluated* — split-safe rounding only
    guarantees the calibration set, and finite-threshold routing of ±inf/NaN
    is dtype-independent, so the full fixture batch must stay exact too.
    """
    finite = RECORDS[np.all(np.isfinite(RECORDS), axis=1)]
    qf = QuantizedForest(
        FOREST, N_ATTRS, thr_dtype=thr_dtype, calibration=finite
    )
    got = forest_eval_fused_q(jnp.asarray(RECORDS), qf)
    _assert_exact(got, FOREST_REF, f"quant-split-safe/{thr_dtype}")


# ---------------------------------------------------------------------------
# The padding traced into each jitted entry point: ragged M (never a whole
# tile), A off the 128-lane grid, non-finite attributes — every kernel path
# on prebuilt tables, against the serial reference
# ---------------------------------------------------------------------------

def _padding_case(m: int, a: int):
    """Three trees over ``a`` attributes and (m, a) records whose first rows
    carry NaN, ±inf and mixed non-finite attributes."""
    trees = [breadth_first_encode(random_tree(n_attrs=a, n_classes=N_CLASSES,
                                              max_depth=d, seed=a + d))
             for d in (2, 5, 7)]
    rec = np.random.default_rng(m + a).normal(size=(m, a)).astype(np.float32)
    rec[0, ::3], rec[0, 1::3], rec[0, 2::3] = np.nan, np.inf, -np.inf
    rec[1:2, :] = np.inf
    rec[2:3, :] = -np.inf
    rec[3:4, :] = np.nan
    return trees, rec


@pytest.mark.parametrize("a", [19, 130])
@pytest.mark.parametrize("m", [1, 200, 1000])
@pytest.mark.parametrize("algorithm", ["speculative", "data_parallel"])
@pytest.mark.parametrize("path", ["tree", "forest", "quant", "votes"])
def test_jitted_padding_conforms(path, algorithm, m, a):
    trees, rec = _padding_case(m, a)
    want = np.stack([eval_serial(t, rec) for t in trees])      # (T, M)
    forest = EncodedForest(trees)
    label = f"{path}/{algorithm}/m={m}/a={a}"
    if path == "tree":
        got = np.stack([tree_eval(jnp.asarray(rec), PackedTree(t, a), algorithm=algorithm)
                        for t in trees])
    elif path == "forest":
        got = forest_eval_fused(jnp.asarray(rec), PackedForest(forest, a), algorithm=algorithm)
    elif path == "quant":
        got = forest_eval_fused_q(jnp.asarray(rec), QuantizedForest(forest, a),
                                  algorithm=algorithm)
    else:
        got = forest_votes_fused(jnp.asarray(rec), PackedForest(forest, a),
                                 n_classes=N_CLASSES, algorithm=algorithm)
        want = np.stack([np.bincount(col, minlength=N_CLASSES) for col in want.T])
    _assert_exact(got, want.astype(np.int32), label)


# ---------------------------------------------------------------------------
# Cascade at bound=1.0 (no early exit) equals the full majority vote
# ---------------------------------------------------------------------------

def test_cascade_conforms():
    want = np.asarray(majority_vote(jnp.asarray(FOREST_REF), N_CLASSES))
    result = eval_cascade(FOREST, jnp.asarray(RECORDS), n_classes=N_CLASSES, bound=1.0)
    _assert_exact(result.classes, want, "cascade/bound=1.0")
