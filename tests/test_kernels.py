"""Pallas tree-evaluation kernels vs the pure-jnp oracle (ref.py).

Shape/dtype sweeps in interpret mode per the kernel-validation contract:
records M ∈ {1, 7, 8, 100, 1000}, attrs A ∈ {1, 19, 130}, trees from depth 1
to 10, dtypes f32/bf16, both algorithms × both jump modes.
"""

import numpy as np
import pytest
import jax.numpy as jnp

# hypothesis is optional: the shim runs a deterministic fixed-example sweep
# when the real package is not installed (see hypothesis_compat.py).
from hypothesis_compat import given, settings, st

from repro.core import breadth_first_encode, paper_tree, random_tree, tree_depth
from repro.kernels.tree_eval import (
    PackedForest,
    PackedTree,
    forest_eval,
    forest_eval_fused,
    tree_eval,
    tree_eval_ref,
)
from repro.kernels.tree_eval.ops import choose_block_m


def _enc(depth=6, attrs=19, seed=0, balance=1.0):
    return breadth_first_encode(
        random_tree(n_attrs=attrs, n_classes=7, max_depth=depth, seed=seed, balance=balance)
    )


def _ref(enc, rec):
    return np.asarray(
        tree_eval_ref(
            jnp.asarray(rec),
            jnp.asarray(enc.attr_idx),
            jnp.asarray(enc.threshold),
            jnp.asarray(enc.child),
            jnp.asarray(enc.class_val),
            max_depth=max(tree_depth(enc), 1),
        )
    )


@pytest.mark.parametrize("algorithm,jump_mode", [
    ("speculative", "gather"),
    ("speculative", "onehot"),
    ("data_parallel", "gather"),
])
@pytest.mark.parametrize("m", [1, 7, 8, 100])
def test_kernel_matches_ref_shapes(algorithm, jump_mode, m):
    enc = _enc(depth=5, seed=2)
    rec = np.random.default_rng(m).normal(size=(m, 19)).astype(np.float32)
    out = np.asarray(tree_eval(rec, enc, algorithm=algorithm, jump_mode=jump_mode))
    assert np.array_equal(out, _ref(enc, rec))


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    enc = _enc(depth=4, seed=5)
    rec = jnp.asarray(
        np.random.default_rng(0).normal(size=(64, 19)), dtype=dtype
    )
    out = np.asarray(tree_eval(rec, enc, algorithm="speculative"))
    ref = _ref(enc, np.asarray(rec, np.float32))
    assert np.array_equal(out, ref)


@given(
    seed=st.integers(0, 60),
    depth=st.integers(1, 10),
    balance=st.floats(0.3, 1.0),
    m=st.integers(1, 200),
    attrs=st.sampled_from([1, 5, 19, 130]),
)
@settings(max_examples=20, deadline=None)
def test_kernel_property_sweep(seed, depth, balance, m, attrs):
    enc = breadth_first_encode(
        random_tree(n_attrs=attrs, n_classes=7, max_depth=depth, seed=seed, balance=balance)
    )
    rec = np.random.default_rng(seed + 1).normal(size=(m, attrs)).astype(np.float32)
    ref = _ref(enc, rec)
    for algorithm in ("speculative", "data_parallel"):
        out = np.asarray(tree_eval(rec, enc, algorithm=algorithm))
        assert np.array_equal(out, ref), algorithm


def test_large_tree_multi_lane_blocks():
    """N > 128 exercises the lane-padded multi-block tree layout."""
    enc = _enc(depth=8, seed=9)          # perfect depth-8: 511 nodes > 128
    assert enc.n_nodes > 128
    rec = np.random.default_rng(3).normal(size=(256, 19)).astype(np.float32)
    out = np.asarray(tree_eval(rec, enc, algorithm="speculative"))
    assert np.array_equal(out, _ref(enc, rec))


def test_paper_tree_kernel_all_paths():
    enc = breadth_first_encode(paper_tree())
    rec = np.random.default_rng(4).normal(size=(1024, 19)).astype(np.float32)
    ref = _ref(enc, rec)
    for alg, jm in [("speculative", "gather"), ("speculative", "onehot"), ("data_parallel", "gather")]:
        assert np.array_equal(np.asarray(tree_eval(rec, enc, algorithm=alg, jump_mode=jm)), ref)


def test_forest_eval_kernel():
    trees = [_enc(depth=d, seed=d) for d in (3, 5, 7)]
    packed = [PackedTree(t, 19) for t in trees]
    rec = np.random.default_rng(5).normal(size=(128, 19)).astype(np.float32)
    out = np.asarray(forest_eval(rec, packed))
    assert out.shape == (3, 128)
    for i, t in enumerate(trees):
        assert np.array_equal(out[i], _ref(t, rec))


@pytest.mark.parametrize("algorithm,jump_mode", [
    ("speculative", "gather"),
    ("speculative", "onehot"),
    ("data_parallel", "gather"),
])
@pytest.mark.parametrize("m", [1, 7, 100])
def test_fused_forest_kernel_matches_ref(algorithm, jump_mode, m):
    """The fused stacked-forest launch is bit-identical to tree-by-tree
    evaluation for every algorithm × jump mode × ragged record count."""
    from repro.core.forest import EncodedForest

    trees = [_enc(depth=d, seed=10 + d) for d in (2, 5, 7)]
    forest = EncodedForest(trees)
    rec = np.random.default_rng(m).normal(size=(m, 19)).astype(np.float32)
    out = np.asarray(
        forest_eval_fused(rec, forest, algorithm=algorithm, jump_mode=jump_mode)
    )
    assert out.shape == (3, m)
    assert out.dtype == np.int32
    for i in range(3):
        assert np.array_equal(out[i], _ref(forest.tree(i), rec))


def test_fused_forest_packed_reuse_and_block_m():
    """A prebuilt PackedForest (the dispatch fast path) and explicit block_m
    overrides produce the same bits as the one-shot call."""
    from repro.core.forest import EncodedForest

    trees = [_enc(depth=d, seed=20 + d) for d in (3, 6)]
    forest = EncodedForest(trees)
    rec = np.random.default_rng(9).normal(size=(130, 19)).astype(np.float32)
    ref = np.asarray(forest_eval_fused(rec, forest))
    packed = PackedForest(forest, 19)
    assert np.array_equal(np.asarray(forest_eval_fused(rec, packed)), ref)
    for bm in (8, 32):
        assert np.array_equal(
            np.asarray(forest_eval_fused(rec, packed, block_m=bm)), ref
        )


def test_block_m_vmem_model():
    """BlockSpec sizing: chosen tile must fit the VMEM budget model."""
    bm = choose_block_m(128, 128)
    assert bm >= 8 and bm & (bm - 1) == 0      # power of two, ≥ sublane
    bm_big_tree = choose_block_m(1024, 256)
    assert bm_big_tree <= bm
    bm_onehot = choose_block_m(256, 128, jump_mode="onehot")
    assert bm_onehot <= choose_block_m(256, 128, jump_mode="gather")


def test_explicit_block_m_override():
    enc = _enc(depth=4, seed=11)
    rec = np.random.default_rng(6).normal(size=(64, 19)).astype(np.float32)
    for bm in (8, 16, 64):
        out = np.asarray(tree_eval(rec, enc, algorithm="speculative", block_m=bm))
        assert np.array_equal(out, _ref(enc, rec))


def test_block_m_refuses_when_no_tile_fits():
    """No fallback tile: where nothing fits the budget, choose_block_m raises."""
    from repro.kernels.tree_eval.ops import VMEM_BUDGET, block_m_fits, vmem_bytes

    assert not block_m_fits(512, 128, jump_mode="onehot")
    with pytest.raises(ValueError, match="no record tile fits"):
        choose_block_m(512, 128, jump_mode="onehot")
    for n in (128, 512, 1024):
        for jm in ("gather", "onehot"):
            if block_m_fits(n, 128, jump_mode=jm):
                bm = choose_block_m(n, 128, jump_mode=jm)
                assert vmem_bytes(bm, n, 128, jump_mode=jm) <= VMEM_BUDGET


def test_pallas_interpret_follows_the_backend(monkeypatch):
    """Interpreted on CPU, compiled on TPU, refused elsewhere."""
    import jax

    from repro.kernels.tree_eval.ops import pallas_interpret

    assert pallas_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="backend 'gpu'"):
        pallas_interpret()


def test_search_space_offers_only_fitting_pallas_tiles():
    """The onehot Pallas jump is not offered where its one-hot cannot fit."""
    from repro.tune.space import WorkloadShape, search_space

    wide = WorkloadShape(m=1024, n_nodes=511, n_attrs=19, depth=8)
    names = {c.variant for c in search_space(wide, engines=("pallas",))}
    assert names == {"pallas_speculative_gather", "pallas_data_parallel"}
    narrow = WorkloadShape(m=1024, n_nodes=100, n_attrs=19, depth=7)
    cands = list(search_space(narrow, engines=("pallas",)))
    assert any(c.variant == "pallas_speculative_onehot" for c in cands)
    for c in cands:
        jm = "onehot" if c.variant.endswith("onehot") else "gather"
        assert c.param_dict["block_m"] <= choose_block_m(128, 128, jump_mode=jm)
