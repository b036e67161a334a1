"""Compile every tree-eval Pallas kernel for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, from shapes alone.  Interpret mode cannot catch
what these catch — block shapes the tiling refuses, gathers Mosaic cannot
lower, tiles that overflow VMEM.  Each jitted entry point of ``ops.py`` is
compiled with ``interpret=False`` at the paper's M = 65,536 records of
A = 19 attributes, unpadded (the entry point pads them to 128 lanes inside
its program), and both node widths the serving paths use: N = 128 (a
depth ≤ 7 tree) and N = 512 (depth 8).  The record tile is the one
``choose_block_m`` picks, so the VMEM model is checked too.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and every test worker imports
this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.tree_eval import ops

M, A = 65_536, 19
A_PAD = 128
WIDTHS = [128, 512]
T_FOREST = 64
# (algorithm, jump_mode) of the class and vote kernels
ALGORITHMS = [("speculative", "gather"), ("speculative", "onehot"), ("data_parallel", "gather")]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _block_m_or_refused(n: int, jump_mode: str):
    """The tile the model picks, or None where no tile fits (asserted)."""
    if ops.block_m_fits(n, A_PAD, jump_mode=jump_mode):
        return ops.choose_block_m(n, A_PAD, jump_mode=jump_mode)
    with pytest.raises(ValueError, match="no record tile fits"):
        ops.choose_block_m(n, A_PAD, jump_mode=jump_mode)
    return None


def _compile(fn, *args, **static) -> str:
    text = fn.lower(*args, interpret=False, **static).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _f32_tables(sharding, t: int | None, n: int):
    """(attr_select, attr_idx, threshold, child, class_val) shapes; t=None
    is the single-tree layout ((A_PAD, N) and (1, N))."""
    lead = (1,) if t is None else (t,)
    sel = (A_PAD, n) if t is None else (t, A_PAD, n)
    return (
        _spec(sharding, sel),
        _spec(sharding, lead + (n,), jnp.int32),
        _spec(sharding, lead + (n,)),
        _spec(sharding, lead + (n,), jnp.int32),
        _spec(sharding, lead + (n,), jnp.int32),
    )


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("algorithm,jump_mode", ALGORITHMS)
def test_tree_kernel_compiles(one_chip, algorithm, jump_mode, n):
    bm = _block_m_or_refused(n, jump_mode)
    if bm is None:
        return
    _compile(ops._tree_eval_padded, _spec(one_chip, (M, A)), *_f32_tables(one_chip, None, n),
             algorithm=algorithm, block_m=bm, jump_mode=jump_mode, jumps=3, max_depth=8)


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("algorithm,jump_mode", ALGORITHMS)
def test_forest_kernel_compiles(one_chip, algorithm, jump_mode, n):
    bm = _block_m_or_refused(n, jump_mode)
    if bm is None:
        return
    _compile(ops._forest_eval_padded, _spec(one_chip, (M, A)),
             *_f32_tables(one_chip, T_FOREST, n),
             algorithm=algorithm, block_m=bm, jump_mode=jump_mode, jumps=3, max_depth=8)


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("algorithm,jump_mode", ALGORITHMS)
def test_vote_kernel_compiles(one_chip, algorithm, jump_mode, n):
    bm = _block_m_or_refused(n, jump_mode)
    if bm is None:
        return
    _compile(ops._forest_votes_padded, _spec(one_chip, (M, A)),
             *_f32_tables(one_chip, T_FOREST, n),
             algorithm=algorithm, block_m=bm, jump_mode=jump_mode, jumps=3, max_depth=8,
             n_classes=7)


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("algorithm", ["speculative", "data_parallel"])
def test_quant_kernel_compiles(one_chip, algorithm, n):
    """The narrowest storage dtypes QuantizedForest packs (int8 indices and
    classes, int16 children, bf16 thresholds)."""
    dtypes = (jnp.int8, jnp.bfloat16, jnp.int16, jnp.int8)
    tables = [_spec(one_chip, (T_FOREST, n), d) for d in dtypes]
    _compile(ops._quant_forest_eval_padded, _spec(one_chip, (M, A)), *tables,
             algorithm=algorithm, block_m=ops.choose_block_m(n, A_PAD), jumps=3, max_depth=8)
