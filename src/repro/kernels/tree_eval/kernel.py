"""Pallas TPU kernels for classification-tree evaluation.

Two kernels mirror the paper's two parallel decompositions, re-tiled for the
TPU memory hierarchy (HBM → VMEM → VREG) and compute units (MXU/VPU):

``speculative``  (paper Procedure 4/5, EvalTreeByNode)
    Records ride the sublane axis, tree nodes ride the 128-lane axis.
    Node evaluation is a single MXU matmul ``vals = records @ attr_select``
    (the one-hot selection matrix replaces the CUDA shared-memory gather),
    followed by a branch-free successor computation and ``⌈log₂ d⌉`` pointer
    jumps.  Jumps come in two flavours:
      * ``gather``  — ``path[i] ← path[path[i]]`` as lane gathers, one per
        (destination, source) pair of 128-lane slices (Mosaic gathers only
        within one vreg row, see :func:`_lane_take`),
      * ``onehot``  — batched permutation matmul ``pathᵢ₊₁ = P · pathᵢ``,
        all-MXU, no cross-lane gathers at all (the fully systolic variant).

``data_parallel`` (paper Procedure 3, EvalTreeBySample)
    One record per sublane; ``max_depth`` dependent rounds of table reads.
    This is the faithful TPU port of the data decomposition and exists to
    reproduce the paper's comparison: its inner loop is *serially dependent*
    (length d) whereas the speculative kernel needs only log₂ d dependent
    steps after one matmul.

Every kernel is a forest kernel: tree tables are stacked along a leading
tree axis and the grid is ``(M/block_m, T)`` with trees innermost, so each
record tile stays resident in VMEM while the T tree tables stream past it.
A single tree is the T = 1 case.  The per-tree scalar tables are stored as
``(T, 1, N)`` so that their ``(1, 1, N)`` blocks meet the TPU tiling rule
(the last two block dims must equal the array's or be multiples of
(8, 128)); ``attr_select`` is ``(T, A, N)`` with ``(1, A, N)`` blocks.  All
shapes are padded by ``ops.py`` so that M % block_m == 0, N % 128 == 0 and
A % 128 == 0 (MXU alignment).

Each kernel has one of two epilogues: per-tree classes ``(T, M, 1)``, or
the per-record vote histogram ``(M, C)`` accumulated across the tree axis
(the cascade's stage primitive).  The quantized layouts read int8/int16
indices and bf16/f16 thresholds instead of ``attr_select`` and upcast in
registers, so their results are bit-identical to the f32 kernels; the
quantized speculative kernel builds its one-hot selection in VMEM.

Interpret mode is chosen by the caller (``ops.pallas_interpret``); no
kernel here defaults it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# lane reads
# ---------------------------------------------------------------------------


def _select_lane(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[b, idx[b]]`` for ``table`` (BM or 1, K) and ``idx`` (BM, 1).

    A mask and a lane sum rather than a gather: Mosaic lowers a lane gather
    only when the index has the table's shape.  Exactly one lane survives
    the mask, so the sum returns it bit for bit (x + 0 = x).
    """
    lanes = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], table.shape[-1]), 1)
    return jnp.sum(jnp.where(lanes == idx, table, 0), axis=1, keepdims=True)


def _lane_take(src: jax.Array, idx: jax.Array) -> jax.Array:
    """``out[b, l] = src[b, idx[b, l]]`` for ``src`` (BM, K), ``idx`` (BM, L).

    Mosaic gathers within one 128-lane vreg only, so each 128-lane slice of
    the output gathers from every 128-lane slice of the source and keeps
    the lanes whose index falls in that slice: (L/128)·(K/128) gathers of
    the same (BM, 128) shape.  K and L are multiples of 128.
    """
    lo, hi = idx & (LANE - 1), idx >> 7
    out = []
    for o in range(0, idx.shape[-1], LANE):
        lo_o, hi_o = lo[:, o:o + LANE], hi[:, o:o + LANE]
        acc = None
        for c in range(0, src.shape[-1], LANE):
            part = jnp.take_along_axis(src[:, c:c + LANE], lo_o, axis=1)
            acc = part if acc is None else jnp.where(hi_o == c // LANE, part, acc)
        out.append(acc)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# per-tree bodies on VMEM-resident arrays; each returns (BM, 1) int32 classes
# ---------------------------------------------------------------------------


def _jump(path: jax.Array, total_jumps: int, jump_mode: str) -> jax.Array:
    """Pointer jumping ``path[i] ← path[path[i]]`` (Procedure 4 l.15)."""
    if jump_mode == "gather":
        for _ in range(total_jumps):
            path = _lane_take(path, path)
        return path
    if jump_mode == "onehot":
        n = path.shape[-1]
        pathf = path.astype(jnp.float32)
        for _ in range(total_jumps):
            onehot = jax.nn.one_hot(path, n, dtype=jnp.float32)    # (BM, N, N)
            pathf = jnp.einsum("bin,bn->bi", onehot, pathf, precision=_HIGHEST)
            path = pathf.astype(jnp.int32)
        return path
    raise ValueError(f"unknown jump_mode {jump_mode!r}")


def _speculative(rec, sel, thr, child, class_val, *, total_jumps: int, jump_mode: str):
    """Procedure 4/5: every node for every record, then pointer jumps.

    rec (BM, A) f32; sel (A, N) one-hot; thr/child/class_val (1, N).
    """
    # HIGHEST keeps the one-hot product exact (a single bf16 pass would
    # round the attribute values)
    vals = jnp.dot(rec, sel.astype(jnp.float32), precision=_HIGHEST,
                   preferred_element_type=jnp.float32)             # (BM, N)
    path = child + (vals > thr).astype(jnp.int32)                  # (BM, N)
    path = _jump(path, total_jumps, jump_mode)
    # the root's eventual successor is the terminal leaf; read its class
    return _select_lane(class_val, path[:, 0:1])


def _speculative_q(rec, attr_idx, thr, child, class_val, *, total_jumps: int):
    """Procedure 4/5 on quantized tables: the one-hot selection matrix is
    built in VMEM from ``attr_idx`` (A·N compares) instead of being stored."""
    sel = jax.lax.broadcasted_iota(jnp.int32, (rec.shape[1], attr_idx.shape[-1]), 0)
    sel = (sel == attr_idx.astype(jnp.int32)).astype(jnp.float32)
    return _speculative(rec, sel, thr.astype(jnp.float32), child.astype(jnp.int32),
                        class_val.astype(jnp.int32),
                        total_jumps=total_jumps, jump_mode="gather")


def _data_parallel(rec, attr_idx, thr, child, class_val, *, max_depth: int):
    """Procedure 3: ``max_depth`` dependent descents, one record per row.

    Tables (1, N) of any int/float storage dtype; upcast in registers.
    """
    attr_idx = attr_idx.astype(jnp.int32)
    thr = thr.astype(jnp.float32)
    child = child.astype(jnp.int32)
    idx = jnp.zeros((rec.shape[0], 1), jnp.int32)
    for _ in range(max_depth):
        a = _select_lane(attr_idx, idx)
        t = _select_lane(thr, idx)
        c = _select_lane(child, idx)
        v = _select_lane(rec, a)                                   # per-record attr
        idx = c + (v > t).astype(jnp.int32)
    return _select_lane(class_val.astype(jnp.int32), idx)


_COMPUTE = {
    ("speculative", "f32"): _speculative,
    ("data_parallel", "f32"): _data_parallel,
    ("speculative", "quant"): _speculative_q,
    ("data_parallel", "quant"): _data_parallel,
}


# ---------------------------------------------------------------------------
# one launcher for every kernel
# ---------------------------------------------------------------------------


def _classes_body(compute, records_ref, *refs):
    *table_refs, out_ref = refs
    out_ref[0] = compute(records_ref[...].astype(jnp.float32),
                         *(r[0] for r in table_refs))


def _votes_body(compute, records_ref, *refs):
    """Add one tree's one-hot vote into the (BM, C) tile revisited across
    the tree axis (initialised at the first tree)."""
    *table_refs, out_ref = refs
    cls = compute(records_ref[...].astype(jnp.float32), *(r[0] for r in table_refs))
    bm, c = out_ref.shape
    votes = (jax.lax.broadcasted_iota(jnp.int32, (bm, c), 1) == cls).astype(jnp.int32)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = votes

    @pl.when(j != 0)
    def _add():
        out_ref[...] += votes


def kernel_name(algorithm: str, layout: str = "f32", jump_mode: str = "gather",
                votes: bool = False) -> str:
    """The kernel's name on the device trace's ops line, e.g.
    ``tree_eval_speculative_gather`` or ``tree_eval_data_parallel_q_votes``:
    the algorithm, the jump mode of the f32 speculative kernel, ``_q`` for
    the quantized tables and ``_votes`` for the vote epilogue."""
    name = f"tree_eval_{algorithm}"
    if algorithm == "speculative" and layout == "f32":
        name += f"_{jump_mode}"
    if layout == "quant":
        name += "_q"
    return name + ("_votes" if votes else "")


def forest_pallas(
    records: jax.Array,
    tables: tuple[jax.Array, ...],
    *,
    algorithm: str,
    layout: str = "f32",
    block_m: int,
    interpret: bool,
    n_classes: int | None = None,
    total_jumps: int = 1,
    jump_mode: str = "gather",
    max_depth: int = 1,
) -> jax.Array:
    """Launch one tree-evaluation kernel over a stacked forest.

    Args:
      records: (M, A) padded records (any float dtype; compared in f32).
      tables: the per-tree tables, leading tree axis T.  Speculative f32:
        ``(attr_select (T, A, N), threshold, child, class_val)``; every
        other kernel: ``(attr_idx, threshold, child, class_val)``.  Scalar
        tables are (T, N), viewed here as (T, 1, N).
      algorithm: "speculative" (Procedure 4/5) or "data_parallel" (Proc. 3).
      layout: "f32" or "quant" (storage-dtype tables, no ``attr_select``).
      block_m: records per tile; divides M.
      interpret: run the Pallas interpreter (CPU backend) or compile.
      n_classes: None for the per-tree class epilogue, else the padded vote
        width C of the vote epilogue.
      total_jumps / jump_mode: speculative pointer-jump schedule.
      max_depth: data-parallel descent rounds.

    Returns:
      (T, M, 1) int32 per-tree classes, or (M, C) int32 vote counts.
    """
    m, a = records.shape
    assert m % block_m == 0, (m, block_m)
    tables = tuple(x if x.ndim == 3 else x[:, None, :] for x in tables)
    t = tables[0].shape[0]
    if algorithm == "speculative":
        kw = {"total_jumps": total_jumps}
        if layout == "f32":
            kw["jump_mode"] = jump_mode
    elif algorithm == "data_parallel":
        kw = {"max_depth": max_depth}
    else:
        raise ValueError(f"unknown algorithm {algorithm!r}")
    compute = functools.partial(_COMPUTE[(algorithm, layout)], **kw)
    if n_classes is None:
        body = functools.partial(_classes_body, compute)
        out_spec = pl.BlockSpec((1, block_m, 1), lambda i, j: (j, i, 0))
        out_shape = jax.ShapeDtypeStruct((t, m, 1), jnp.int32)
    else:
        body = functools.partial(_votes_body, compute)
        out_spec = pl.BlockSpec((block_m, n_classes), lambda i, j: (i, 0))
        out_shape = jax.ShapeDtypeStruct((m, n_classes), jnp.int32)
    return pl.pallas_call(
        body,
        grid=(m // block_m, t),
        in_specs=[pl.BlockSpec((block_m, a), lambda i, j: (i, 0))]   # record tile resident
        + [pl.BlockSpec((1,) + x.shape[1:], lambda i, j: (j, 0, 0))  # tree tables stream
           for x in tables],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        name=kernel_name(algorithm, layout, jump_mode, votes=n_classes is not None),
    )(records, *tables)
