"""Public jit'd wrappers for the tree-evaluation Pallas kernels.

Handles everything the raw kernels assume away: lane/sublane padding of the
tree and record arrays, VMEM-budget-driven block-size selection, phantom-node
padding (the paper's half-warp phantom generalised to 128-lane tiles), the
interpret-or-compile decision (:func:`pallas_interpret`), and unpadding of
results.

The node tables are packed once (:class:`PackedTree`, :class:`PackedForest`,
``QuantizedForest``) and reused across calls; the records are sanitised
(speculative kernels), padded to whole tiles and 128 lanes, and the output
sliced back inside each kernel's jitted program (:func:`_pad_records`), so a
call on packed tables is one jitted call and no eager device work.

The tree path and the fused forest paths time their host phases on the
``tracer`` the caller passes (``kernel.pack``: tables packed here, only when
the caller hands over an encoding rather than packed tables;
``kernel.launch``: the jitted call) and count the padding the kernels add to
the records on ``pad_bytes``, a counter the caller holds
(``kernel.pad_bytes`` of :mod:`repro.tune.dispatch`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.eval_speculative import sanitize_records
from repro.core.tree import EncodedTree, attr_select_matrix, pad_tree, tree_depth
from repro.kernels.tree_eval import kernel as _k
from repro.kernels.tree_eval.quant import QuantizedForest, packed_forest_nbytes

LANE = 128          # TPU vector lane count / MXU edge
SUBLANE = 8
# Half of the 16 MiB scoped-VMEM limit Mosaic applies on a v5e core by
# default; the other half is headroom for temporaries the model below does
# not count.  tests/test_chip_compile.py compiles the chosen tiles for v5e.
VMEM_BUDGET = 8 * 2**20
MAX_BLOCK_M = 1024


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Whether the Pallas kernels run in the interpreter on this backend.

    The one place the choice is made: interpreted on the CPU backend (the
    test suite), compiled on TPU, and refused anywhere else — there is no
    silent fallback from a device to the interpreter.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the tree-eval Pallas kernels compile for TPU and are interpreted on "
        f"CPU; backend {backend!r} has neither path")


def vmem_bytes(block_m: int, n_nodes: int, n_attrs: int, *, jump_mode: str = "gather") -> int:
    """Modelled VMEM of one grid step (padded N and A, f32/int32 words).

    Blocks are double-buffered by the Pallas pipeline: the record tile
    (BM·A), one tree's ``attr_select`` (A·N), its scalar tables (each a
    (1, N) row padded to a sublane tile) and the output tile (BM rows of
    one 128-lane vreg).  Temporaries: about four (BM, N) arrays (node
    values, the path and the jump's partial gathers); the onehot jump adds
    its (BM, N, N) one-hot.
    """
    blocks = block_m * n_attrs + n_attrs * n_nodes + 4 * SUBLANE * n_nodes + block_m * LANE
    temps = 4 * block_m * n_nodes
    if jump_mode == "onehot":
        temps += block_m * n_nodes * n_nodes
    return 4 * (2 * blocks + temps)


def choose_block_m(n_nodes: int, n_attrs: int, *, jump_mode: str = "gather") -> int:
    """The largest power-of-two record tile (≤ MAX_BLOCK_M) whose modelled
    VMEM (:func:`vmem_bytes`) fits ``VMEM_BUDGET``.

    Raises:
      ValueError: when not even a one-sublane tile fits (e.g. the onehot
        jump at N = 512, whose one-hot alone is 8 MiB per 8 records).
    """
    bm = MAX_BLOCK_M
    while bm >= SUBLANE:
        if vmem_bytes(bm, n_nodes, n_attrs, jump_mode=jump_mode) <= VMEM_BUDGET:
            return bm
        bm //= 2
    raise ValueError(
        f"no record tile fits {VMEM_BUDGET} B of VMEM at N={n_nodes}, "
        f"A={n_attrs}, jump_mode={jump_mode!r}")


def block_m_fits(n_nodes: int, n_attrs: int, *, jump_mode: str = "gather") -> bool:
    """Whether any record tile fits (the kernel can run at this width)."""
    return vmem_bytes(SUBLANE, n_nodes, n_attrs, jump_mode=jump_mode) <= VMEM_BUDGET


def _pointer_jumps(max_depth: int) -> int:
    """Pointer-jump rounds that resolve a path of ``max_depth`` hops."""
    return max(1, math.ceil(math.log2(max(max_depth, 2))))


class PackedTree:
    """Device-ready padded tree tables for the kernels."""

    def __init__(self, enc: EncodedTree, n_attrs: int, *, max_depth: int | None = None):
        self.logical_nodes = enc.n_nodes
        self.n_attrs = n_attrs
        self.max_depth = max_depth if max_depth is not None else tree_depth(enc)
        n_pad = _round_up(enc.n_nodes, LANE)
        a_pad = _round_up(n_attrs, LANE)
        penc = pad_tree(enc, n_pad)
        sel = np.zeros((a_pad, n_pad), np.float32)
        sel[:n_attrs] = attr_select_matrix(penc, n_attrs)
        self.n_nodes = n_pad
        self.n_attrs_padded = a_pad
        self.attr_select = jnp.asarray(sel)
        self.attr_idx = jnp.asarray(penc.attr_idx[None, :], jnp.int32)
        self.threshold = jnp.asarray(penc.threshold[None, :], jnp.float32)
        self.child = jnp.asarray(penc.child[None, :], jnp.int32)
        self.class_val = jnp.asarray(penc.class_val[None, :], jnp.int32)


def _pad_records(records, *, algorithm: str, block_m: int, a_pad: int) -> jax.Array:
    """The (M, A) records as the kernels take them, traced inside each
    jitted entry point: zero-padded to (M rounded up to ``block_m``,
    ``a_pad``), and for the speculative kernels sanitised first, since they
    evaluate every node with a records@S product where non-finite
    attributes would poison whole rows (inf*0 = NaN)."""
    if algorithm == "speculative":
        records = sanitize_records(records)
    m, a = records.shape
    return jnp.pad(records, ((0, _round_up(max(m, 1), block_m) - m), (0, a_pad - a)))


def _count_padding(records, algorithm: str, block_m: int, a_pad: int, pad_bytes) -> None:
    """Count on ``pad_bytes`` (when given) the bytes :func:`_pad_records`
    adds to ``records``, from their shape alone."""
    if pad_bytes is None:
        return
    m, a = np.shape(records)
    dtype = np.float32 if algorithm == "speculative" else getattr(records, "dtype", np.float32)
    itemsize = jax.dtypes.canonicalize_dtype(dtype).itemsize
    pad_bytes.inc((_round_up(max(m, 1), block_m) * a_pad - m * a) * itemsize)


@functools.partial(
    jax.jit,
    static_argnames=("algorithm", "block_m", "jump_mode", "jumps", "max_depth", "interpret"),
)
def _tree_eval_padded(
    records,
    attr_select,
    attr_idx,
    threshold,
    child,
    class_val,
    *,
    algorithm: str,
    block_m: int,
    jump_mode: str,
    jumps: int,
    max_depth: int,
    interpret: bool,
):
    """One tree ((1, N) tables, (A_pad, N) ``attr_select``) as a T = 1
    forest over (M, A) records, padded here and sliced back to M."""
    m = records.shape[0]
    padded = _pad_records(records, algorithm=algorithm, block_m=block_m,
                          a_pad=attr_select.shape[-2])
    first = attr_select[None] if algorithm == "speculative" else attr_idx
    out = _k.forest_pallas(
        padded, (first, threshold, child, class_val),
        algorithm=algorithm, block_m=block_m, interpret=interpret,
        total_jumps=jumps, jump_mode=jump_mode, max_depth=max_depth,
    )
    return out[0, :m, 0]


def tree_eval(
    records,
    tree: PackedTree | EncodedTree,
    *,
    n_attrs: int | None = None,
    algorithm: str = "speculative",
    jump_mode: str = "gather",
    block_m: int | None = None,
    tracer: obs.Tracer = obs.NULL_TRACER,
    pad_bytes=None,
) -> jax.Array:
    """Evaluate a classification tree over a record batch with a TPU kernel.

    Args:
      records: (M, A) float array (any float dtype; compared in f32).
      tree: an :class:`EncodedTree` (packed here, every call) or prebuilt
        :class:`PackedTree`.
      algorithm: "speculative" (Procedure 4/5) or "data_parallel" (Procedure 3).
      jump_mode: "gather" | "onehot" pointer-jump implementation.
      block_m: records per tile; default = VMEM-model choice.
      tracer: times the host phases (module docstring).
      pad_bytes: counter of the padding bytes added to the records, or None.

    Returns:
      (M,) int32 class assignments.
    """
    if isinstance(tree, EncodedTree):
        with tracer.span("kernel.pack", cat="kernel"):
            if n_attrs is None:
                n_attrs = int(np.shape(records)[-1])
            tree = PackedTree(tree, n_attrs)
    if block_m is None:
        block_m = choose_block_m(tree.n_nodes, tree.n_attrs_padded, jump_mode=jump_mode)
    _count_padding(records, algorithm, block_m, tree.n_attrs_padded, pad_bytes)
    with tracer.span("kernel.launch", cat="kernel"):
        return _tree_eval_padded(
            records,
            tree.attr_select,
            tree.attr_idx,
            tree.threshold,
            tree.child,
            tree.class_val,
            algorithm=algorithm,
            block_m=block_m,
            jump_mode=jump_mode,
            jumps=_pointer_jumps(tree.max_depth),
            max_depth=tree.max_depth,
            interpret=pallas_interpret(),
        )


def forest_eval(
    records,
    trees: list[PackedTree],
    **kw,
) -> jax.Array:
    """Per-tree kernel evaluation, (T, M). Trees may have different sizes."""
    return jnp.stack([tree_eval(records, t, **kw) for t in trees])


class PackedForest:
    """Device-ready stacked padded tables for the fused forest kernels.

    All T trees are padded to one lane-aligned node count (phantom self-loop
    leaves, §3.2) and their tables stacked along a leading tree axis:
    ``attr_select`` (T, A_pad, N_pad), the scalar tables (T, N_pad).  The
    fused kernels then evaluate the whole forest in one launch with the tree
    axis on the grid.

    Args:
      forest: an :class:`repro.core.forest.EncodedForest` (trees already
        stacked at a common logical node count) — or anything exposing its
        ``n_trees`` / ``n_nodes`` / ``max_depth`` / ``tree(i)`` surface.
      n_attrs: record attribute count A (pre-padding).
      max_depth: depth bound over the forest; default ``forest.max_depth``.
    """

    def __init__(self, forest, n_attrs: int, *, max_depth: int | None = None):
        self.n_trees = int(forest.n_trees)
        self.logical_nodes = int(forest.n_nodes)
        self.n_attrs = n_attrs
        self.max_depth = int(max_depth if max_depth is not None else forest.max_depth)
        n_pad = _round_up(self.logical_nodes, LANE)
        a_pad = _round_up(n_attrs, LANE)
        penc = [pad_tree(forest.tree(i), n_pad) for i in range(self.n_trees)]
        sel = np.zeros((self.n_trees, a_pad, n_pad), np.float32)
        for i, p in enumerate(penc):
            sel[i, :n_attrs] = attr_select_matrix(p, n_attrs)
        self.n_nodes = n_pad
        self.n_attrs_padded = a_pad
        self.attr_select = jnp.asarray(sel)
        self.attr_idx = jnp.asarray(np.stack([p.attr_idx for p in penc]), jnp.int32)
        self.threshold = jnp.asarray(np.stack([p.threshold for p in penc]), jnp.float32)
        self.child = jnp.asarray(np.stack([p.child for p in penc]), jnp.int32)
        self.class_val = jnp.asarray(np.stack([p.class_val for p in penc]), jnp.int32)

    @property
    def nbytes(self) -> int:
        """Total node-table bytes (incl. ``attr_select`` — the f32 baseline
        the quantized layouts are benchmarked against)."""
        return packed_forest_nbytes(self)


@functools.partial(
    jax.jit,
    static_argnames=("algorithm", "block_m", "jump_mode", "jumps", "max_depth", "interpret"),
)
def _forest_eval_padded(
    records,
    attr_select,
    attr_idx,
    threshold,
    child,
    class_val,
    *,
    algorithm: str,
    block_m: int,
    jump_mode: str,
    jumps: int,
    max_depth: int,
    interpret: bool,
):
    m = records.shape[0]
    padded = _pad_records(records, algorithm=algorithm, block_m=block_m,
                          a_pad=attr_select.shape[-2])
    first = attr_select if algorithm == "speculative" else attr_idx
    out = _k.forest_pallas(
        padded, (first, threshold, child, class_val),
        algorithm=algorithm, block_m=block_m, interpret=interpret,
        total_jumps=jumps, jump_mode=jump_mode, max_depth=max_depth,
    )
    return out[:, :m, 0]


def forest_eval_fused(
    records,
    forest: "PackedForest | object",
    *,
    n_attrs: int | None = None,
    algorithm: str = "speculative",
    jump_mode: str = "gather",
    block_m: int | None = None,
    tracer: obs.Tracer = obs.NULL_TRACER,
    pad_bytes=None,
) -> jax.Array:
    """Evaluate a whole forest with one fused Pallas launch.

    Args:
      records: (M, A) float array (any float dtype; compared in f32).
      forest: an ``EncodedForest`` (packed internally) or prebuilt
        :class:`PackedForest`.
      algorithm: "speculative" (Procedure 4/5) or "data_parallel" (Procedure 3).
      jump_mode: "gather" | "onehot" pointer-jump implementation.
      block_m: records per tile; default = VMEM-model choice.
      tracer / pad_bytes: as for :func:`tree_eval`.

    Returns:
      (T, M) int32 per-tree class assignments, bit-identical to running
      :func:`tree_eval` tree by tree.
    """
    if not isinstance(forest, PackedForest):
        with tracer.span("kernel.pack", cat="kernel"):
            if n_attrs is None:
                n_attrs = int(np.shape(records)[-1])
            forest = PackedForest(forest, n_attrs)
    if block_m is None:
        block_m = choose_block_m(forest.n_nodes, forest.n_attrs_padded, jump_mode=jump_mode)
    _count_padding(records, algorithm, block_m, forest.n_attrs_padded, pad_bytes)
    with tracer.span("kernel.launch", cat="kernel"):
        return _forest_eval_padded(
            records,
            forest.attr_select,
            forest.attr_idx,
            forest.threshold,
            forest.child,
            forest.class_val,
            algorithm=algorithm,
            block_m=block_m,
            jump_mode=jump_mode,
            jumps=_pointer_jumps(forest.max_depth),
            max_depth=forest.max_depth,
            interpret=pallas_interpret(),
        )


@functools.partial(
    jax.jit,
    static_argnames=("algorithm", "block_m", "jumps", "max_depth", "interpret"),
)
def _quant_forest_eval_padded(
    records,
    attr_idx,
    threshold,
    child,
    class_val,
    *,
    algorithm: str,
    block_m: int,
    jumps: int,
    max_depth: int,
    interpret: bool,
):
    m, a = records.shape
    padded = _pad_records(records, algorithm=algorithm, block_m=block_m,
                          a_pad=_round_up(a, LANE))
    out = _k.forest_pallas(
        padded, (attr_idx, threshold, child, class_val),
        algorithm=algorithm, layout="quant", block_m=block_m, interpret=interpret,
        total_jumps=jumps, max_depth=max_depth,
    )
    return out[:, :m, 0]


def forest_eval_fused_q(
    records,
    forest: "QuantizedForest | object",
    *,
    n_attrs: int | None = None,
    algorithm: str = "speculative",
    thr_dtype: str = "bfloat16",
    calibration=None,
    block_m: int | None = None,
    tracer: obs.Tracer = obs.NULL_TRACER,
    pad_bytes=None,
) -> jax.Array:
    """Evaluate a whole forest with one fused launch over *quantized* tables.

    The compact-layout dual of :func:`forest_eval_fused`: node tables arrive
    as int8/int16 indices and bf16/f16 split-safe thresholds (see
    :mod:`repro.kernels.tree_eval.quant`) and no ``attr_select`` is stored:
    the speculative kernel builds the one-hot selection in VMEM from the
    index table, the data-parallel kernel reads each record's attribute.

    Args:
      records: (M, A) float array (compared in f32 after upcast).
      forest: a prebuilt :class:`QuantizedForest`, or an ``EncodedForest``
        quantized here (``thr_dtype``/``calibration`` control the rounding;
        ``calibration=None`` — the default — quantizes only thresholds whose
        cast round-trips exactly, so results are bit-exact for *any* input).
      algorithm: "speculative" (Procedure 4/5) or "data_parallel" (Procedure 3).
      block_m: records per tile; default = VMEM-model choice.
      tracer / pad_bytes: as for :func:`tree_eval`.

    Returns:
      (T, M) int32 per-tree class assignments.
    """
    if not isinstance(forest, QuantizedForest):
        with tracer.span("kernel.pack", cat="kernel"):
            if n_attrs is None:
                n_attrs = int(np.shape(records)[-1])
            forest = QuantizedForest(
                forest, n_attrs, thr_dtype=thr_dtype, calibration=calibration
            )
    if block_m is None:
        block_m = choose_block_m(forest.n_nodes, forest.n_attrs_padded, jump_mode="gather")
    _count_padding(records, algorithm, block_m, forest.n_attrs_padded, pad_bytes)
    with tracer.span("kernel.launch", cat="kernel"):
        return _quant_forest_eval_padded(
            records,
            forest.attr_idx,
            forest.threshold,
            forest.child,
            forest.class_val,
            algorithm=algorithm,
            block_m=block_m,
            jumps=_pointer_jumps(forest.max_depth),
            max_depth=forest.max_depth,
            interpret=pallas_interpret(),
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "algorithm", "block_m", "jump_mode", "jumps", "max_depth", "n_classes", "interpret",
    ),
)
def _forest_votes_padded(
    records,
    attr_select,
    attr_idx,
    threshold,
    child,
    class_val,
    *,
    algorithm: str,
    block_m: int,
    jump_mode: str,
    jumps: int,
    max_depth: int,
    n_classes: int,
    interpret: bool,
):
    m = records.shape[0]
    padded = _pad_records(records, algorithm=algorithm, block_m=block_m,
                          a_pad=attr_select.shape[-2])
    first = attr_select if algorithm == "speculative" else attr_idx
    out = _k.forest_pallas(
        padded, (first, threshold, child, class_val),
        algorithm=algorithm, block_m=block_m, interpret=interpret,
        n_classes=_round_up(max(n_classes, 2), LANE),
        total_jumps=jumps, jump_mode=jump_mode, max_depth=max_depth,
    )
    return out[:m, :n_classes]


def forest_votes_fused(
    records,
    forest: "PackedForest | object",
    *,
    n_classes: int,
    n_attrs: int | None = None,
    algorithm: str = "speculative",
    jump_mode: str = "gather",
    block_m: int | None = None,
) -> jax.Array:
    """Accumulate the forest's class votes in one fused Pallas launch.

    The per-tree classes stay inside VMEM: each tree grid-step adds its
    one-hot vote into a persistent (block_m, C_pad) output tile, so the
    (T, M) class matrix is never materialised in HBM.  This is the stage
    primitive of the cascade evaluator.

    Returns:
      (M, n_classes) int32 vote counts; ``argmax`` along the last axis
      reproduces :func:`repro.core.forest.majority_vote` exactly.
    """
    if not isinstance(forest, PackedForest):
        if n_attrs is None:
            n_attrs = int(np.shape(records)[-1])
        forest = PackedForest(forest, n_attrs)
    if block_m is None:
        block_m = choose_block_m(forest.n_nodes, forest.n_attrs_padded, jump_mode=jump_mode)
    return _forest_votes_padded(
        records,
        forest.attr_select,
        forest.attr_idx,
        forest.threshold,
        forest.child,
        forest.class_val,
        algorithm=algorithm,
        block_m=block_m,
        jump_mode=jump_mode,
        jumps=_pointer_jumps(forest.max_depth),
        max_depth=forest.max_depth,
        n_classes=int(n_classes),
        interpret=pallas_interpret(),
    )


# ---------------------------------------------------------------------------
# Variant registry (consumed by repro.tune)
# ---------------------------------------------------------------------------
#
# Every registered variant is a semantically identical evaluator of the
# branchless encoded tree with a uniform calling convention:
#
#     fn(records, enc: EncodedTree, *, max_depth: int, tracer=NULL_TRACER,
#        pad_bytes=None, **params) -> (M,) int32
#
# ``params`` only ever contains keys named in ``tunables``; the tuner
# enumerates (variant × parameter grid) candidates from this table and the
# dispatch layer replays the winning entry.  ``tracer`` and ``pad_bytes``
# are the dispatching evaluator's (see the module docstring); the Pallas
# variants time and count with them and the jnp variants drop them with the
# other keywords they do not read.


@dataclasses.dataclass(frozen=True)
class VariantSpec:
    """One evaluator implementation plus the knobs the tuner may sweep.

    Attributes:
      name: registry key, e.g. ``"pallas_speculative_onehot"``.
      algorithm: "speculative" (Procedure 4/5) or "data_parallel" (Procedure 3)
        — links the variant to the §3.6 runtime model (T₅ vs T₃).
      engine: "pallas" (TPU kernel path) or "jnp" (XLA-compiled host/TPU path).
      jump_mode: node-evaluation formulation, "gather" or "onehot" (MXU).
      tunables: names of the free parameters, e.g. ("block_m",).
      fn: the evaluator callable (uniform signature above).
    """

    name: str
    algorithm: str
    engine: str
    jump_mode: str
    tunables: tuple[str, ...]
    fn: Callable


VARIANTS: dict[str, VariantSpec] = {}


def register_variant(spec: VariantSpec) -> VariantSpec:
    if spec.name in VARIANTS:
        raise ValueError(f"variant {spec.name!r} already registered")
    VARIANTS[spec.name] = spec
    return spec


def get_variant(name: str) -> VariantSpec:
    try:
        return VARIANTS[name]
    except KeyError:
        raise KeyError(
            f"unknown variant {name!r}; registered: {sorted(VARIANTS)}"
        ) from None


def list_variants(*, engine: str | None = None, algorithm: str | None = None) -> list[VariantSpec]:
    out = [
        s
        for s in VARIANTS.values()
        if (engine is None or s.engine == engine)
        and (algorithm is None or s.algorithm == algorithm)
    ]
    return sorted(out, key=lambda s: s.name)


def _pallas_fn(algorithm: str, jump_mode: str) -> Callable:
    def fn(records, enc, *, max_depth=None, tracer=obs.NULL_TRACER, pad_bytes=None,
           **params):
        del max_depth  # PackedTree derives it from the encoding
        return tree_eval(
            records,
            enc,
            algorithm=algorithm,
            jump_mode=jump_mode,
            block_m=params.get("block_m"),
            tracer=tracer,
            pad_bytes=pad_bytes,
        )

    return fn


def _jnp_speculative_fn(jump_mode: str) -> Callable:
    from repro.core.eval_speculative import eval_speculative_tree

    def fn(records, enc, *, max_depth, **params):
        return eval_speculative_tree(
            enc,
            records,
            max_depth=max_depth,
            jumps_per_round=int(params.get("jumps_per_round", 2)),
            use_onehot_matmul=(jump_mode == "onehot"),
        )

    return fn


def _jnp_data_parallel_fn(records, enc, *, max_depth, **params):
    from repro.core.eval_dataparallel import eval_data_parallel_tree

    del params
    return eval_data_parallel_tree(enc, records, max_depth=max_depth)


for _alg, _jm in (("speculative", "gather"), ("speculative", "onehot"), ("data_parallel", "gather")):
    register_variant(
        VariantSpec(
            name=f"pallas_{_alg}" + (f"_{_jm}" if _alg == "speculative" else ""),
            algorithm=_alg,
            engine="pallas",
            jump_mode=_jm,
            tunables=("block_m",),
            fn=_pallas_fn(_alg, _jm),
        )
    )

for _jm in ("gather", "onehot"):
    register_variant(
        VariantSpec(
            name=f"jnp_speculative_{_jm}",
            algorithm="speculative",
            engine="jnp",
            jump_mode=_jm,
            tunables=("jumps_per_round",),
            fn=_jnp_speculative_fn(_jm),
        )
    )

register_variant(
    VariantSpec(
        name="jnp_data_parallel",
        algorithm="data_parallel",
        engine="jnp",
        jump_mode="gather",
        tunables=(),
        fn=_jnp_data_parallel_fn,
    )
)


# ---------------------------------------------------------------------------
# Forest variant registry (consumed by repro.tune's forest-level tuner)
# ---------------------------------------------------------------------------
#
# A *forest* variant evaluates all T trees of a stacked forest at once with a
# uniform calling convention:
#
#     fn(records, forest, *, max_depth: int, tracer=NULL_TRACER, pad_bytes=None,
#        **params) -> (T, M) int32
#
# where ``forest`` is an EncodedForest (or PackedForest for the fused
# family).  Two families are registered here; the third family the forest
# tuner considers — ``per_tree``, a vector of per-tree winners — is not a
# single callable and lives in ``repro.tune.dispatch.ForestTunedEvaluator``.

# Family name the forest tuner uses for the per-tree-variant-vector path;
# kept here so the cache vocabulary is defined next to the registry.
PER_TREE_FAMILY = "per_tree"


@dataclasses.dataclass(frozen=True)
class ForestVariantSpec:
    """One whole-forest evaluator plus the knobs the tuner may sweep.

    Attributes:
      name: registry key, e.g. ``"forest_fused_speculative_onehot"``.
      family: "fused" (one Pallas launch, tree axis on the grid) or "vmap"
        (the stacked jnp formulation ``vmap``-ed over the tree axis).
      algorithm: "speculative" or "data_parallel" (§3.6 T₅ vs T₃ per shard).
      engine: "pallas" or "jnp" (same meaning as :class:`VariantSpec`).
      jump_mode: "gather" | "onehot" node-evaluation/jump formulation.
      tunables: names of the free parameters, e.g. ("block_m",).
      fn: the evaluator callable (uniform signature above).
      layout: node-table layout family — "f32" (the full-width
        :class:`PackedForest` tables) or "quant" (the compact
        :class:`QuantizedForest` SoA layout).  Quantized layouts only enter
        the search space when a caller opts in
        (``forest_search_space(..., layouts=...)``), and the ``thr_dtype``
        tunable is consumed at *packing* time, not passed to the kernel.
    """

    name: str
    family: str
    algorithm: str
    engine: str
    jump_mode: str
    tunables: tuple[str, ...]
    fn: Callable
    layout: str = "f32"


FOREST_VARIANTS: dict[str, ForestVariantSpec] = {}


def register_forest_variant(spec: ForestVariantSpec) -> ForestVariantSpec:
    if spec.name in FOREST_VARIANTS:
        raise ValueError(f"forest variant {spec.name!r} already registered")
    FOREST_VARIANTS[spec.name] = spec
    return spec


def get_forest_variant(name: str) -> ForestVariantSpec:
    try:
        return FOREST_VARIANTS[name]
    except KeyError:
        raise KeyError(
            f"unknown forest variant {name!r}; registered: {sorted(FOREST_VARIANTS)}"
        ) from None


def list_forest_variants(
    *, engine: str | None = None, family: str | None = None
) -> list[ForestVariantSpec]:
    out = [
        s
        for s in FOREST_VARIANTS.values()
        if (engine is None or s.engine == engine)
        and (family is None or s.family == family)
    ]
    return sorted(out, key=lambda s: s.name)


def _forest_tables(forest):
    return (
        jnp.asarray(forest.attr_idx, jnp.int32),
        jnp.asarray(forest.threshold, jnp.float32),
        jnp.asarray(forest.child, jnp.int32),
        jnp.asarray(forest.class_val, jnp.int32),
    )


def _vmap_speculative_fn(jump_mode: str) -> Callable:
    def fn(records, forest, *, max_depth, **params):
        from repro.core.eval_speculative import eval_speculative

        rec = jnp.asarray(records, jnp.float32)
        jumps = int(params.get("jumps_per_round", 2))

        def one(a, t, c, k):
            return eval_speculative(
                rec, a, t, c, k,
                max_depth=max_depth,
                jumps_per_round=jumps,
                use_onehot_matmul=(jump_mode == "onehot"),
            )

        return jax.vmap(one)(*_forest_tables(forest))

    return fn


def _vmap_data_parallel_fn(records, forest, *, max_depth, **params):
    from repro.core.eval_dataparallel import eval_data_parallel

    del params
    rec = jnp.asarray(records, jnp.float32)

    def one(a, t, c, k):
        return eval_data_parallel(rec, a, t, c, k, max_depth=max_depth)

    return jax.vmap(one)(*_forest_tables(forest))


def _fused_fn(algorithm: str, jump_mode: str) -> Callable:
    def fn(records, forest, *, max_depth=None, tracer=obs.NULL_TRACER, pad_bytes=None,
           **params):
        del max_depth  # PackedForest derives it from the encodings
        return forest_eval_fused(
            records,
            forest,
            algorithm=algorithm,
            jump_mode=jump_mode,
            block_m=params.get("block_m"),
            tracer=tracer,
            pad_bytes=pad_bytes,
        )

    return fn


def _fused_q_fn(algorithm: str) -> Callable:
    def fn(records, forest, *, max_depth=None, tracer=obs.NULL_TRACER, pad_bytes=None,
           **params):
        del max_depth  # QuantizedForest derives it from the encodings
        return forest_eval_fused_q(
            records,
            forest,
            algorithm=algorithm,
            thr_dtype=params.get("thr_dtype", "bfloat16"),
            block_m=params.get("block_m"),
            tracer=tracer,
            pad_bytes=pad_bytes,
        )

    return fn


for _alg in ("speculative", "data_parallel"):
    register_forest_variant(
        ForestVariantSpec(
            name=f"forest_fused_{_alg}_q",
            family="fused",
            algorithm=_alg,
            engine="pallas",
            jump_mode="gather",
            tunables=("block_m", "thr_dtype"),
            fn=_fused_q_fn(_alg),
            layout="quant",
        )
    )


for _alg, _jm in (("speculative", "gather"), ("speculative", "onehot"), ("data_parallel", "gather")):
    _suffix = f"_{_jm}" if _alg == "speculative" else ""
    register_forest_variant(
        ForestVariantSpec(
            name=f"forest_fused_{_alg}" + _suffix,
            family="fused",
            algorithm=_alg,
            engine="pallas",
            jump_mode=_jm,
            tunables=("block_m",),
            fn=_fused_fn(_alg, _jm),
        )
    )
    register_forest_variant(
        ForestVariantSpec(
            name=f"forest_vmap_{_alg}" + _suffix,
            family="vmap",
            algorithm=_alg,
            engine="jnp",
            jump_mode=_jm,
            tunables=("jumps_per_round",) if _alg == "speculative" else (),
            fn=(
                _vmap_speculative_fn(_jm)
                if _alg == "speculative"
                else _vmap_data_parallel_fn
            ),
        )
    )
