"""Workload shapes, shape bucketing, and the candidate search space.

A *workload shape* is the 4-tuple the paper's §4 runtime model is written
over: record count M, node count N, attribute count A and tree depth d.
Candidates are (variant, params) pairs drawn from the kernel variant
registry (:mod:`repro.kernels.tree_eval.ops`); :func:`search_space`
enumerates only the candidates that are *valid* for a given shape: a Pallas
kernel whose record tile cannot fit VMEM (the onehot jump at N = 512) is
one the chip's compiler refuses, so it is never offered.

Shapes are *bucketed* before they key the cache: M rounds up to a power of
two, N and A round up to the 128-lane tile the kernels pad to anyway, and
depth rounds up to the next power of two.  Bucketing trades a little
optimality near bucket edges for cache hits across the jitter of real
request sizes — the same reason the serve engine pads waves.

Forest-level tuning adds :class:`ForestShape` — the (T, M, N_max, A,
depth-profile) operating point of a whole forest call — and
:func:`forest_search_space`, which enumerates the three candidate families
(per-tree variant vectors, the shared-variant vmap path, and the fused
stacked Pallas kernel) that :class:`repro.tune.ForestTunedEvaluator` ranks.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Iterator

import jax

from repro.kernels.tree_eval.cascade import (
    MAJORITY_FAMILY,
    exit_enabling_prefix,
    list_cascade_variants,
)
from repro.kernels.tree_eval.ops import (
    LANE,
    PER_TREE_FAMILY,
    SUBLANE,
    ForestVariantSpec,
    VariantSpec,
    _round_up,
    block_m_fits,
    choose_block_m,
    list_forest_variants,
    list_variants,
    on_tpu,
)

# One-hot speculative candidates materialise an (M, N) matmul against an
# (A, N) selection matrix; past this node count the matmul work dwarfs the
# gather it replaces on every backend we model.
MAX_ONEHOT_NODES = 2048

# Threshold dtypes the quantized-layout candidates sweep.  The dtype is a
# cache-identity parameter (consumed when the QuantizedForest packs), so
# winners tuned at different node dtypes never collide in the cache.
QUANT_THR_DTYPES = ("bfloat16", "float16")


def _next_pow2(x: int) -> int:
    x = max(int(x), 1)
    return 1 << (x - 1).bit_length()


def backend_tag() -> str:
    """Backend + device kind + topology tag for cache keys.

    ``jax.default_backend()`` alone conflates machine classes that tune very
    differently (v5e vs v5p TPUs, laptop vs server CPUs), and a winner tuned
    on one topology may lose on another (device count changes the shard
    shapes the dist executor asks about).  Keying on
    ``backend:device_kind:xN`` lets one shared cache file serve a
    heterogeneous fleet: every machine class reads and writes its own rows.
    """
    devs = jax.devices()
    kind = str(getattr(devs[0], "device_kind", "") or jax.default_backend())
    kind = re.sub(r"[^0-9A-Za-z_.-]+", "_", kind).strip("_").lower()
    return f"{jax.default_backend()}:{kind}:x{len(devs)}"


@dataclasses.dataclass(frozen=True)
class WorkloadShape:
    """The (M, N, A, depth) operating point of one tree-eval call."""

    m: int        # records
    n_nodes: int  # tree nodes (unpadded)
    n_attrs: int  # record attributes
    depth: int    # max root→leaf depth (edges)

    def bucket(self) -> "WorkloadShape":
        """Quantise to the cache-key granularity (idempotent)."""
        return WorkloadShape(
            m=_next_pow2(self.m),
            n_nodes=_round_up(max(self.n_nodes, 1), LANE),
            n_attrs=_round_up(max(self.n_attrs, 1), LANE),
            depth=_next_pow2(self.depth),
        )

    def key(self, backend: str | None = None) -> str:
        """Stable cache key: backend/topology tag + bucketed shape.

        ``backend`` defaults to :func:`backend_tag` (device kind + count),
        not the bare ``jax.default_backend()`` string.
        """
        b = self.bucket()
        tag = backend if backend is not None else backend_tag()
        return f"{tag}|M{b.m}|N{b.n_nodes}|A{b.n_attrs}|d{b.depth}"

    @classmethod
    def of(cls, records, enc, depth: int | None = None) -> "WorkloadShape":
        import numpy as np

        from repro.core.tree import tree_depth

        shape = np.asarray(records).shape if not hasattr(records, "shape") else records.shape
        return cls(
            m=int(shape[0]),
            n_nodes=int(enc.n_nodes),
            n_attrs=int(shape[1]),
            depth=int(depth if depth is not None else max(tree_depth(enc), 1)),
        )


@dataclasses.dataclass(frozen=True)
class Candidate:
    """A concrete (variant, parameter assignment) the tuner can time."""

    variant: str
    params: tuple[tuple[str, object], ...] = ()

    @property
    def param_dict(self) -> dict:
        return dict(self.params)

    @classmethod
    def make(cls, variant: str, **params) -> "Candidate":
        return cls(variant=variant, params=tuple(sorted(params.items())))


def _block_m_grid(shape: WorkloadShape, jump_mode: str) -> list[int]:
    """The VMEM model's block size and the next smaller power of two (the
    larger neighbour does not fit the model by construction)."""
    b = shape.bucket()
    base = choose_block_m(b.n_nodes, b.n_attrs, jump_mode=jump_mode)
    return sorted({base, max(base // 2, SUBLANE)})


def _jumps_grid(shape: WorkloadShape) -> list[int]:
    """Procedure-5 multi-jump factors worth trying (paper found 2 optimal)."""
    if shape.depth <= 2:
        return [1]
    return [1, 2, 3]


def default_engines() -> tuple[str, ...]:
    """Engines worth timing on this backend.

    On TPU the Pallas kernels are the real contenders and the jnp paths are
    kept for reference; off-TPU the kernels run in interpret mode (orders of
    magnitude slow, and not what dispatch would ever pick), so only the
    XLA-compiled jnp variants enter the space.
    """
    return ("pallas", "jnp") if on_tpu() else ("jnp",)


def variant_valid(spec: VariantSpec | ForestVariantSpec, shape: WorkloadShape) -> bool:
    """Whether ``spec`` can run at ``shape`` and is worth timing there.

    Onehot formulations stop past MAX_ONEHOT_NODES; a Pallas kernel also
    needs a record tile that fits VMEM (:func:`ops.block_m_fits`) — without
    one the chip's compiler refuses it.
    """
    if spec.jump_mode == "onehot" and shape.n_nodes > MAX_ONEHOT_NODES:
        return False
    b = shape.bucket()
    return spec.engine != "pallas" or block_m_fits(
        b.n_nodes, b.n_attrs, jump_mode=spec.jump_mode)


def search_space(
    shape: WorkloadShape,
    *,
    engines: tuple[str, ...] | None = None,
) -> Iterator[Candidate]:
    """Enumerate every candidate valid for ``shape``, cheapest-grid first.

    Args:
      shape: the (M, N, A, depth) operating point to tune for.
      engines: permitted engines ("pallas"/"jnp"); default =
        :func:`default_engines` for this backend.

    Yields:
      :class:`Candidate` values — each registered variant crossed with its
      tunable-parameter grid (block_m from the VMEM model ± a power of
      two, jumps_per_round from the Procedure-5 grid).
    """
    engines = default_engines() if engines is None else tuple(engines)
    for spec in list_variants():
        if spec.engine not in engines or not variant_valid(spec, shape):
            continue
        if "block_m" in spec.tunables:
            for bm in _block_m_grid(shape, spec.jump_mode):
                yield Candidate.make(spec.name, block_m=bm)
        elif "jumps_per_round" in spec.tunables:
            for j in _jumps_grid(shape):
                yield Candidate.make(spec.name, jumps_per_round=j)
        else:
            yield Candidate.make(spec.name)


# ---------------------------------------------------------------------------
# Forest-level shapes and candidates
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ForestShape:
    """The (T, M, N_max, A, depth profile) operating point of one forest call.

    The depth *profile* — (depth_min, depth_max) over the forest's trees —
    is what distinguishes forest buckets from a per-tree
    :class:`WorkloadShape`: a homogeneous profile favours the stacked
    families (padding every tree to the common geometry is free), a spread
    profile charges the stacked families ``depth_max`` rounds for trees that
    would finish in ``depth_min``.
    """

    t: int          # trees
    m: int          # records
    n_nodes: int    # common (padded) node count per tree — N_max
    n_attrs: int    # record attributes
    depth_min: int  # shallowest tree's max root→leaf depth (edges)
    depth_max: int  # deepest tree's max root→leaf depth (edges)

    def bucket(self) -> "ForestShape":
        """Quantise to the cache-key granularity (idempotent)."""
        return ForestShape(
            t=_next_pow2(self.t),
            m=_next_pow2(self.m),
            n_nodes=_round_up(max(self.n_nodes, 1), LANE),
            n_attrs=_round_up(max(self.n_attrs, 1), LANE),
            depth_min=_next_pow2(self.depth_min),
            depth_max=_next_pow2(self.depth_max),
        )

    def key(self, backend: str | None = None) -> str:
        """Stable cache key for the forest bucket.

        The ``T``/depth-profile components keep forest keys disjoint from
        the per-tree ``WorkloadShape`` keys in the same cache file.
        """
        b = self.bucket()
        tag = backend if backend is not None else backend_tag()
        return f"{tag}|T{b.t}|M{b.m}|N{b.n_nodes}|A{b.n_attrs}|d{b.depth_min}-{b.depth_max}"

    def tree_shape(self) -> WorkloadShape:
        """The padded common geometry as a per-tree shape (heuristic input)."""
        return WorkloadShape(
            m=self.m, n_nodes=self.n_nodes, n_attrs=self.n_attrs, depth=self.depth_max
        )

    def classes_key(self, n_classes: int, backend: str | None = None) -> str:
        """Cache key for the *class-level* (majority/cascade) bucket.

        Class-level winners answer a different question than forest winners
        — "what classes?" rather than "what per-tree matrix?" — and the
        candidate set depends on C (the vote tally width), so the key is the
        forest key suffixed with the class count.
        """
        return f"{self.key(backend)}|C{int(n_classes)}"

    @classmethod
    def of(
        cls,
        records,
        forest,
        *,
        depth_min: int | None = None,
        depth_max: int | None = None,
    ) -> "ForestShape":
        """Derive the shape from a record batch + EncodedForest.

        Per-tree depths cost an O(T·N) host pass; callers that hold a
        resolved evaluator (which computes them once) pass them in.
        """
        import numpy as np

        from repro.core.tree import tree_depth

        shape = np.asarray(records).shape if not hasattr(records, "shape") else records.shape
        if depth_min is None or depth_max is None:
            depths = [max(tree_depth(forest.tree(i)), 1) for i in range(forest.n_trees)]
            depth_min = min(depths) if depth_min is None else depth_min
            depth_max = max(depths) if depth_max is None else depth_max
        return cls(
            t=int(forest.n_trees),
            m=int(shape[0]),
            n_nodes=int(forest.n_nodes),
            n_attrs=int(shape[1]),
            depth_min=int(depth_min),
            depth_max=int(depth_max),
        )


def forest_variant_valid(spec: ForestVariantSpec, shape: ForestShape) -> bool:
    return variant_valid(spec, shape.tree_shape())


def forest_search_space(
    shape: ForestShape,
    *,
    engines: tuple[str, ...] | None = None,
    families: tuple[str, ...] | None = None,
    layouts: tuple[str, ...] | None = None,
) -> Iterator[Candidate]:
    """Enumerate every forest candidate valid for ``shape``.

    Three families compete (issue/ROADMAP: forest-level tuning):

      * ``per_tree`` — the PR 3 path: each tree dispatches through its own
        per-tree winner (a variant *vector*, represented by the sentinel
        candidate ``Candidate(PER_TREE_FAMILY)``);
      * ``vmap``     — one shared variant, the stacked jnp formulation
        ``vmap``-ed over the tree axis;
      * ``fused``    — the stacked Pallas kernel: one launch, tree axis on
        the grid.

    ``families`` restricts the enumeration (the dist executor asks only for
    the shared families — a shard body needs a single kern).  ``layouts``
    gates the node-table layouts: the default ``("f32",)`` keeps the
    enumeration to the full-width tables; opting in with
    ``("f32", "quant")`` adds the compact :class:`QuantizedForest`
    candidates, crossed over :data:`QUANT_THR_DTYPES` (the threshold dtype
    is part of the candidate — and therefore cache — identity).
    """
    engines = default_engines() if engines is None else tuple(engines)
    families = ("per_tree", "vmap", "fused") if families is None else tuple(families)
    layouts = ("f32",) if layouts is None else tuple(layouts)
    if PER_TREE_FAMILY in families and "f32" in layouts:
        yield Candidate.make(PER_TREE_FAMILY)
    for spec in list_forest_variants():
        if (
            spec.family not in families
            or spec.engine not in engines
            or getattr(spec, "layout", "f32") not in layouts
            or not forest_variant_valid(spec, shape)
        ):
            continue
        tshape = shape.tree_shape()
        if "thr_dtype" in spec.tunables:
            for td in QUANT_THR_DTYPES:
                if "block_m" in spec.tunables:
                    for bm in _block_m_grid(tshape, spec.jump_mode):
                        yield Candidate.make(spec.name, block_m=bm, thr_dtype=td)
                else:
                    yield Candidate.make(spec.name, thr_dtype=td)
        elif "block_m" in spec.tunables:
            for bm in _block_m_grid(tshape, spec.jump_mode):
                yield Candidate.make(spec.name, block_m=bm)
        elif "jumps_per_round" in spec.tunables:
            for j in _jumps_grid(tshape):
                yield Candidate.make(spec.name, jumps_per_round=j)
        else:
            yield Candidate.make(spec.name)


# ---------------------------------------------------------------------------
# Class-level (majority / cascade) candidates
# ---------------------------------------------------------------------------


def cascade_stage_grid(shape: ForestShape) -> list[int]:
    """Stage counts worth timing for a ``shape.t``-tree forest.

    A cascade needs the exit-enabling first stage (``k_min`` trees at
    bound 1.0) *plus* at least one later stage the exits can skip, so
    forests with fewer than 3 trees admit no useful cascade.  The later
    stages partition the ``t - k_min`` remaining trees; stage counts whose
    tail stages would be empty are dropped.
    """
    t = int(shape.t)
    if t < 3:
        return []
    k_min = exit_enabling_prefix(t, 1.0)
    rest = t - k_min
    if rest < 1:
        return []
    return [s for s in (2, 3, 4) if s - 1 <= rest]


def cascade_search_space(
    shape: ForestShape,
    n_classes: int,
    *,
    engines: tuple[str, ...] | None = None,
) -> Iterator[Candidate]:
    """Enumerate class-level candidates: full majority vote vs cascades.

    The baseline sentinel ``Candidate(MAJORITY_FAMILY)`` routes through the
    forest-level winner (all T trees) followed by ``majority_vote``; the
    cascade candidates cross each registered cascade variant with the stage
    grid (× the block-size grid for the pallas engine).  Every candidate is
    exact at bound 1.0, so the class-level choice never changes results.
    """
    del n_classes  # shapes the tally width, not the candidate set (kept for keying)
    engines = default_engines() if engines is None else tuple(engines)
    yield Candidate.make(MAJORITY_FAMILY)
    stage_grid = cascade_stage_grid(shape)
    if not stage_grid:
        return
    tshape = shape.tree_shape()
    for spec in list_cascade_variants():
        if spec.engine not in engines or not variant_valid(spec, tshape):
            continue
        for s in stage_grid:
            if "block_m" in spec.tunables:
                for bm in _block_m_grid(tshape, spec.jump_mode):
                    yield Candidate.make(spec.name, stages=s, block_m=bm)
            else:
                yield Candidate.make(spec.name, stages=s)
