"""Early-exit cascade evaluation of a packed forest (staged majority vote).

The paper's speculative decomposition spends SIMD lanes on work that *might*
be needed; the cascade is the dual lever at forest scale — stop spending
lanes on work that *cannot change the answer*.  Trees are ordered by
discriminative power and evaluated in stages; after each stage every
record's vote margin (top-1 minus top-2 vote count) is compared against a
confidence bound derived from the number of remaining trees:

    margin > bound * remaining

With ``bound = 1.0`` the inequality is exact — even if every remaining tree
voted for the runner-up class the leader could not be overtaken (strict
``>`` matters: the majority-vote argmax breaks ties toward the *lower*
class index, so a tied finish may flip the answer and must not exit).
Records that clear the bound exit; the survivors are **compacted** into a
dense tile (gather), the next stage runs only on them, and their votes are
scattered back.  Masked lanes therefore stop costing kernel time instead of
idling inside the tile.

``bound=None`` disables the exit entirely, making the cascade bit-identical
to ``majority_vote(eval_forest_tuned(...))`` (vote counts are invariant
under tree reordering).  ``bound < 1`` trades exactness for speed; the
per-record ``confidence`` output reports how decided each answer is.

An optional per-call ``deadline_ms`` gives *anytime* semantics: evaluation
stops at the deepest stage the remaining latency budget allows (stage 0
always runs) and the partial-margin confidence is reported for records the
truncated stages never re-examined.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.tree_eval import ops as _ops
from repro.kernels.tree_eval.ref import forest_eval_ref

# Vote margins are integer counts bounded by the forest size; a coarse
# power-of-two grid keeps the exit-margin histograms readable at any T.
_MARGIN_BOUNDARIES = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)

# Family name the class-level tuner uses for the plain "evaluate everything,
# then majority-vote" path (no early exit); defined next to the cascade
# registry so the cache vocabulary for class-level winners lives in one place.
MAJORITY_FAMILY = "forest_majority"

CASCADE_FAMILY = "cascade"


# ---------------------------------------------------------------------------
# Plan: tree order + stage geometry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CascadePlan:
    """Tree evaluation order and how many trees each stage takes.

    ``order`` is a permutation of the forest's tree indices, most
    discriminative first; ``stage_sizes`` partitions it into consecutive
    stages.  The first stage is the *exit-enabling prefix*: with ``k`` trees
    evaluated and ``T - k`` remaining, an exit requires
    ``margin > bound * (T - k)`` and the margin after ``k`` unanimous trees
    is at most ``k``, so the smallest useful first stage is
    ``k_min = floor(bound * T / (1 + bound)) + 1``.
    """

    order: tuple[int, ...]
    stage_sizes: tuple[int, ...]

    def __post_init__(self):
        if sum(self.stage_sizes) != len(self.order):
            raise ValueError(
                f"stage_sizes {self.stage_sizes} must partition the "
                f"{len(self.order)}-tree order"
            )
        if any(s <= 0 for s in self.stage_sizes):
            raise ValueError(f"stage sizes must be positive: {self.stage_sizes}")
        if sorted(self.order) != list(range(len(self.order))):
            raise ValueError("order must be a permutation of range(n_trees)")

    @property
    def n_trees(self) -> int:
        return len(self.order)

    @property
    def n_stages(self) -> int:
        return len(self.stage_sizes)

    def stage_trees(self, s: int) -> tuple[int, ...]:
        start = sum(self.stage_sizes[:s])
        return self.order[start : start + self.stage_sizes[s]]


def exit_enabling_prefix(n_trees: int, bound: float) -> int:
    """Smallest first-stage size after which an early exit is possible."""
    k = int(np.floor(bound * n_trees / (1.0 + bound))) + 1
    return min(max(k, 1), n_trees)


def rank_trees(forest, records, *, n_classes: int, sample: int = 512) -> tuple[int, ...]:
    """Order trees by agreement with the full-forest majority vote.

    A tree that usually agrees with the ensemble's final answer drives the
    margin up fastest when placed early, which is exactly what the exit
    bound rewards.  Ranked on (a sample of) a calibration batch via the
    reference evaluator; stable sort keeps the original order among ties so
    plans are deterministic.
    """
    rec = np.asarray(records, np.float32)
    if rec.ndim != 2 or rec.shape[0] == 0:
        return tuple(range(int(forest.n_trees)))
    rec = rec[: max(1, int(sample))]
    per_tree = np.asarray(
        forest_eval_ref(
            jnp.asarray(rec),
            jnp.asarray(forest.attr_idx, jnp.int32),
            jnp.asarray(forest.threshold, jnp.float32),
            jnp.asarray(forest.child, jnp.int32),
            jnp.asarray(forest.class_val, jnp.int32),
            max_depth=int(forest.max_depth),
        )
    )  # (T, M)
    c = max(int(n_classes), int(per_tree.max(initial=0)) + 1, 2)
    votes = np.zeros((rec.shape[0], c), np.int32)
    for t in range(per_tree.shape[0]):
        votes[np.arange(rec.shape[0]), per_tree[t]] += 1
    maj = votes.argmax(axis=1)
    agreement = (per_tree == maj[None, :]).mean(axis=1)
    return tuple(int(i) for i in np.argsort(-agreement, kind="stable"))


def plan_cascade(
    forest,
    records=None,
    *,
    n_classes: int,
    stages: int = 2,
    bound: float | None = 1.0,
    sample: int = 512,
    order: tuple[int, ...] | None = None,
) -> CascadePlan:
    """Build a :class:`CascadePlan` for ``forest``.

    Args:
      records: optional calibration batch used to rank trees by
        discriminative power (see :func:`rank_trees`); without it trees run
        in their stored order.
      stages: requested stage count (clamped to what the forest admits).
      bound: the exit bound the plan should enable; sizes the first stage at
        the exit-enabling prefix.  ``None`` plans as if ``1.0``.
      order: explicit tree order overriding calibration.
    """
    t = int(forest.n_trees)
    if order is None:
        if records is not None:
            order = rank_trees(forest, records, n_classes=n_classes, sample=sample)
        else:
            order = tuple(range(t))
    order = tuple(int(i) for i in order)
    if sorted(order) != list(range(t)):
        raise ValueError("order must be a permutation of the forest's tree indices")
    stages = max(1, min(int(stages), t))
    b = 1.0 if bound is None else float(bound)
    if b <= 0.0:
        raise ValueError(f"bound must be positive (or None), got {bound}")
    if stages == 1:
        return CascadePlan(order=order, stage_sizes=(t,))
    first = exit_enabling_prefix(t, b)
    rest = t - first
    n_rest = min(stages - 1, rest)
    if n_rest == 0:
        return CascadePlan(order=order, stage_sizes=(t,))
    base, extra = divmod(rest, n_rest)
    sizes = (first,) + tuple(base + (1 if i < extra else 0) for i in range(n_rest))
    return CascadePlan(order=order, stage_sizes=sizes)


# ---------------------------------------------------------------------------
# Stage vote engines
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("max_depth", "n_classes", "algorithm", "jump_mode")
)
def _votes_jnp(
    records,
    attr_idx,
    threshold,
    child,
    class_val,
    *,
    max_depth: int,
    n_classes: int,
    algorithm: str,
    jump_mode: str,
):
    """(M, C) vote counts for one stage's trees via the jnp evaluators."""
    from repro.core.eval_dataparallel import eval_data_parallel
    from repro.core.eval_speculative import eval_speculative

    rec = jnp.asarray(records, jnp.float32)

    def one(a, t, c, k):
        if algorithm == "speculative":
            return eval_speculative(
                rec, a, t, c, k,
                max_depth=max_depth,
                use_onehot_matmul=(jump_mode == "onehot"),
            )
        return eval_data_parallel(rec, a, t, c, k, max_depth=max_depth)

    per_tree = jax.vmap(one)(attr_idx, threshold, child, class_val)  # (S, M)
    onehot = jax.nn.one_hot(per_tree, n_classes, dtype=jnp.int32)    # (S, M, C)
    return onehot.sum(axis=0)


class _StageForest:
    """View of a subset of a forest's trees (PackedForest-compatible)."""

    def __init__(self, forest, tree_ids: tuple[int, ...]):
        self._forest = forest
        self._ids = tuple(tree_ids)
        self.n_trees = len(self._ids)
        self.n_nodes = int(forest.n_nodes)
        self.max_depth = int(forest.max_depth)

    def tree(self, i: int):
        return self._forest.tree(self._ids[i])


# ---------------------------------------------------------------------------
# Evaluator
# ---------------------------------------------------------------------------


class CascadeResult(NamedTuple):
    """Per-record outcome of one cascade evaluation.

    Attributes:
      classes: (M,) int32 predicted class per record.
      margin: (M,) int32 final top-1 minus top-2 vote count.
      trees_evaluated: (M,) int32 trees that actually voted per record.
      exit_stage: (M,) int32 stage index at which the record cleared the
        bound, or -1 (ran every executed stage).
      stages_run: number of stages executed (< plan.n_stages when the
        deadline truncated the cascade or every record exited).
      confidence: (M,) float32 in [0, 1]; 1.0 when the answer is provably
        final, otherwise the partial-margin ratio ``margin / remaining``.
      stage_survivors: records entering each executed stage.
    """

    classes: np.ndarray
    margin: np.ndarray
    trees_evaluated: np.ndarray
    exit_stage: np.ndarray
    stages_run: int
    confidence: np.ndarray
    stage_survivors: tuple[int, ...]


def _pad_rows(n: int) -> int:
    """Bucket a survivor count to the next power of two (≥ one sublane)."""
    p = _ops.SUBLANE
    while p < n:
        p *= 2
    return p


class CascadeEvaluator:
    """Staged early-exit forest evaluator with inter-stage compaction.

    The stage loop runs on the host: surviving record indices are gathered
    into a dense tile (padded to a power-of-two row count so stage kernels
    retrace only O(log M) times), the stage's vote kernel accumulates
    (rows, C) vote counts on device, and the votes are scattered back into
    the full (M, C) tally.  Exit decisions are pure numpy on the tally.

    Args:
      forest: an ``EncodedForest`` (or anything with its surface).
      plan: explicit :class:`CascadePlan`; default = :func:`plan_cascade`
        over ``calibration`` (or stored tree order).
      n_classes: number of vote classes C.
      bound: exit bound; ``1.0`` exact (default), ``< 1`` relaxed,
        ``None`` disabled (full evaluation, bit-identical to majority vote).
      engine: "pallas" (fused vote kernel) or "jnp" (vmap evaluators);
        default pallas on TPU, jnp elsewhere.
      algorithm / jump_mode / block_m: forwarded to the stage kernels.
      stages / calibration: used only when ``plan`` is None.
    """

    def __init__(
        self,
        forest,
        plan: CascadePlan | None = None,
        *,
        n_classes: int,
        bound: float | None = 1.0,
        engine: str | None = None,
        algorithm: str = "speculative",
        jump_mode: str = "gather",
        block_m: int | None = None,
        stages: int = 2,
        calibration=None,
        registry: obs.Registry | None = None,
        tracer: obs.Tracer | None = None,
    ):
        if bound is not None and float(bound) <= 0.0:
            raise ValueError(f"bound must be positive or None, got {bound}")
        if engine is None:
            engine = "pallas" if _ops.on_tpu() else "jnp"
        if engine not in ("pallas", "jnp"):
            raise ValueError(f"unknown engine {engine!r}")
        self.forest = forest
        self.n_classes = int(n_classes)
        self._c = max(self.n_classes, 2)
        self.bound = None if bound is None else float(bound)
        self.engine = engine
        self.algorithm = algorithm
        self.jump_mode = jump_mode
        self.block_m = block_m
        if plan is None:
            plan = plan_cascade(
                forest,
                calibration,
                n_classes=self.n_classes,
                stages=stages,
                bound=self.bound,
            )
        if plan.n_trees != int(forest.n_trees):
            raise ValueError(
                f"plan covers {plan.n_trees} trees, forest has {forest.n_trees}"
            )
        self.plan = plan
        self._stages = [self._build_stage(s) for s in range(plan.n_stages)]
        # (stage, padded_rows) → EMA of observed stage latency, for the
        # anytime deadline check.
        self._stage_ms: dict[tuple[int, int], float] = {}
        self.obs = registry if registry is not None else obs.Registry()
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        r = self.obs
        self.m_evals = r.counter("cascade.evals", "cascade evaluations")
        self.m_records = r.counter("cascade.records", "records evaluated")
        self.m_stage_ms = r.histogram(
            "cascade.stage_ms", "per-stage kernel latency", ("stage",))
        self.m_survival = r.histogram(
            "cascade.stage_survival",
            "fraction of the batch entering each stage", ("stage",),
            boundaries=obs.DEFAULT_RATIO_BOUNDARIES)
        self.m_exit_margin = r.histogram(
            "cascade.exit_margin", "final top-1 minus top-2 vote margins",
            boundaries=_MARGIN_BOUNDARIES)
        self.m_compact_ms = r.histogram(
            "cascade.compact_ms",
            "host-side survivor compaction per stage (gather + scatter + exit test)",
            ("stage",))

    # -- stage construction -------------------------------------------------

    def _build_stage(self, s: int) -> Callable:
        """Stage ``s``'s vote program: a traceable function of a device
        record tile (rows, A) returning its (rows, C) vote counts."""
        ids = self.plan.stage_trees(s)
        if self.engine == "pallas":
            # The packed tables depend on the record attribute count, which
            # EncodedForest does not store — pack lazily on first call.
            packed_by_a: dict[int, _ops.PackedForest] = {}

            def votes(rec: jax.Array) -> jax.Array:
                a = rec.shape[1]
                packed = packed_by_a.get(a)
                if packed is None:
                    packed = _ops.PackedForest(_StageForest(self.forest, ids), a)
                    packed_by_a[a] = packed
                return _ops.forest_votes_fused(
                    rec,
                    packed,
                    n_classes=self._c,
                    algorithm=self.algorithm,
                    jump_mode=self.jump_mode,
                    block_m=self.block_m,
                )

            return votes

        idx = list(ids)
        tables = (
            jnp.asarray(np.asarray(self.forest.attr_idx)[idx], jnp.int32),
            jnp.asarray(np.asarray(self.forest.threshold)[idx], jnp.float32),
            jnp.asarray(np.asarray(self.forest.child)[idx], jnp.int32),
            jnp.asarray(np.asarray(self.forest.class_val)[idx], jnp.int32),
        )
        max_depth = int(self.forest.max_depth)

        def votes(rec: jax.Array) -> jax.Array:
            return _votes_jnp(
                rec,
                *tables,
                max_depth=max_depth,
                n_classes=self._c,
                algorithm=self.algorithm,
                jump_mode=self.jump_mode,
            )

        return votes

    def _stage_votes(self, s: int, rec: np.ndarray) -> tuple[np.ndarray, int]:
        """Run stage ``s`` on a dense record tile; returns (votes, pad_rows)."""
        n = rec.shape[0]
        rows = _pad_rows(n)
        if rows != n:
            rec = np.concatenate(
                [rec, np.zeros((rows - n, rec.shape[1]), rec.dtype)], axis=0
            )
        t0 = time.perf_counter()
        with self.tracer.span("cascade.stage", cat="cascade", stage=s,
                              survivors=n, rows=rows):
            votes = np.asarray(jax.block_until_ready(
                self._stages[s](jnp.asarray(rec))))[:n]
        ms = (time.perf_counter() - t0) * 1e3
        self.m_stage_ms.labels(stage=s).observe(ms)
        key = (s, rows)
        prev = self._stage_ms.get(key)
        self._stage_ms[key] = ms if prev is None else 0.7 * prev + 0.3 * ms
        return votes, rows

    def _stage_estimate_ms(self, s: int, n: int) -> float:
        """Predicted latency of stage ``s`` over ``n`` records (0 = unknown)."""
        rows = _pad_rows(n)
        est = self._stage_ms.get((s, rows))
        if est is not None:
            return est
        # fall back to the nearest observed bucket for this stage
        seen = [(abs(r - rows), v) for (si, r), v in self._stage_ms.items() if si == s]
        return min(seen)[1] if seen else 0.0

    # -- evaluation ---------------------------------------------------------

    def __call__(self, records, *, deadline_ms: float | None = None) -> CascadeResult:
        rec = np.asarray(records, np.float32)
        if rec.ndim != 2:
            raise ValueError(f"records must be (M, A), got {rec.shape}")
        m = rec.shape[0]
        t_total = self.plan.n_trees
        votes = np.zeros((m, self._c), np.int32)
        trees_evaluated = np.zeros((m,), np.int32)
        exit_stage = np.full((m,), -1, np.int32)
        alive = np.arange(m)
        survivors: list[int] = []
        stages_run = 0
        self.m_evals.inc()
        self.m_records.inc(m)
        espan = self.tracer.span("cascade.eval", cat="cascade", records=m,
                                 deadline_ms=deadline_ms)
        t_start = time.perf_counter()

        with espan:
            for s, size in enumerate(self.plan.stage_sizes):
                if alive.size == 0:
                    break
                if deadline_ms is not None and s > 0:
                    elapsed = (time.perf_counter() - t_start) * 1e3
                    if elapsed + self._stage_estimate_ms(s, alive.size) > deadline_ms:
                        break
                survivors.append(int(alive.size))
                self.m_survival.labels(stage=s).observe(alive.size / max(m, 1))
                # Survivor compaction is host numpy today (see ROADMAP: a
                # Pallas prefix-scan would keep it on-device) — time both
                # halves so it stops being invisible next to the kernels.
                c0 = time.perf_counter()
                with self.tracer.span("cascade.compact", cat="cascade", stage=s,
                                      phase="gather", survivors=int(alive.size)):
                    stage_rec = rec[alive]
                compact_ms = (time.perf_counter() - c0) * 1e3
                stage_votes, _ = self._stage_votes(s, stage_rec)
                c1 = time.perf_counter()
                with self.tracer.span("cascade.compact", cat="cascade", stage=s,
                                      phase="scatter", survivors=int(alive.size)):
                    votes[alive] += stage_votes
                    trees_evaluated[alive] += size
                    stages_run = s + 1
                    remaining = t_total - int(trees_evaluated[alive[0]]) if alive.size else 0
                    if self.bound is not None and remaining > 0:
                        va = votes[alive]
                        top2 = np.partition(va, -2, axis=1)[:, -2:]
                        margin = top2[:, 1] - top2[:, 0]
                        decided = margin > self.bound * remaining
                        if decided.any():
                            exit_stage[alive[decided]] = s
                            alive = alive[~decided]
                compact_ms += (time.perf_counter() - c1) * 1e3
                self.m_compact_ms.labels(stage=s).observe(compact_ms)
            espan.set(stages_run=stages_run)

        classes = votes.argmax(axis=1).astype(np.int32)
        top2 = np.partition(votes, -2, axis=1)[:, -2:]
        margin = (top2[:, 1] - top2[:, 0]).astype(np.int32)
        self.m_exit_margin.observe_many(margin)
        remaining_all = (t_total - trees_evaluated).astype(np.int32)
        with np.errstate(divide="ignore", invalid="ignore"):
            conf = np.where(
                remaining_all <= 0,
                1.0,
                np.clip(margin / np.maximum(remaining_all, 1), 0.0, 1.0),
            ).astype(np.float32)
        return CascadeResult(
            classes=classes,
            margin=margin,
            trees_evaluated=trees_evaluated,
            exit_stage=exit_stage,
            stages_run=stages_run,
            confidence=conf,
            stage_survivors=tuple(survivors),
        )


def eval_cascade(
    forest,
    records,
    *,
    n_classes: int,
    stages: int = 2,
    bound: float | None = 1.0,
    plan: CascadePlan | None = None,
    calibration=None,
    engine: str | None = None,
    algorithm: str = "speculative",
    jump_mode: str = "gather",
    block_m: int | None = None,
    deadline_ms: float | None = None,
    registry: "obs.Registry | None" = None,
    tracer: "obs.Tracer | None" = None,
) -> CascadeResult:
    """One-shot cascade evaluation (builds a :class:`CascadeEvaluator`).

    For repeated batches build the evaluator once — it caches per-stage
    packed tables, compiled kernels and latency estimates.
    """
    ev = CascadeEvaluator(
        forest,
        plan,
        n_classes=n_classes,
        bound=bound,
        engine=engine,
        algorithm=algorithm,
        jump_mode=jump_mode,
        block_m=block_m,
        stages=stages,
        calibration=calibration if calibration is not None else records,
        registry=registry,
        tracer=tracer,
    )
    return ev(records, deadline_ms=deadline_ms)


# ---------------------------------------------------------------------------
# Cascade variant registry (consumed by repro.tune's class-level tuner)
# ---------------------------------------------------------------------------
#
# A cascade variant *builds* a CascadeEvaluator rather than evaluating a
# batch directly: the evaluator is stateful (packed stage tables, latency
# EMAs), so the dispatch layer constructs it once per resolved bucket and
# replays it per batch.  Contract:
#
#     spec.build(forest, *, n_classes, plan=None, stages, bound, block_m,
#                calibration=None) -> CascadeEvaluator


@dataclasses.dataclass(frozen=True)
class CascadeVariantSpec:
    """One cascade evaluator configuration plus its tunable knobs.

    ``family`` is always :data:`CASCADE_FAMILY`; ``tunables`` always
    includes ``"stages"`` (the stage-count grid) and, for the pallas
    engine, ``"block_m"``.
    """

    name: str
    family: str
    algorithm: str
    engine: str
    jump_mode: str
    tunables: tuple[str, ...]
    build: Callable


CASCADE_VARIANTS: dict[str, CascadeVariantSpec] = {}


def register_cascade_variant(spec: CascadeVariantSpec) -> CascadeVariantSpec:
    if spec.name in CASCADE_VARIANTS:
        raise ValueError(f"cascade variant {spec.name!r} already registered")
    CASCADE_VARIANTS[spec.name] = spec
    return spec


def get_cascade_variant(name: str) -> CascadeVariantSpec:
    try:
        return CASCADE_VARIANTS[name]
    except KeyError:
        raise KeyError(
            f"unknown cascade variant {name!r}; registered: {sorted(CASCADE_VARIANTS)}"
        ) from None


def list_cascade_variants(*, engine: str | None = None) -> list[CascadeVariantSpec]:
    out = [
        s for s in CASCADE_VARIANTS.values() if engine is None or s.engine == engine
    ]
    return sorted(out, key=lambda s: s.name)


def _builder(engine: str, algorithm: str, jump_mode: str) -> Callable:
    def build(
        forest,
        *,
        n_classes: int,
        plan: CascadePlan | None = None,
        stages: int = 2,
        bound: float | None = 1.0,
        block_m: int | None = None,
        calibration=None,
        registry: obs.Registry | None = None,
        tracer: obs.Tracer | None = None,
    ) -> CascadeEvaluator:
        return CascadeEvaluator(
            forest,
            plan,
            n_classes=n_classes,
            bound=bound,
            engine=engine,
            algorithm=algorithm,
            jump_mode=jump_mode,
            block_m=block_m,
            stages=stages,
            calibration=calibration,
            registry=registry,
            tracer=tracer,
        )

    return build


for _alg, _jm in (("speculative", "gather"), ("speculative", "onehot"), ("data_parallel", "gather")):
    _suffix = f"_{_jm}" if _alg == "speculative" else ""
    register_cascade_variant(
        CascadeVariantSpec(
            name=f"forest_cascade_fused_{_alg}" + _suffix,
            family=CASCADE_FAMILY,
            algorithm=_alg,
            engine="pallas",
            jump_mode=_jm,
            tunables=("stages", "block_m"),
            build=_builder("pallas", _alg, _jm),
        )
    )
    register_cascade_variant(
        CascadeVariantSpec(
            name=f"forest_cascade_vmap_{_alg}" + _suffix,
            family=CASCADE_FAMILY,
            algorithm=_alg,
            engine="jnp",
            jump_mode=_jm,
            tunables=("stages",),
            build=_builder("jnp", _alg, _jm),
        )
    )
