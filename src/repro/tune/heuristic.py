"""Model-based fallback: pick a variant from the paper's runtime analysis.

When the cache has no entry for a shape bucket (first call on a new machine,
or tuning disabled) dispatch still has to pick a variant.  We evaluate the
paper's closed-form runtime model (§3.6 / §4 analysis, `repro.core.analysis`)
at the workload's operating point:

    T₃(P) = (M/P)·d_µ·(t_e + t_c) + t_i + t_s(M)          (data decomposition)
    T₅(P) = (M·p/P)·(t_e + log₂(d_µ)·t_c) + t_i + t_s(M)  (speculative)

with p = the record-group processor count, which in our TPU mapping is the
number of *internal* nodes each record's lane-group evaluates speculatively.
The cheaper predicted time picks the algorithm — equivalently, equation (1)'s
crossover ``p < 2·d_µ/(1 + log₂ d_µ)`` under t_e ≈ t_c — and backend rules
pick engine/jump-mode (Pallas + one-hot MXU on TPU, XLA gather elsewhere).
"""

from __future__ import annotations

from repro.core.analysis import CostModel, t3_data_parallel, t5_speculative
from repro.kernels.tree_eval.cascade import MAJORITY_FAMILY, plan_cascade
from repro.kernels.tree_eval.ops import PER_TREE_FAMILY, block_m_fits, choose_block_m, on_tpu
from repro.tune.space import (
    MAX_ONEHOT_NODES,
    Candidate,
    ForestShape,
    WorkloadShape,
    cascade_stage_grid,
    default_engines,
)

# Per-launch dispatch overhead in §3.6 node-evaluation units (the planner's
# γ_launch prior): what the per-tree family pays T times and the stacked
# families pay once.  Only the *ratio* against the compute terms matters —
# the heuristic ranks families, it does not predict milliseconds.
FOREST_LAUNCH_OVERHEAD = 50.0


def _pallas_jump_mode(shape: WorkloadShape) -> str:
    """The Pallas pointer-jump flavour: the all-MXU onehot jump on TPU
    where its (BM, N, N) one-hot fits VMEM, lane gathers otherwise."""
    b = shape.bucket()
    onehot_ok = (
        on_tpu()
        and shape.n_nodes <= MAX_ONEHOT_NODES
        and block_m_fits(b.n_nodes, b.n_attrs, jump_mode="onehot")
    )
    return "onehot" if onehot_ok else "gather"


def default_p_group(shape: WorkloadShape) -> int:
    """Processors per record group: the internal nodes of a full binary tree.

    The paper's p — each record group assigns one processor per internal
    node during speculative node evaluation ((N-1)/2 for a full tree).
    """
    return max(1, (shape.n_nodes - 1) // 2)


def default_d_mu(shape: WorkloadShape) -> float:
    """Estimated mean traversal depth when no measurement is supplied.

    Real d_µ lies between log₂(leaves) (balanced) and depth (vine); the
    midpoint is a serviceable prior for an untuned shape.
    """
    import math

    balanced = math.log2(max(shape.n_nodes, 2))
    return max(1.0, (balanced + shape.depth) / 2.0)


def measured_d_mu(enc, records, *, sample: int = 256) -> float:
    """d_µ measured on a record sample (the paper's "significant sample").

    The geometry prior of :func:`default_d_mu` can sit far from the truth —
    a deep vine whose traffic all exits at the first split has measured
    d_µ ≈ 1 but a large prior — and equation (1)'s crossover moves with d_µ,
    so the prior can pick the wrong algorithm.  Dispatch feeds the actual
    batch through the branchless descent (host-side, on at most ``sample``
    records) and hands the measured mean to the §3.6 model instead.
    """
    import numpy as np

    from repro.core.analysis import mean_traversal_depth, observed_depths

    rec = np.asarray(records)
    if rec.shape[0] == 0:
        return 1.0
    if rec.shape[0] > sample:
        rec = rec[:sample]
    return max(1.0, float(mean_traversal_depth(observed_depths(enc, rec))))


def predicted_times(
    shape: WorkloadShape,
    *,
    cm: CostModel = CostModel(),
    d_mu: float | None = None,
    p_group: float | None = None,
    p_total: float = 1.0,
) -> dict[str, float]:
    """§3.6 model runtimes per algorithm for this shape.

    Args:
      shape: the (M, N, A, depth) operating point.
      cm: §3.6 machine constants (t_e, t_c, t_i, σ, γ).
      d_mu: mean traversal depth; default = the geometry prior.
      p_group: processors per record group; default = internal-node count.
      p_total: total processors P the work divides over.

    Returns:
      {"data_parallel": T₃, "speculative": T₅} in model units — rank-valid
      per shape, not milliseconds.
    """
    d = d_mu if d_mu is not None else default_d_mu(shape)
    d = max(float(d), 1.0)
    p = p_group if p_group is not None else default_p_group(shape)
    return {
        "data_parallel": t3_data_parallel(shape.m, d, p_total, cm),
        "speculative": t5_speculative(shape.m, d, p_total, p, cm),
    }


def heuristic_candidate(
    shape: WorkloadShape,
    *,
    cm: CostModel = CostModel(),
    d_mu: float | None = None,
    p_group: float | None = None,
    engines: tuple[str, ...] | None = None,
) -> Candidate:
    """Shape-derived variant choice mirroring the paper's analysis."""
    times = predicted_times(shape, cm=cm, d_mu=d_mu, p_group=p_group)
    algorithm = min(times, key=times.get)
    engines = default_engines() if engines is None else tuple(engines)
    engine = "pallas" if "pallas" in engines else "jnp"

    if engine == "pallas":
        if algorithm == "data_parallel":
            name, jump_mode = "pallas_data_parallel", "gather"
        else:
            jump_mode = _pallas_jump_mode(shape)
            name = f"pallas_speculative_{jump_mode}"
        b = shape.bucket()
        bm = choose_block_m(b.n_nodes, b.n_attrs, jump_mode=jump_mode)
        return Candidate.make(name, block_m=bm)

    if algorithm == "data_parallel":
        return Candidate.make("jnp_data_parallel")
    # paper: 2 jumps per synchronisation round was the measured optimum
    return Candidate.make("jnp_speculative_gather", jumps_per_round=2)


# ---------------------------------------------------------------------------
# Forest-level heuristic: per-tree vector vs stacked (vmap / fused)
# ---------------------------------------------------------------------------


def measured_forest_d_mu(forest, records, *, trees: int = 4, sample: int = 256) -> float:
    """Forest d_µ: measured mean over a few trees × a record sample.

    Args:
      forest: an :class:`repro.core.forest.EncodedForest`.
      records: (M, A) record batch (host or device array).
      trees: how many trees to walk (the first ``min(T, trees)``).
      sample: records per tree (:func:`measured_d_mu`'s sample bound).

    Returns:
      Mean traversal depth ≥ 1.0 — the d_µ the §3.6 forms are evaluated at.
    """
    import numpy as np

    rec = np.asarray(records)[:sample]
    picked = range(min(int(forest.n_trees), max(trees, 1)))
    return float(np.mean([measured_d_mu(forest.tree(i), rec, sample=sample) for i in picked]))


def forest_heuristic_candidate(
    shape: ForestShape,
    *,
    cm: CostModel = CostModel(),
    d_mu: float | None = None,
    p_group: float | None = None,
    engines: tuple[str, ...] | None = None,
    families: tuple[str, ...] | None = None,
    launch_overhead: float = FOREST_LAUNCH_OVERHEAD,
) -> Candidate:
    """Model-based forest family + variant choice (the no-cache fallback).

    The stacked families evaluate every tree at the *padded* common geometry
    — each tree pays the deepest tree's rounds — but launch once; the
    per-tree family pays each tree's own depth but launches T times.  With
    t(d) = the §3.6 winner's time at depth-profile point d:

        stacked  ≈ T · t(depth_max)                + γ
        per-tree ≈ T · (t(depth_min)+t(depth_max))/2 + T·γ

    (the midpoint is the depth-profile prior for the mean per-tree cost).
    A homogeneous profile therefore always picks a stacked family; a spread
    profile flips to per-tree once the padding waste outgrows the saved
    launches.  Within a stacked family, engine rules mirror
    :func:`heuristic_candidate`: fused Pallas on TPU, the vmap jnp path off
    it.

    Args:
      shape: the forest operating point (T, M, N_max, A, depth profile).
      cm / d_mu / p_group: §3.6 model inputs, as in :func:`predicted_times`.
      engines: permitted engines; default = :func:`default_engines`.
      families: permitted families; default = all three.
      launch_overhead: γ in node-evaluation units.

    Returns:
      A :class:`Candidate` — ``Candidate(PER_TREE_FAMILY)`` or a registered
      forest variant with its parameters filled in.
    """
    engines = default_engines() if engines is None else tuple(engines)
    families = ("per_tree", "vmap", "fused") if families is None else tuple(families)

    deep = WorkloadShape(m=shape.m, n_nodes=shape.n_nodes,
                         n_attrs=shape.n_attrs, depth=shape.depth_max)
    shallow = WorkloadShape(m=shape.m, n_nodes=shape.n_nodes,
                            n_attrs=shape.n_attrs, depth=shape.depth_min)

    def best_time(s: WorkloadShape, d: float | None) -> float:
        return min(predicted_times(s, cm=cm, d_mu=d, p_group=p_group).values())

    # d_µ scales with the profile point: a measured/maximum-depth d_µ maps
    # onto the shallow end proportionally (the prior does this implicitly).
    d_deep = d_mu
    d_shallow = None if d_mu is None else max(1.0, d_mu * shape.depth_min / max(shape.depth_max, 1))
    t_deep = best_time(deep, d_deep)
    t_shallow = best_time(shallow, d_shallow)

    stacked_cost = shape.t * t_deep + launch_overhead
    per_tree_cost = shape.t * (t_deep + t_shallow) / 2.0 + shape.t * launch_overhead

    # a stacked family is usable only when its engine is permitted: fused is
    # the Pallas path, vmap the jnp one (forest_search_space filters the
    # same way, so the heuristic never names a candidate the space excludes)
    stacked_ok = [
        f for f in ("fused", "vmap")
        if f in families and (("pallas" in engines) if f == "fused" else ("jnp" in engines))
    ]
    if not stacked_ok and PER_TREE_FAMILY not in families:
        # the caller forced stacked families whose engines they excluded:
        # honour the family request over the engine filter, native engine
        stacked_ok = [f for f in ("fused", "vmap") if f in families]
    want_stacked = bool(stacked_ok) and (
        PER_TREE_FAMILY not in families or stacked_cost <= per_tree_cost
    )
    if not want_stacked:
        return Candidate.make(PER_TREE_FAMILY)

    family = stacked_ok[0]
    engine = "pallas" if family == "fused" else "jnp"

    times = predicted_times(deep, cm=cm, d_mu=d_deep, p_group=p_group)
    algorithm = min(times, key=times.get)
    if algorithm == "data_parallel":
        name, jump_mode = f"forest_{family}_data_parallel", "gather"
    else:
        jump_mode = _pallas_jump_mode(deep) if engine == "pallas" else "gather"
        name = f"forest_{family}_speculative_{jump_mode}"

    if family == "fused":
        b = shape.bucket()
        bm = choose_block_m(b.n_nodes, b.n_attrs, jump_mode=jump_mode)
        return Candidate.make(name, block_m=bm)
    if algorithm == "speculative":
        # paper: 2 jumps per synchronisation round was the measured optimum
        return Candidate.make(name, jumps_per_round=2)
    return Candidate.make(name)


# ---------------------------------------------------------------------------
# Class-level heuristic: full majority vote vs early-exit cascade
# ---------------------------------------------------------------------------


def measured_survival_rate(
    forest,
    records,
    n_classes: int,
    *,
    plan=None,
    stages: int = 2,
    bound: float = 1.0,
    sample: int = 256,
) -> tuple[float, ...]:
    """Fraction of records entering each cascade stage, measured on a sample.

    Simulates the exit rule on the reference per-tree classes (host numpy,
    no kernels): accumulate votes stage by stage in the plan's tree order
    and retire records whose margin exceeds ``bound`` times the remaining
    tree count.  Element 0 is always 1.0; the tail elements are the
    survival-rate term the §3.6-style cascade model multiplies stage costs
    by.
    """
    import numpy as np

    import jax.numpy as jnp

    from repro.kernels.tree_eval.ref import forest_eval_ref

    rec = np.asarray(records, np.float32)[: max(1, int(sample))]
    if plan is None:
        plan = plan_cascade(forest, rec, n_classes=n_classes, stages=stages, bound=bound)
    per_tree = np.asarray(
        forest_eval_ref(
            jnp.asarray(rec),
            jnp.asarray(forest.attr_idx, jnp.int32),
            jnp.asarray(forest.threshold, jnp.float32),
            jnp.asarray(forest.child, jnp.int32),
            jnp.asarray(forest.class_val, jnp.int32),
            max_depth=int(forest.max_depth),
        )
    )
    m = rec.shape[0]
    t_total = plan.n_trees
    c = max(int(n_classes), int(per_tree.max(initial=0)) + 1, 2)
    votes = np.zeros((m, c), np.int32)
    alive = np.ones((m,), bool)
    out: list[float] = []
    done = 0
    for size in plan.stage_sizes:
        out.append(float(alive.mean()) if m else 0.0)
        for j in range(done, done + size):
            votes[np.arange(m), per_tree[plan.order[j]]] += 1
        done += size
        remaining = t_total - done
        if remaining > 0:
            top2 = np.partition(votes, -2, axis=1)[:, -2:]
            margin = top2[:, 1] - top2[:, 0]
            alive &= ~(margin > bound * remaining)
    return tuple(out)


def default_survival(n_stages: int) -> tuple[float, ...]:
    """Survival prior when no calibration batch is available.

    Everyone enters stage 0; each later stage keeps roughly half its
    predecessor's records — a deliberately conservative prior (measured
    easy-mix survivals are far lower) so the heuristic only picks a cascade
    when it wins even on middling workloads.
    """
    return tuple(min(1.0, 0.5**s) for s in range(max(1, int(n_stages))))


def cascade_heuristic_candidate(
    shape: ForestShape,
    n_classes: int,
    *,
    survival: tuple[float, ...] | None = None,
    cm: CostModel = CostModel(),
    d_mu: float | None = None,
    p_group: float | None = None,
    engines: tuple[str, ...] | None = None,
    launch_overhead: float = FOREST_LAUNCH_OVERHEAD,
) -> Candidate:
    """Model-based class-level choice: majority vote vs early-exit cascade.

    Extends the §3.6 forest model by the survival-rate term.  With t(d) the
    per-tree winner's model time, surv_s the fraction of records entering
    stage s and size_s the stage's tree count:

        full     ≈ T · t(d)                     + γ
        cascade  ≈ Σ_s size_s · surv_s · t(d)   + S · γ

    Each stage pays its launch overhead γ in full (the compacted tile still
    launches) but only its survivors' share of the compute.  The best stage
    count from :func:`cascade_stage_grid` competes against the full path;
    ties go to the full path (simpler, no compaction machinery).

    Args:
      survival: per-stage entering fractions from
        :func:`measured_survival_rate`; longer/shorter tuples than a
        candidate's stage count are resampled from the tail prior.  Default
        = :func:`default_survival`.
    """
    engines = default_engines() if engines is None else tuple(engines)
    deep = shape.tree_shape()
    t_tree = min(predicted_times(deep, cm=cm, d_mu=d_mu, p_group=p_group).values())
    full_cost = shape.t * t_tree + launch_overhead

    grid = cascade_stage_grid(shape)
    best: tuple[float, int] | None = None
    for s in grid:
        plan = plan_cascade(_ShapeForest(shape), n_classes=n_classes, stages=s, bound=1.0)
        surv = survival if survival is not None else default_survival(plan.n_stages)
        cost = plan.n_stages * launch_overhead
        for i, size in enumerate(plan.stage_sizes):
            f = surv[i] if i < len(surv) else default_survival(i + 1)[-1]
            cost += size * max(0.0, min(1.0, f)) * t_tree
        if best is None or cost < best[0]:
            best = (cost, s)

    if best is None or best[0] >= full_cost:
        return Candidate.make(MAJORITY_FAMILY)

    stages = best[1]
    engine = "pallas" if "pallas" in engines else "jnp"
    times = predicted_times(deep, cm=cm, d_mu=d_mu, p_group=p_group)
    algorithm = min(times, key=times.get)
    family = "fused" if engine == "pallas" else "vmap"
    jump_mode = "gather"
    if algorithm == "data_parallel":
        name = f"forest_cascade_{family}_data_parallel"
    else:
        if engine == "pallas":
            jump_mode = _pallas_jump_mode(deep)
        name = f"forest_cascade_{family}_speculative_{jump_mode}"
    if engine == "pallas":
        b = shape.bucket()
        bm = choose_block_m(b.n_nodes, b.n_attrs, jump_mode=jump_mode)
        return Candidate.make(name, stages=stages, block_m=bm)
    return Candidate.make(name, stages=stages)


class _ShapeForest:
    """Just enough forest surface for :func:`plan_cascade` stage sizing."""

    def __init__(self, shape: ForestShape):
        self.n_trees = int(shape.t)
