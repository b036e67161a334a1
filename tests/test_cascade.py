"""Early-exit cascade evaluation: exactness, provable exits, dispatch, serving.

The load-bearing property is *exactness under the provable bound*: with
``bound=1.0`` (and with the bound disabled outright) the staged cascade must
return class assignments bit-identical to the tuned full-forest path —
early exit is purely a performance decision.  A record may leave the
cascade only when its accumulated vote margin strictly exceeds the number
of trees it has not yet seen, which makes the exit *unflippable*: no
adversarial completion of the remaining trees can change the argmax.
"""

import json
import pathlib
import tempfile

import numpy as np
import pytest

from repro.core import (
    EncodedForest,
    breadth_first_encode,
    eval_forest_cascade,
    eval_forest_tuned,
    majority_vote,
    random_tree,
)
from repro.kernels.tree_eval import (
    CASCADE_VARIANTS,
    MAJORITY_FAMILY,
    CascadeEvaluator,
    CascadePlan,
    CascadeVariantSpec,
    cascade_eval_ref,
    exit_enabling_prefix,
    forest_votes_fused,
    get_cascade_variant,
    plan_cascade,
    register_cascade_variant,
)
from repro.tune import (
    ForestShape,
    ForestTunedEvaluator,
    TuneCache,
    cascade_search_space,
    cascade_stage_grid,
    measured_survival_rate,
    registry_fingerprint,
    tune_cascade_workload,
)
from repro.tune.cache import CACHE_VERSION

# hypothesis is optional: the shim runs a deterministic fixed-example sweep
# when the real package is not installed (see hypothesis_compat.py).
from hypothesis_compat import given, settings, st


def _forest(n_trees=12, n_attrs=9, n_classes=6, depth_span=5, seed0=0):
    trees = [
        breadth_first_encode(
            random_tree(n_attrs=n_attrs, n_classes=n_classes,
                        max_depth=2 + ((seed0 + i) % depth_span), seed=seed0 + i)
        )
        for i in range(n_trees)
    ]
    return EncodedForest(trees)


def _records(m, a, seed=0):
    # thresholds are normal-distributed, so normal records exercise both sides
    return np.random.default_rng(seed).normal(size=(m, a)).astype(np.float32)


def _cache():
    return TuneCache(pathlib.Path(tempfile.mkdtemp()) / "c.json")


def _full_majority(forest, rec, n_classes, cache):
    per_tree = eval_forest_tuned(forest, rec, cache=cache)
    return np.asarray(majority_vote(per_tree, n_classes))


# -- plan geometry -----------------------------------------------------------


def test_exit_enabling_prefix():
    # k trees can decide against T-k outstanding only if margin k > (T-k)·b
    for t in (2, 3, 8, 16, 33):
        for b in (1.0, 0.5, 0.25):
            k = exit_enabling_prefix(t, b)
            assert k > b * (t - k)                    # the prefix can decide
            assert k == 1 or (k - 1) <= b * (t - (k - 1))  # and is minimal


def test_plan_cascade_geometry_and_validation():
    forest = _forest(n_trees=16)
    rec = _records(256, 9, seed=3)
    plan = plan_cascade(forest, rec, n_classes=6, stages=3, bound=1.0)
    assert plan.n_trees == 16 and plan.n_stages == 3
    assert sum(plan.stage_sizes) == 16
    assert sorted(plan.order) == list(range(16))
    # first stage is exit-enabling: its margin can beat all remaining trees
    assert plan.stage_sizes[0] >= exit_enabling_prefix(16, 1.0)
    with pytest.raises(ValueError):
        CascadePlan(order=tuple(range(16)), stage_sizes=(8, 9))   # not a partition
    with pytest.raises(ValueError):
        CascadePlan(order=(0, 0, 1), stage_sizes=(2, 1))          # not a permutation


def test_plan_respects_explicit_order():
    forest = _forest(n_trees=8)
    order = tuple(reversed(range(8)))
    plan = plan_cascade(forest, n_classes=6, stages=2, order=order)
    assert plan.order == order


# -- exactness ---------------------------------------------------------------


def test_cascade_exact_parity_with_tuned_forest():
    forest = _forest(n_trees=12)
    rec = _records(700, 9, seed=1)
    cache = _cache()
    want = _full_majority(forest, rec, 6, cache)
    for bound in (None, 1.0):
        res = eval_forest_cascade(forest, rec, n_classes=6, stages=3, bound=bound)
        assert np.array_equal(np.asarray(res.classes), want), bound
    # provable bound: every exited record's margin beats its remaining trees
    res = eval_forest_cascade(forest, rec, n_classes=6, stages=3, bound=1.0)
    exited = np.asarray(res.exit_stage) >= 0
    remaining = forest.n_trees - np.asarray(res.trees_evaluated)
    assert np.all(np.asarray(res.margin)[exited] > remaining[exited])
    assert np.all(np.asarray(res.trees_evaluated)[~exited] == forest.n_trees)
    assert np.all((np.asarray(res.confidence) >= 0) & (np.asarray(res.confidence) <= 1))


def test_cascade_engines_agree_with_reference():
    forest = _forest(n_trees=10, n_classes=5)
    rec = _records(300, 9, seed=7)
    plan = plan_cascade(forest, rec, n_classes=5, stages=3, bound=1.0)
    ref_cls, ref_stage, ref_trees = cascade_eval_ref(
        rec, forest.attr_idx, forest.threshold, forest.child, forest.class_val,
        max_depth=forest.max_depth, order=plan.order, stage_sizes=plan.stage_sizes,
        n_classes=5, bound=1.0,
    )
    for kw in (
        dict(engine="jnp"),
        dict(engine="pallas", block_m=64),
        dict(engine="jnp", algorithm="data_parallel"),
    ):
        ev = CascadeEvaluator(forest, plan, n_classes=5, bound=1.0, **kw)
        res = ev(rec)
        assert np.array_equal(np.asarray(res.classes), ref_cls), kw
        assert np.array_equal(np.asarray(res.exit_stage), ref_stage), kw
        assert np.array_equal(np.asarray(res.trees_evaluated), ref_trees), kw


def test_forest_votes_fused_matches_onehot_sum():
    forest = _forest(n_trees=9, n_classes=4)
    rec = _records(200, 9, seed=11)
    cache = _cache()
    per_tree = np.asarray(eval_forest_tuned(forest, rec, cache=cache))  # (T, M)
    want = np.zeros((rec.shape[0], 4), np.int64)
    for t in range(forest.n_trees):
        np.add.at(want, (np.arange(rec.shape[0]), per_tree[t]), 1)
    for algorithm, jump_mode in (
        ("speculative", "gather"),
        ("speculative", "onehot"),
        ("data_parallel", "gather"),
    ):
        votes = np.asarray(forest_votes_fused(
            rec, forest, n_classes=4, algorithm=algorithm, jump_mode=jump_mode,
            block_m=64,
        ))
        assert votes.shape == (rec.shape[0], 4)
        assert np.array_equal(votes, want), (algorithm, jump_mode)


# -- property: early exits are provably unflippable --------------------------


@settings(max_examples=10, deadline=None)
@given(
    n_trees=st.integers(4, 20),
    stages=st.integers(2, 4),
    n_classes=st.integers(2, 7),
    seed=st.integers(0, 1000),
)
def test_early_exit_margins_unflippable(n_trees, stages, n_classes, seed):
    forest = _forest(n_trees=n_trees, n_classes=n_classes, seed0=seed % 17)
    rec = _records(120, 9, seed=seed)
    plan = plan_cascade(forest, rec[:64], n_classes=n_classes,
                        stages=stages, bound=1.0)
    res = eval_forest_cascade(forest, rec, n_classes=n_classes,
                              plan=plan, bound=1.0)
    cache = _cache()
    per_tree = np.asarray(eval_forest_tuned(forest, rec, cache=cache))  # (T, M)
    classes = np.asarray(res.classes)
    exit_stage = np.asarray(res.exit_stage)
    trees_eval = np.asarray(res.trees_evaluated)
    order = np.asarray(plan.order)
    for i in np.flatnonzero(exit_stage >= 0):
        k = int(trees_eval[i])
        votes = np.bincount(per_tree[order[:k], i], minlength=n_classes)
        top1 = int(votes.argmax())
        assert top1 == classes[i]
        # adversarial completion: hand every unseen tree to the runner-up —
        # the exit class must still win outright (strict, so argmax
        # tie-breaking toward lower indices can never flip it)
        adv = votes.copy()
        adv[top1] = -1
        runner = int(adv.argmax())
        worst = votes.copy()
        worst[runner] += n_trees - k
        assert votes[top1] > worst[runner]
        # and the full forest agrees with the early answer
        full = np.bincount(per_tree[:, i], minlength=n_classes)
        assert int(full.argmax()) == top1


# -- tuner integration -------------------------------------------------------


def test_cascade_search_space_and_stage_grid():
    shape = ForestShape(t=16, m=1024, n_nodes=128, n_attrs=16,
                        depth_min=3, depth_max=6)
    grid = cascade_stage_grid(shape)
    assert grid and all(s >= 2 for s in grid)
    cands = list(cascade_search_space(shape, 6))
    names = {c.variant for c in cands}
    assert MAJORITY_FAMILY in names
    assert any(n.startswith("forest_cascade_") for n in names)
    for c in cands:
        if c.variant != MAJORITY_FAMILY:
            assert get_cascade_variant(c.variant) is not None
            assert 2 <= dict(c.params)["stages"] <= 4
    # tiny forests cannot stage: no cascade candidates, majority only
    tiny = ForestShape(t=2, m=64, n_nodes=16, n_attrs=8, depth_min=2, depth_max=2)
    assert cascade_stage_grid(tiny) == []
    assert {c.variant for c in cascade_search_space(tiny, 6)} == {MAJORITY_FAMILY}


def test_measured_survival_rate_shape():
    forest = _forest(n_trees=12)
    rec = _records(256, 9, seed=5)
    surv = measured_survival_rate(forest, rec, 6, stages=3)
    assert len(surv) == 3 and surv[0] == 1.0
    assert all(0.0 <= s <= 1.0 for s in surv)
    assert all(b <= a + 1e-9 for a, b in zip(surv, surv[1:]))  # non-increasing


def test_predict_dispatch_parity_and_cache_round_trip():
    forest = _forest(n_trees=12)
    rec = _records(600, 9, seed=9)
    cache = _cache()
    want = _full_majority(forest, rec, 6, cache)

    fev = ForestTunedEvaluator(forest, cache=cache, autotune=True)
    got = np.asarray(fev.predict(rec, 6))
    assert np.array_equal(got, want)
    cand, source = fev.resolve_classes(rec, 6)
    assert source in ("memo", "cache", "autotune")
    assert cand.variant == MAJORITY_FAMILY or cand.variant in CASCADE_VARIANTS

    # the stored winner survives a cold restart through the JSON cache
    fev2 = ForestTunedEvaluator(forest, cache=TuneCache(cache.path), autotune=False)
    got2 = np.asarray(fev2.predict(rec, 6))
    assert np.array_equal(got2, want)
    cand2, source2 = fev2.resolve_classes(rec, 6)
    assert source2 in ("memo", "cache")
    assert cand2.variant == cand.variant


def test_tune_cascade_workload_stores_classes_key():
    forest = _forest(n_trees=12)
    rec = _records(512, 9, seed=13)
    cache = _cache()
    entry, measurements = tune_cascade_workload(
        rec, forest, 6, cache=cache, warmup=1, iters=2)
    assert measurements
    assert entry.variant == MAJORITY_FAMILY or entry.variant in CASCADE_VARIANTS
    raw = json.loads(pathlib.Path(cache.path).read_text())
    assert any("|C6" in k for k in raw["entries"])


def test_cache_version_and_fingerprint_cover_cascade():
    assert CACHE_VERSION >= 3
    base = registry_fingerprint()
    spec = get_cascade_variant(next(iter(CASCADE_VARIANTS)))
    probe = CascadeVariantSpec(
        name="forest_cascade_probe", family=spec.family, algorithm=spec.algorithm,
        engine=spec.engine, jump_mode=spec.jump_mode, tunables=spec.tunables,
        build=spec.build,
    )
    register_cascade_variant(probe)
    registry_fingerprint.cache_clear()   # memoized for the hot dispatch path
    try:
        assert registry_fingerprint() != base
    finally:
        del CASCADE_VARIANTS["forest_cascade_probe"]
        registry_fingerprint.cache_clear()
    assert registry_fingerprint() == base


# -- anytime serving ---------------------------------------------------------


def test_anytime_serving_generous_and_tight_slo():
    from repro.serve import AnytimePolicy, ForestServeEngine, TreeRequest

    forest = _forest(n_trees=12)
    cache = _cache()
    rng = np.random.default_rng(21)
    reqs = [TreeRequest(uid=i, records=rng.normal(size=(96, 9)).astype(np.float32))
            for i in range(4)]
    ref = {r.uid: _full_majority(forest, r.records, 6, cache) for r in reqs}

    eng = ForestServeEngine(forest, max_batch=512, n_classes=6, cache=cache,
                            anytime=AnytimePolicy(slo_ms=10_000.0, stages=3))
    eng.run(reqs)
    assert eng.stats.anytime_waves >= 1
    assert eng.stats.anytime_truncations == 0      # generous SLO: full cascade
    for r in reqs:
        assert r.done and np.array_equal(r.out, ref[r.uid])
        assert r.confidence is not None
        assert np.all((r.confidence >= 0) & (r.confidence <= 1))

    reqs2 = [TreeRequest(uid=i, records=rng.normal(size=(96, 9)).astype(np.float32))
             for i in range(4)]
    eng2 = ForestServeEngine(forest, max_batch=512, n_classes=6, cache=cache,
                             anytime=AnytimePolicy(slo_ms=1e-4, stages=3))
    eng2.run(reqs2)
    # an impossible SLO truncates the cascade after its first stage but
    # still answers every request with a confidence estimate
    assert eng2.stats.anytime_truncations >= 1
    assert eng2.stats.anytime_stages and max(eng2.stats.anytime_stages) < 3
    for r in reqs2:
        assert r.done and r.out is not None and r.confidence is not None

    with pytest.raises(ValueError):
        ForestServeEngine(forest, anytime=AnytimePolicy(slo_ms=1.0))  # no n_classes


# -- streaming overlap stats -------------------------------------------------


def test_stream_overlap_stats_and_first_eval_geometry():
    from repro.dist import ShardedForestEvaluator, StreamingChunker

    forest = _forest(n_trees=8)
    rec = _records(1000, 9, seed=17)
    cache = _cache()
    ev = ShardedForestEvaluator(forest, cache=cache)
    ck = StreamingChunker(ev, chunk_records=256)
    want = np.asarray(eval_forest_tuned(forest, rec, cache=cache))
    out = ck.eval(rec)
    assert np.array_equal(out, want)
    # first eval always honours the configured chunk size, coalescing or not
    assert ck.stats.chunks == 4                    # ceil(1000/256)
    assert len(ck.stats.overlap_ratio) == ck.stats.chunks
    assert all(0.0 <= o <= 1.0 for o in ck.stats.overlap_ratio)
    assert ck.stats.overlap_ratio[0] == 0.0        # nothing to overlap with
    for _ in range(6):                             # let coalescing settle
        assert np.array_equal(ck.eval(rec), want)
    assert ck.stats.coalesced_chunk_records >= ck.chunk_records
