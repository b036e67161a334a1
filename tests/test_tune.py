"""repro.tune: cache round-trip, bucketing, heuristic vs §4 analysis, dispatch.

Dispatch correctness is the load-bearing property: whatever variant the
tuner or heuristic picks, ``tuned_eval`` must return class assignments
bit-identical to the branchless serial reference (Procedure 2).
"""

import json

import numpy as np
import pytest

from repro.core import (
    Node, breadth_first_encode, eval_serial, paper_tree, random_tree, tree_depth,
)
from repro.core.analysis import CostModel, speculative_wins
from repro.core.forest import EncodedForest
from repro.kernels.tree_eval import (
    FOREST_VARIANTS,
    PER_TREE_FAMILY,
    VARIANTS,
    get_forest_variant,
    get_variant,
)
from repro.tune import (
    Candidate,
    ForestShape,
    ForestTunedEvaluator,
    TuneCache,
    TuneEntry,
    TunedEvaluator,
    WorkloadShape,
    backend_tag,
    forest_heuristic_candidate,
    forest_search_space,
    heuristic_candidate,
    measured_d_mu,
    predicted_times,
    registry_fingerprint,
    search_space,
    tune_forest_workload,
    tuned_eval,
    tuned_eval_forest,
    tune_workload,
)

# hypothesis is optional: the shim runs a deterministic fixed-example sweep
# when the real package is not installed (see hypothesis_compat.py).
from hypothesis_compat import given, settings, st


def _records(m, a, seed=0):
    return np.random.default_rng(seed).normal(size=(m, a)).astype(np.float32)


# ---------------------------------------------------------------------------
# Shape bucketing
# ---------------------------------------------------------------------------


class TestShapeBucketing:
    def test_bucket_rounds_up(self):
        b = WorkloadShape(m=100, n_nodes=31, n_attrs=19, depth=11).bucket()
        assert b == WorkloadShape(m=128, n_nodes=128, n_attrs=128, depth=16)

    def test_bucket_idempotent(self):
        s = WorkloadShape(m=100, n_nodes=31, n_attrs=19, depth=11)
        assert s.bucket().bucket() == s.bucket()

    def test_nearby_shapes_share_bucket(self):
        a = WorkloadShape(m=100, n_nodes=31, n_attrs=19, depth=11)
        b = WorkloadShape(m=127, n_nodes=40, n_attrs=25, depth=9)
        assert a.key("cpu") == b.key("cpu")

    def test_distinct_shapes_distinct_keys(self):
        a = WorkloadShape(m=128, n_nodes=31, n_attrs=19, depth=11)
        b = WorkloadShape(m=129, n_nodes=31, n_attrs=19, depth=11)  # next pow2
        assert a.key("cpu") != b.key("cpu")
        assert a.key("cpu") != a.key("tpu")

    def test_of_derives_from_records_and_tree(self):
        enc = breadth_first_encode(paper_tree())
        s = WorkloadShape.of(_records(50, 19), enc)
        assert s == WorkloadShape(m=50, n_nodes=31, n_attrs=19, depth=11)


# ---------------------------------------------------------------------------
# Multi-backend cache keys: backend + device kind + topology
# ---------------------------------------------------------------------------


class TestBackendTag:
    def test_tag_carries_backend_kind_and_count(self):
        import jax

        tag = backend_tag()
        backend, kind, count = tag.split(":")
        assert backend == jax.default_backend()
        assert kind and "|" not in kind and " " not in kind
        assert count == f"x{jax.device_count()}"

    def test_key_defaults_to_backend_tag(self):
        s = WorkloadShape(m=100, n_nodes=31, n_attrs=19, depth=11)
        assert s.key() == s.key(backend_tag())
        # distinct topologies key distinct rows in one shared file
        assert s.key("tpu:v5e:x8") != s.key("tpu:v5p:x8") != s.key("cpu:cpu:x1")

    def test_dispatch_stores_under_backend_tag(self, tmp_path):
        cache = TuneCache(tmp_path / "c.json")
        enc = breadth_first_encode(paper_tree())
        ev = TunedEvaluator(enc, cache=cache, autotune=True,
                            measure_kw={"warmup": 1, "iters": 2})
        ev(_records(32, 19, seed=21))
        assert len(cache) == 1
        assert cache.keys()[0].startswith(backend_tag() + "|")


# ---------------------------------------------------------------------------
# Search space
# ---------------------------------------------------------------------------


class TestSearchSpace:
    def test_candidates_only_registered_variants(self):
        shape = WorkloadShape(m=256, n_nodes=31, n_attrs=19, depth=6)
        cands = list(search_space(shape))
        assert cands, "search space must not be empty"
        for c in cands:
            assert c.variant in VARIANTS
            spec = get_variant(c.variant)
            assert set(c.param_dict) <= set(spec.tunables)

    def test_onehot_excluded_for_huge_trees(self):
        shape = WorkloadShape(m=256, n_nodes=100_000, n_attrs=19, depth=17)
        for c in search_space(shape):
            assert get_variant(c.variant).jump_mode != "onehot"

    def test_engine_filter(self):
        shape = WorkloadShape(m=256, n_nodes=31, n_attrs=19, depth=6)
        for c in search_space(shape, engines=("pallas",)):
            assert get_variant(c.variant).engine == "pallas"


# ---------------------------------------------------------------------------
# Cache: write → reload → hit
# ---------------------------------------------------------------------------


class TestCache:
    def test_round_trip(self, tmp_path):
        path = tmp_path / "cache.json"
        cache = TuneCache(path)
        entry = TuneEntry(
            variant="jnp_data_parallel", params={}, median_ms=1.25,
            shape={"m": 128, "n_nodes": 31, "n_attrs": 19, "depth": 11},
            backend="cpu",
        )
        cache.store("cpu|M128|N128|A128|d16", entry)
        assert path.exists()

        reloaded = TuneCache(path)
        hit = reloaded.lookup("cpu|M128|N128|A128|d16")
        assert hit is not None
        assert hit.variant == entry.variant
        assert hit.median_ms == entry.median_ms
        assert hit.shape == entry.shape
        assert reloaded.lookup("cpu|M999|N128|A128|d16") is None

    def test_params_preserved(self, tmp_path):
        cache = TuneCache(tmp_path / "c.json")
        cache.store("k", TuneEntry(variant="jnp_speculative_gather",
                                   params={"jumps_per_round": 3}, median_ms=0.5))
        hit = TuneCache(tmp_path / "c.json").lookup("k")
        assert hit.params == {"jumps_per_round": 3}

    def test_corrupt_file_tolerated(self, tmp_path):
        path = tmp_path / "c.json"
        path.write_text("{not json")
        cache = TuneCache(path)
        assert len(cache) == 0
        cache.store("k", TuneEntry(variant="jnp_data_parallel", params={}, median_ms=1.0))
        assert TuneCache(path).lookup("k") is not None

    def test_version_mismatch_discarded(self, tmp_path):
        path = tmp_path / "c.json"
        path.write_text(json.dumps({"version": 999, "entries": {"k": {"variant": "x"}}}))
        assert TuneCache(path).lookup("k") is None

    def test_lru_front_bounded(self, tmp_path):
        cache = TuneCache(tmp_path / "c.json", lru_size=2)
        for i in range(5):
            cache.store(f"k{i}", TuneEntry(variant="jnp_data_parallel",
                                           params={}, median_ms=float(i)))
        assert len(cache._lru) <= 2
        # evicted keys still resolve from the table
        assert cache.lookup("k0").median_ms == 0.0


# ---------------------------------------------------------------------------
# Registry-fingerprint invalidation: kernel rewrites drop stored winners
# ---------------------------------------------------------------------------


class TestRegistryInvalidation:
    ENTRY = TuneEntry(variant="jnp_data_parallel", params={}, median_ms=1.0)

    def test_fingerprint_stable_and_nonempty(self):
        assert registry_fingerprint()
        assert registry_fingerprint() == registry_fingerprint()

    def test_same_registry_round_trips(self, tmp_path):
        TuneCache(tmp_path / "c.json", registry="fp_a").store("k", self.ENTRY)
        assert TuneCache(tmp_path / "c.json", registry="fp_a").lookup("k") is not None

    def test_changed_registry_discards_entries(self, tmp_path):
        """A kernel rewrite (new fingerprint) must orphan every stored
        winner: its medians priced code that no longer exists."""
        TuneCache(tmp_path / "c.json", registry="fp_a").store("k", self.ENTRY)
        stale = TuneCache(tmp_path / "c.json", registry="fp_b")
        assert len(stale) == 0
        assert stale.lookup("k") is None
        # re-tuning on the new registry overwrites the file cleanly
        stale.store("k", self.ENTRY)
        assert TuneCache(tmp_path / "c.json", registry="fp_b").lookup("k") is not None
        assert TuneCache(tmp_path / "c.json", registry="fp_a").lookup("k") is None

    def test_default_registry_is_live_fingerprint(self, tmp_path):
        cache = TuneCache(tmp_path / "c.json")
        assert cache.registry == registry_fingerprint()
        cache.store("k", self.ENTRY)
        assert TuneCache(tmp_path / "c.json").lookup("k") is not None


# ---------------------------------------------------------------------------
# Measured d_µ in the heuristic (vs the geometry prior)
# ---------------------------------------------------------------------------


def _shallow_exit_vine(depth: int = 14) -> "Node":
    """A depth-``depth`` vine whose root sends *every* record to a depth-1
    leaf: geometry prior d_µ ≈ (log₂N + depth)/2, measured d_µ = 1."""
    node = Node(attr=0, threshold=0.0, left=Node(class_val=0), right=Node(class_val=1))
    for _ in range(depth - 1):
        node = Node(attr=0, threshold=0.0, left=node, right=Node(class_val=2))
    # root: threshold -1e9 ⇒ r[0] > -1e9 for all finite records ⇒ go right
    return Node(attr=0, threshold=-1e9, left=node, right=Node(class_val=3))


class TestMeasuredDmu:
    def test_measured_d_mu_sees_shallow_traffic(self):
        enc = breadth_first_encode(_shallow_exit_vine())
        rec = _records(200, 5, seed=30)
        assert measured_d_mu(enc, rec) == 1.0

    def test_crossover_shifts_with_measured_d_mu(self, tmp_path):
        """Equation (1)'s crossover moves with d_µ: at p_group=4 the prior
        (d_µ ≈ 9.4) predicts speculative wins, the measured depth (d_µ = 1)
        predicts data decomposition.  Dispatch must follow the measurement."""
        from repro.tune.heuristic import default_d_mu

        enc = breadth_first_encode(_shallow_exit_vine(depth=14))
        rec = _records(64, 5, seed=31)
        shape = WorkloadShape.of(rec, enc)
        hk = {"cm": CostModel(t_e=1.0, t_c=1.0), "p_group": 4.0}

        prior = heuristic_candidate(shape, d_mu=default_d_mu(shape), **hk)
        measured = heuristic_candidate(shape, d_mu=measured_d_mu(enc, rec), **hk)
        assert get_variant(prior.variant).algorithm == "speculative"
        assert get_variant(measured.variant).algorithm == "data_parallel"

        ev_meas = TunedEvaluator(enc, cache=TuneCache(tmp_path / "a.json"),
                                 heuristic_kw=hk)
        cand, source = ev_meas.resolve(rec)
        assert source == "heuristic"
        assert get_variant(cand.variant).algorithm == "data_parallel"

        ev_prior = TunedEvaluator(enc, cache=TuneCache(tmp_path / "b.json"),
                                  measure_d_mu=False, heuristic_kw=hk)
        cand, _ = ev_prior.resolve(rec)
        assert get_variant(cand.variant).algorithm == "speculative"

        # either way, dispatch stays bit-identical to the serial reference
        assert np.array_equal(np.asarray(ev_meas(rec)), eval_serial(enc, rec))
        assert np.array_equal(np.asarray(ev_prior(rec)), eval_serial(enc, rec))


# ---------------------------------------------------------------------------
# Heuristic fallback vs the §4 analysis
# ---------------------------------------------------------------------------


class TestHeuristic:
    def test_model_choice_matches_crossover(self):
        """With t_e = t_c and no overheads, the model-predicted winner must
        flip exactly at equation (1): p < 2·d_µ/(1 + log₂ d_µ)."""
        cm = CostModel(t_e=1.0, t_c=1.0, t_i=0.0, sigma=0.0, gamma=0.0)
        shape = WorkloadShape(m=1024, n_nodes=31, n_attrs=19, depth=8)
        for d_mu in (2.0, 4.0, 8.0, 16.0, 32.0):
            for p_factor in (0.5, 0.9, 1.1, 2.0):
                from repro.core.analysis import crossover_group_size

                p = crossover_group_size(d_mu) * p_factor
                times = predicted_times(shape, cm=cm, d_mu=d_mu, p_group=p)
                model_says_spec = times["speculative"] < times["data_parallel"]
                assert model_says_spec == speculative_wins(d_mu, p), (d_mu, p)

    def test_heuristic_follows_synthetic_timings(self):
        """Feeding the cost model synthetic operating points drives the
        candidate's algorithm exactly as the analysis predicts."""
        cm = CostModel(t_e=1.0, t_c=1.0)
        shape = WorkloadShape(m=512, n_nodes=31, n_attrs=19, depth=8)
        # tiny record groups, deep traversals -> speculative wins
        c_spec = heuristic_candidate(shape, cm=cm, d_mu=30.0, p_group=2.0)
        assert get_variant(c_spec.variant).algorithm == "speculative"
        # huge groups, shallow traversals -> data decomposition wins
        c_dp = heuristic_candidate(shape, cm=cm, d_mu=2.0, p_group=500.0)
        assert get_variant(c_dp.variant).algorithm == "data_parallel"

    def test_heuristic_yields_valid_candidate(self):
        for depth, n in ((2, 7), (11, 31), (8, 511)):
            shape = WorkloadShape(m=256, n_nodes=n, n_attrs=19, depth=depth)
            c = heuristic_candidate(shape)
            spec = get_variant(c.variant)
            assert set(c.param_dict) <= set(spec.tunables)


# ---------------------------------------------------------------------------
# Dispatch correctness: bit-identical to the serial reference
# ---------------------------------------------------------------------------


class TestDispatch:
    def test_heuristic_path_bit_identical(self, tmp_path):
        cache = TuneCache(tmp_path / "c.json")
        enc = breadth_first_encode(paper_tree())
        rec = _records(300, 19, seed=3)
        out = np.asarray(tuned_eval(rec, enc, cache=cache))
        assert out.dtype == np.int32
        assert np.array_equal(out, eval_serial(enc, rec))

    @given(
        seed=st.integers(0, 40),
        depth=st.integers(1, 9),
        balance=st.floats(0.3, 1.0),
        m=st.integers(1, 150),
    )
    @settings(max_examples=15, deadline=None)
    def test_randomized_trees_bit_identical(self, seed, depth, balance, m):
        enc = breadth_first_encode(
            random_tree(n_attrs=7, n_classes=5, max_depth=depth, seed=seed, balance=balance)
        )
        import tempfile
        from pathlib import Path

        rec = _records(m, 7, seed=seed + 1)
        cache = TuneCache(Path(tempfile.gettempdir()) / "repro_tune_test_absent.json")
        out = np.asarray(tuned_eval(rec, enc, cache=cache))
        assert np.array_equal(out, eval_serial(enc, rec))

    def test_autotuned_path_bit_identical_and_cached(self, tmp_path):
        cache = TuneCache(tmp_path / "c.json")
        enc = breadth_first_encode(
            random_tree(n_attrs=5, n_classes=4, max_depth=5, seed=7)
        )
        rec = _records(64, 5, seed=8)
        ev = TunedEvaluator(enc, cache=cache, autotune=True,
                            measure_kw={"warmup": 1, "iters": 2})
        out = np.asarray(ev(rec))
        assert np.array_equal(out, eval_serial(enc, rec))
        assert len(cache) == 1  # winner persisted under the bucket key

        # a fresh evaluator on a fresh cache handle must hit, not re-tune
        ev2 = TunedEvaluator(enc, cache=TuneCache(tmp_path / "c.json"))
        _, source = ev2.resolve(rec)
        assert source == "cache"
        assert np.array_equal(np.asarray(ev2(rec)), eval_serial(enc, rec))

    def test_tune_workload_winner_is_measured_minimum(self, tmp_path):
        cache = TuneCache(tmp_path / "c.json")
        enc = breadth_first_encode(paper_tree())
        rec = _records(32, 19, seed=9)
        entry, measurements = tune_workload(rec, enc, cache=cache, warmup=1, iters=2)
        ok = [m for m in measurements if not m.failed]
        assert entry.median_ms == min(m.median_ms for m in ok)
        assert entry.variant in VARIANTS

    def test_dispatch_stale_cache_variant_falls_back(self, tmp_path):
        """An entry naming a since-removed variant must not break dispatch."""
        cache = TuneCache(tmp_path / "c.json")
        enc = breadth_first_encode(paper_tree())
        rec = _records(40, 19, seed=10)
        key = WorkloadShape.of(rec, enc).key()  # default backend_tag
        cache.store(key, TuneEntry(variant="gone_variant", params={}, median_ms=1.0))
        ev = TunedEvaluator(enc, cache=cache)
        cand, source = ev.resolve(rec)
        assert source == "heuristic"
        assert np.array_equal(np.asarray(ev(rec)), eval_serial(enc, rec))

    def test_memo_source_on_second_resolve(self, tmp_path):
        enc = breadth_first_encode(paper_tree())
        rec = _records(16, 19)
        ev = TunedEvaluator(enc, cache=TuneCache(tmp_path / "c.json"))
        assert ev.resolve(rec)[1] == "heuristic"
        assert ev.resolve(rec)[1] == "memo"

    def test_explicit_candidate_params_respected(self):
        c = Candidate.make("jnp_speculative_gather", jumps_per_round=3)
        assert c.param_dict == {"jumps_per_round": 3}
        # frozen/hashable: usable as dict keys in resolution memos
        assert hash(c) == hash(Candidate.make("jnp_speculative_gather", jumps_per_round=3))


# ---------------------------------------------------------------------------
# Pack once: a Pallas winner's tables are built when its bucket resolves
# ---------------------------------------------------------------------------


def _packs(registry, level):
    from repro import obs

    return obs.snapshot(registry)["counters"].get(f'kernel.packs{{level="{level}"}}', 0)


class TestPackOnce:
    def test_tree_tables_packed_once_per_width(self, tmp_path):
        from repro import obs

        registry = obs.Registry()
        enc = breadth_first_encode(paper_tree())
        ev = TunedEvaluator(enc, cache=TuneCache(tmp_path / "c.json"),
                            engines=("pallas",), registry=registry)

        def check(rec):
            assert np.array_equal(np.asarray(ev(rec)), eval_serial(enc, rec))

        for seed in range(5):                       # same shape: one pack
            check(_records(64, 19, seed=seed))
        check(_records(200, 19, seed=5))            # another bucket, same width
        assert _packs(registry, "tree") == 1
        check(_records(64, 23, seed=6))             # a new width packs again
        assert _packs(registry, "tree") == 2

        # a winner swap re-resolves the bucket but reuses the tables
        rec = _records(64, 19, seed=7)
        cand, _ = ev.resolve(rec)
        other = next(s.name for s in VARIANTS.values()
                     if s.engine == "pallas" and s.name != cand.variant)
        ev.promote(WorkloadShape.of(rec, enc).key(), Candidate.make(other))
        check(rec)
        assert ev.resolve(rec)[0].variant == other
        assert _packs(registry, "tree") == 2

    def test_forest_tables_packed_once(self, tmp_path):
        from repro import obs

        registry = obs.Registry()
        forest = EncodedForest([
            breadth_first_encode(random_tree(n_attrs=9, n_classes=6, max_depth=d, seed=d))
            for d in (3, 6)
        ])
        ev = ForestTunedEvaluator(forest, cache=TuneCache(tmp_path / "c.json"),
                                  families=("fused",), registry=registry)
        for seed in range(4):
            rec = _records(100, 9, seed=seed)
            ref = np.stack([eval_serial(forest.tree(i), rec) for i in range(2)])
            assert np.array_equal(np.asarray(ev(rec)), ref)
        assert _packs(registry, "forest") == 1
        assert _packs(registry, "tree") == 0

    def test_measure_candidate_packs_once_per_candidate(self, monkeypatch):
        from repro.kernels.tree_eval import ops
        from repro.tune.measure import measure_candidate

        built = []
        init = ops.PackedTree.__init__

        def counting_init(self, *args, **kw):
            built.append(1)
            init(self, *args, **kw)

        monkeypatch.setattr(ops.PackedTree, "__init__", counting_init)
        enc = breadth_first_encode(paper_tree())
        rec = _records(64, 19, seed=11)
        for name in sorted(s.name for s in VARIANTS.values() if s.engine == "pallas"):
            built.clear()
            m = measure_candidate(Candidate.make(name), rec, enc,
                                  max_depth=tree_depth(enc), warmup=2, iters=3)
            assert not m.failed, m.error
            assert len(built) == 1, name                # not one per timed call


# ---------------------------------------------------------------------------
# Tuned forest + serving wiring
# ---------------------------------------------------------------------------


class TestWiring:
    def test_eval_forest_tuned_matches_serial(self, tmp_path):
        from repro.core import EncodedForest, eval_forest_tuned

        trees = [
            breadth_first_encode(random_tree(n_attrs=9, n_classes=6, max_depth=d, seed=d))
            for d in (2, 5, 8)
        ]
        forest = EncodedForest(trees)
        rec = _records(120, 9, seed=11)
        out = np.asarray(eval_forest_tuned(forest, rec, cache=TuneCache(tmp_path / "c.json")))
        assert out.shape == (3, 120)
        for i in range(3):
            assert np.array_equal(out[i], eval_serial(forest.tree(i), rec))

    def test_forest_shape_buckets_and_keys(self):
        s = ForestShape(t=3, m=100, n_nodes=31, n_attrs=19, depth_min=3, depth_max=11)
        b = s.bucket()
        assert b == ForestShape(t=4, m=128, n_nodes=128, n_attrs=128,
                                depth_min=4, depth_max=16)
        assert b.bucket() == b  # idempotent
        # forest keys are disjoint from per-tree keys in the shared cache
        tree_key = WorkloadShape(m=100, n_nodes=31, n_attrs=19, depth=11).key("cpu")
        assert s.key("cpu") != tree_key and "|T4|" in s.key("cpu")
        # the depth profile is part of the bucket identity
        flat = ForestShape(t=3, m=100, n_nodes=31, n_attrs=19, depth_min=11, depth_max=11)
        assert flat.key("cpu") != s.key("cpu")

    def test_forest_search_space_spans_three_families(self):
        shape = ForestShape(t=4, m=256, n_nodes=31, n_attrs=19, depth_min=6, depth_max=6)
        cands = list(forest_search_space(shape, engines=("pallas", "jnp")))
        variants = {c.variant for c in cands}
        assert PER_TREE_FAMILY in variants
        assert any(v.startswith("forest_vmap_") for v in variants)
        assert any(v.startswith("forest_fused_") for v in variants)
        for c in cands:
            if c.variant == PER_TREE_FAMILY:
                continue
            spec = get_forest_variant(c.variant)
            assert set(c.param_dict) <= set(spec.tunables)
        # onehot candidates vanish for huge trees, per_tree never does
        huge = ForestShape(t=4, m=256, n_nodes=100_000, n_attrs=19,
                           depth_min=17, depth_max=17)
        for c in forest_search_space(huge, engines=("pallas", "jnp")):
            if c.variant != PER_TREE_FAMILY:
                assert get_forest_variant(c.variant).jump_mode != "onehot"

    def test_forest_heuristic_profile_drives_family(self):
        """Homogeneous depth profiles go stacked (one launch, no padding
        waste); spread profiles flip to the per-tree vector."""
        uniform = ForestShape(t=8, m=1024, n_nodes=127, n_attrs=19,
                              depth_min=6, depth_max=6)
        c = forest_heuristic_candidate(uniform, d_mu=5.0)
        assert c.variant != PER_TREE_FAMILY
        spread = ForestShape(t=8, m=1024, n_nodes=127, n_attrs=19,
                             depth_min=1, depth_max=24)
        c = forest_heuristic_candidate(spread, d_mu=12.0, launch_overhead=1e-6)
        assert c.variant == PER_TREE_FAMILY
        # families filter is honoured
        c = forest_heuristic_candidate(spread, families=("vmap",))
        assert get_forest_variant(c.variant).family == "vmap"

    def test_forest_evaluator_bit_identical_all_families(self, tmp_path):
        trees = [
            breadth_first_encode(random_tree(n_attrs=9, n_classes=6, max_depth=d, seed=d))
            for d in (2, 5, 8)
        ]
        forest = EncodedForest(trees)
        rec = _records(150, 9, seed=40)
        ref = np.stack([eval_serial(forest.tree(i), rec) for i in range(3)])
        for families in ((PER_TREE_FAMILY,), ("vmap",), ("fused",), None):
            ev = ForestTunedEvaluator(
                forest, cache=TuneCache(tmp_path / "c.json"), families=families
            )
            out = np.asarray(ev(rec))
            assert out.shape == (3, 150)
            assert np.array_equal(out, ref), families

    def test_forest_autotune_persists_and_hits(self, tmp_path):
        trees = [
            breadth_first_encode(random_tree(n_attrs=7, n_classes=5, max_depth=4, seed=s))
            for s in (1, 2)
        ]
        forest = EncodedForest(trees)
        rec = _records(64, 7, seed=41)
        cache = TuneCache(tmp_path / "c.json")
        ev = ForestTunedEvaluator(forest, cache=cache, autotune=True,
                                  measure_kw={"warmup": 1, "iters": 2})
        ref = np.stack([eval_serial(forest.tree(i), rec) for i in range(2)])
        assert np.array_equal(np.asarray(ev(rec)), ref)
        # the forest winner landed under the forest bucket key
        fkey = ev.shape_of(rec).key()
        entry = cache.lookup(fkey)
        assert entry is not None
        assert entry.variant in FOREST_VARIANTS or entry.variant == PER_TREE_FAMILY

        # a fresh evaluator on a fresh cache handle must hit, not re-tune
        ev2 = ForestTunedEvaluator(forest, cache=TuneCache(tmp_path / "c.json"))
        cand, source = ev2.resolve(rec)
        assert source == "cache"
        assert cand.variant == entry.variant
        assert np.array_equal(np.asarray(ev2(rec)), ref)

    def test_family_restricted_evaluator_ignores_foreign_cache_hit(self, tmp_path):
        """A families-restricted evaluator must not run another family's
        cached winner (it would silently invalidate e.g. the per-tree
        baseline in the forest sweep bench)."""
        trees = [breadth_first_encode(random_tree(n_attrs=7, n_classes=5,
                                                  max_depth=4, seed=s))
                 for s in (8, 9)]
        forest = EncodedForest(trees)
        rec = _records(64, 7, seed=45)
        cache = TuneCache(tmp_path / "c.json")
        restricted = ForestTunedEvaluator(forest, cache=cache,
                                          families=(PER_TREE_FAMILY,))
        # a sibling evaluator cached the vmap winner under the same bucket
        cache.store(restricted.shape_of(rec).key(),
                    TuneEntry(variant="forest_vmap_data_parallel", params={},
                              median_ms=0.1))
        cand, source = restricted.resolve(rec)
        assert source == "heuristic"          # the foreign hit was refused
        assert cand.variant == PER_TREE_FAMILY
        # an unrestricted evaluator does take the hit
        cand, source = ForestTunedEvaluator(forest, cache=cache).resolve(rec)
        assert source == "cache" and cand.variant == "forest_vmap_data_parallel"

    def test_forest_stale_cache_entry_falls_back(self, tmp_path):
        trees = [breadth_first_encode(random_tree(n_attrs=5, n_classes=4,
                                                  max_depth=3, seed=s))
                 for s in (3, 4)]
        forest = EncodedForest(trees)
        rec = _records(32, 5, seed=42)
        cache = TuneCache(tmp_path / "c.json")
        ev = ForestTunedEvaluator(forest, cache=cache)
        cache.store(ev.shape_of(rec).key(),
                    TuneEntry(variant="gone_forest_variant", params={}, median_ms=1.0))
        cand, source = ev.resolve(rec)
        assert source == "heuristic"
        ref = np.stack([eval_serial(forest.tree(i), rec) for i in range(2)])
        assert np.array_equal(np.asarray(ev(rec)), ref)

    def test_tune_forest_workload_winner_is_measured_minimum(self, tmp_path):
        trees = [breadth_first_encode(random_tree(n_attrs=5, n_classes=4,
                                                  max_depth=4, seed=s))
                 for s in (5, 6, 7)]
        forest = EncodedForest(trees)
        rec = _records(48, 5, seed=43)
        entry, measurements = tune_forest_workload(
            rec, forest, cache=TuneCache(tmp_path / "c.json"), warmup=1, iters=2
        )
        ok = [m for m in measurements if not m.failed]
        assert entry.median_ms == min(m.median_ms for m in ok)
        variants = {m.candidate.variant for m in ok}
        assert PER_TREE_FAMILY in variants  # all families were really timed
        assert any(v in FOREST_VARIANTS for v in variants)

    def test_eval_forest_tuned_functional_wrapper(self, tmp_path):
        trees = [breadth_first_encode(random_tree(n_attrs=9, n_classes=6,
                                                  max_depth=d, seed=d))
                 for d in (2, 5, 8)]
        forest = EncodedForest(trees)
        rec = _records(120, 9, seed=44)
        out = np.asarray(tuned_eval_forest(rec, forest,
                                           cache=TuneCache(tmp_path / "c.json")))
        for i in range(3):
            assert np.array_equal(out[i], eval_serial(forest.tree(i), rec))

    def test_tree_serve_engine_waves(self, tmp_path):
        from repro.serve import TreeRequest, TreeServeEngine

        enc = breadth_first_encode(paper_tree())
        rng = np.random.default_rng(12)
        reqs = [
            TreeRequest(uid=i, records=rng.normal(size=(int(rng.integers(1, 100)), 19)).astype(np.float32))
            for i in range(9)
        ]
        eng = TreeServeEngine(enc, max_batch=256, cache=TuneCache(tmp_path / "c.json"))
        eng.run(reqs)
        assert eng.stats.waves >= 2
        assert eng.stats.records == sum(r.records.shape[0] for r in reqs)
        for r in reqs:
            assert r.done
            assert np.array_equal(r.out, eval_serial(enc, r.records))


# ---------------------------------------------------------------------------
# Quantized layouts in the tuner (opt-in candidates, cache identity, refusal)
# ---------------------------------------------------------------------------


class TestQuantLayoutTuning:
    SHAPE = ForestShape(t=4, m=256, n_nodes=31, n_attrs=19, depth_min=6, depth_max=6)

    def _forest(self, seeds=(8, 9)):
        trees = [breadth_first_encode(random_tree(n_attrs=7, n_classes=5,
                                                  max_depth=4, seed=s))
                 for s in seeds]
        return EncodedForest(trees)

    def test_quant_candidates_are_opt_in(self):
        default = {c.variant for c in
                   forest_search_space(self.SHAPE, engines=("pallas", "jnp"))}
        assert not any(v.endswith("_q") for v in default)

        cands = list(forest_search_space(self.SHAPE, engines=("pallas", "jnp"),
                                         layouts=("f32", "quant")))
        quant = [c for c in cands if c.variant.endswith("_q")]
        assert quant, "layouts opt-in must add quantized candidates"
        from repro.tune.space import QUANT_THR_DTYPES
        for c in quant:
            # thr_dtype is a cache-identity parameter: every quant candidate
            # must carry one so different node dtypes never collide.
            assert c.param_dict.get("thr_dtype") in QUANT_THR_DTYPES
        # both dtypes are actually enumerated
        assert {c.param_dict["thr_dtype"] for c in quant} == set(QUANT_THR_DTYPES)

        only_quant = {c.variant for c in
                      forest_search_space(self.SHAPE, engines=("pallas", "jnp"),
                                          layouts=("quant",))}
        assert only_quant and all(v.endswith("_q") for v in only_quant)
        assert PER_TREE_FAMILY not in only_quant  # per-tree rides on f32 tables

    def test_thr_dtype_is_candidate_identity(self):
        a = Candidate.make("forest_fused_speculative_q", block_m=256,
                           thr_dtype="bfloat16")
        b = Candidate.make("forest_fused_speculative_q", block_m=256,
                           thr_dtype="float16")
        assert a != b and hash(a) != hash(b)
        # and the dtype survives a cache round-trip inside the params blob
        assert a.param_dict["thr_dtype"] == "bfloat16"

    def test_thr_dtype_round_trips_through_cache(self, tmp_path):
        cache = TuneCache(tmp_path / "c.json")
        cache.store("k", TuneEntry(variant="forest_fused_speculative_q",
                                   params={"block_m": 256, "thr_dtype": "float16"},
                                   median_ms=0.5))
        hit = TuneCache(tmp_path / "c.json").lookup("k")
        assert hit.params == {"block_m": 256, "thr_dtype": "float16"}

    def test_default_evaluator_refuses_cached_quant_winner(self, tmp_path):
        """layouts=None means f32-only: a quant winner cached by an opted-in
        sibling must not be replayed by a default evaluator."""
        forest = self._forest()
        rec = _records(64, 7, seed=45)
        cache = TuneCache(tmp_path / "c.json")
        ev = ForestTunedEvaluator(forest, cache=cache)
        cache.store(ev.shape_of(rec).key(),
                    TuneEntry(variant="forest_fused_data_parallel_q",
                              params={"block_m": 256, "thr_dtype": "bfloat16"},
                              median_ms=0.01))
        cand, source = ev.resolve(rec)
        assert source == "heuristic"            # quant hit refused
        assert not cand.variant.endswith("_q")
        # an evaluator that opted into quant layouts does take the hit
        opted = ForestTunedEvaluator(forest, cache=cache,
                                     layouts=("f32", "quant"))
        cand, source = opted.resolve(rec)
        assert source == "cache"
        assert cand.variant == "forest_fused_data_parallel_q"
        # and its replay stays bit-exact
        ref = np.stack([eval_serial(forest.tree(i), rec) for i in range(2)])
        assert np.array_equal(np.asarray(opted(rec)), ref)

    def test_layout_restricted_winner_not_stored(self, tmp_path):
        """A layout-filtered autotune winner must not overwrite the bucket's
        unrestricted entry (same rule as family restriction)."""
        forest = self._forest(seeds=(10, 11))
        rec = _records(64, 7, seed=46)
        cache = TuneCache(tmp_path / "c.json")
        ev = ForestTunedEvaluator(forest, cache=cache, autotune=True,
                                  layouts=("quant",),
                                  engines=("pallas", "jnp"),  # quant is pallas-only
                                  measure_kw={"warmup": 1, "iters": 2})
        ref = np.stack([eval_serial(forest.tree(i), rec) for i in range(2)])
        assert np.array_equal(np.asarray(ev(rec)), ref)
        assert cache.lookup(ev.shape_of(rec).key()) is None

    def test_stale_version_quant_winner_discarded(self, tmp_path):
        """A CACHE_VERSION bump orphans stored winners — the medians priced
        node tables that predate the quantized registry."""
        from repro.tune.cache import CACHE_VERSION

        path = tmp_path / "c.json"
        path.write_text(json.dumps({
            "version": CACHE_VERSION - 1,
            "registry": registry_fingerprint(),
            "entries": {"k": {"variant": "forest_fused_speculative_q",
                              "params": {"thr_dtype": "bfloat16"},
                              "median_ms": 0.1}},
        }))
        assert TuneCache(path).lookup("k") is None

    def test_fingerprint_covers_layout(self):
        """The live fingerprint must change if a spec's layout tag changes:
        stored winners priced a registry where that name meant other tables."""
        import dataclasses as _dc

        spec = FOREST_VARIANTS["forest_fused_speculative_q"]
        assert spec.layout == "quant"
        registry_fingerprint.cache_clear()   # fingerprint is memoised
        fp = registry_fingerprint()
        FOREST_VARIANTS["forest_fused_speculative_q"] = _dc.replace(spec, layout="f32")
        registry_fingerprint.cache_clear()
        try:
            assert registry_fingerprint() != fp
        finally:
            FOREST_VARIANTS["forest_fused_speculative_q"] = spec
            registry_fingerprint.cache_clear()
        assert registry_fingerprint() == fp


def test_failed_candidate_is_logged_and_counted(caplog):
    """A candidate that raises is a logged, counted failure with its error —
    never a silent ∞ that quietly loses to a jnp variant."""
    import logging

    import jax.numpy as jnp

    from repro import obs
    from repro.tune.measure import _note_measurements, measure_candidate

    enc = breadth_first_encode(random_tree(n_attrs=19, n_classes=7, max_depth=8, seed=0))
    assert enc.n_nodes > 256   # the onehot jump's one-hot cannot fit VMEM here
    rec = jnp.zeros((64, 19), jnp.float32)
    with caplog.at_level(logging.WARNING, logger="repro.tune.measure"):
        m = measure_candidate(Candidate.make("pallas_speculative_onehot"), rec, enc,
                              max_depth=8, warmup=0, iters=1)
    assert m.failed and "no record tile fits" in m.error
    assert any("pallas_speculative_onehot" in r.getMessage() for r in caplog.records)
    reg = obs.Registry()
    _note_measurements(reg, "tree", [m])
    counters = obs.snapshot(reg)["counters"]
    assert counters['tune.failed_candidates{level="tree"}'] == 1
