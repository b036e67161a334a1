"""Transparent variant dispatch: ``tuned_eval(records, tree)``.

Resolution order for each (backend, shape-bucket):

  1. in-process memo (one dict probe on the hot path),
  2. persistent cache (:class:`repro.tune.cache.TuneCache`),
  3. optional on-miss autotune (``autotune=True`` — measures the search
     space once and persists the winner),
  4. the §3.6-model heuristic (:mod:`repro.tune.heuristic`).

Dispatch zero-pads the record batch up to the bucket's M before running the
variant and slices the padding back off, so every call inside a bucket hits
one jit specialisation and the timings stored by the tuner stay honest.
All variants are exact (bit-identical to the serial reference), so dispatch
never changes results — only which kernel produces them.

:class:`ForestTunedEvaluator` lifts the same contract to whole forests: the
resolution unit is the (T, M, N_max, A, depth-profile) bucket and the
candidate space spans three families (per-tree variant vectors, shared-
variant vmap, fused stacked kernel).  Both evaluators expose ``promote`` /
``invalidate`` — the atomic winner-swap hooks the serve engines' background
re-tune drives.

Both evaluators pack a Pallas winner's node tables once per record width
when its bucket resolves and hand the packed tables to the variant, so a
steady-state call is one jitted call on prepared tables.

A call is timed on the evaluator's tracer in phases: ``tune.h2d`` (host
records copied to the device), ``tune.resolve`` (a fast-path miss, with
``kernel.pack`` inside it where it packs tables), ``tune.pad`` (bucket
padding) and ``tune.variant`` (the variant call up to its asynchronous
return, which times its jitted call as ``kernel.launch``).
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.tree import EncodedTree, tree_depth
from repro.kernels.tree_eval.cascade import (
    CASCADE_VARIANTS,
    MAJORITY_FAMILY,
    get_cascade_variant,
)
from repro.kernels.tree_eval.ops import (
    FOREST_VARIANTS,
    PER_TREE_FAMILY,
    VARIANTS,
    PackedForest,
    PackedTree,
    choose_block_m,
    get_forest_variant,
    get_variant,
)
from repro.kernels.tree_eval.quant import QuantizedForest
from repro.tune.cache import TuneCache, TuneEntry
from repro.tune.heuristic import (
    cascade_heuristic_candidate,
    default_d_mu,
    forest_heuristic_candidate,
    heuristic_candidate,
    measured_d_mu,
    measured_forest_d_mu,
    measured_survival_rate,
)
from repro.tune.measure import (
    bucket_pad_records,
    tune_cascade_workload,
    tune_forest_workload,
    tune_workload,
)
from repro.tune.space import Candidate, ForestShape, WorkloadShape, backend_tag


class _TuneObs:
    """The tuner's shared instrument set on one registry.

    Levels: ``tree`` (per-tree variant resolution), ``forest`` (family
    resolution), ``classes`` (majority-vote vs cascade).  The agreement
    counter compares each *measured* winner against what the §3.6 heuristic
    would have picked for the same bucket — the running answer to "is the
    model good enough to skip measuring?".
    """

    def __init__(self, registry: obs.Registry | None,
                 tracer: obs.Tracer | None):
        self.registry = registry if registry is not None else obs.default_registry()
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        r = self.registry
        self.resolutions = r.counter(
            "tune.resolutions", "kernel resolutions by level and source",
            ("level", "source"))
        self.swaps = r.counter(
            "tune.winner_swaps", "atomic winner promotions (background re-tune)",
            ("level",))
        self.agreement = r.counter(
            "tune.heuristic_agreement",
            "measured winner vs §3.6-heuristic pick, per autotune resolution",
            ("level", "agree"))
        self.d_mu_gauge = r.gauge(
            "tune.d_mu", "d_µ the §3.6 heuristic evaluated at, by provenance",
            ("level", "source"))
        self.d_mu_provenance = r.counter(
            "tune.d_mu_provenance",
            "heuristic resolutions by d_µ provenance "
            "(measured=traversal profiler, sampled=host descent, prior=geometry)",
            ("level", "source"))
        self.d_mu_agreement = r.counter(
            "tune.d_mu_agreement",
            "measured-d_µ heuristic pick vs geometry-prior pick, per resolution",
            ("level", "agree"))
        self.survival_provenance = r.counter(
            "tune.survival_provenance",
            "cascade-survival provenance at class-level resolutions",
            ("source",))
        self.h2d_bytes = r.counter(
            "tune.h2d_bytes", "bytes of records copied host to device")
        self.pad_bytes = r.counter(
            "kernel.pad_bytes",
            "bytes of padding the kernels add to their records (attributes and tile rows)")
        self.packs = r.counter(
            "kernel.packs", "node-table packs built on the dispatch path", ("level",))

    def to_device(self, records) -> jax.Array:
        """``records`` as a float32 device array; a copy from the host is
        timed (``tune.h2d``) and counted (``tune.h2d_bytes``)."""
        if isinstance(records, jax.Array):
            return records if records.dtype == jnp.float32 else records.astype(jnp.float32)
        with self.tracer.span("tune.h2d", cat="tune"):
            records = jnp.asarray(records, jnp.float32)
        self.h2d_bytes.inc(records.nbytes)
        return records

    def pad(self, records, bucket_m: int):
        """``bucket_pad_records`` timed as ``tune.pad`` where it pads."""
        if records.shape[0] == bucket_m:
            return records
        with self.tracer.span("tune.pad", cat="tune", bucket_m=bucket_m):
            return bucket_pad_records(records, bucket_m)

    def pack(self, level: str, build):
        """``build()``, the node tables of a resolved winner, timed as
        ``kernel.pack`` and counted on ``kernel.packs``."""
        with self.tracer.span("kernel.pack", cat="kernel", level=level):
            packed = build()
        self.packs.labels(level=level).inc()
        return packed

    def note_resolution(self, level: str, source: str) -> None:
        self.resolutions.labels(level=level, source=source).inc()

    def note_d_mu(self, level: str, source: str, value: float) -> None:
        self.d_mu_provenance.labels(level=level, source=source).inc()
        self.d_mu_gauge.labels(level=level, source=source).set(value)

    def note_d_mu_agreement(self, level: str, cand: Candidate,
                            prior_pick) -> None:
        """Would the geometry prior have picked the same variant as the
        profiler-measured d_µ did?  Mirrors :meth:`note_agreement` — a
        running answer to "does measuring d_µ actually change decisions?"."""
        try:
            h = prior_pick()
            agree = "yes" if h.variant == cand.variant else "no"
        except Exception:
            agree = "error"
        self.d_mu_agreement.labels(level=level, agree=agree).inc()

    def note_swap(self, level: str, key: str) -> None:
        self.swaps.labels(level=level).inc()
        self.tracer.instant("tune.promote", cat="tune", level=level, bucket=key)

    def note_agreement(self, level: str, measured: Candidate,
                       heuristic_pick) -> None:
        try:
            h = heuristic_pick()
            agree = "yes" if h.variant == measured.variant else "no"
        except Exception:
            agree = "error"
        self.agreement.labels(level=level, agree=agree).inc()


def _resolve_d_mu(kw: dict, *, profiler, key: str, measure: bool, sample_fn):
    """Fill ``kw["d_mu"]`` through the provenance ladder; returns the source.

    caller-supplied ``heuristic_kw`` override > traversal-profiler
    measurement for this bucket > host-sampled descent on the batch >
    geometry prior (``kw`` left without d_mu — the heuristic defaults it).
    """
    if "d_mu" in kw:
        return "caller"
    if profiler is not None:
        measured = profiler.d_mu(key)
        if measured is not None:
            kw["d_mu"] = measured
            return "measured"
    if measure:
        kw["d_mu"] = sample_fn()
        return "sampled"
    return "prior"


class TunedEvaluator:
    """Reusable tuned dispatcher for one encoded tree.

    Prefer this over the functional :func:`tuned_eval` on hot paths (serving,
    forests): it owns the depth computation, the cache handle, and a
    per-bucket resolution memo, so steady-state calls do no lookup work.
    """

    def __init__(
        self,
        enc: EncodedTree,
        *,
        cache: TuneCache | None = None,
        autotune: bool = False,
        engines: tuple[str, ...] | None = None,
        measure_kw: dict | None = None,
        measure_d_mu: bool = True,
        d_mu_sample: int = 256,
        heuristic_kw: dict | None = None,
        registry: obs.Registry | None = None,
        tracer: obs.Tracer | None = None,
        profiler=None,
    ):
        self.enc = enc
        self.cache = cache if cache is not None else TuneCache()
        self.autotune = autotune
        self.engines = engines
        self._obs = _TuneObs(registry, tracer)
        # a TraversalProfiler (or anything with .d_mu(key)): measured d_µ
        # per bucket beats both the host sample and the geometry prior
        self.profiler = profiler
        self.measure_kw = dict(measure_kw or {})
        # heuristic fallback: measure d_µ on a sample of the actual batch
        # (paper: "measured on a significant sample") instead of trusting
        # the geometry prior; heuristic_kw forwards cm/p_group overrides.
        self.measure_d_mu = measure_d_mu
        self.d_mu_sample = d_mu_sample
        self.heuristic_kw = dict(heuristic_kw or {})
        self.depth = max(tree_depth(enc), 1)
        self._resolved: dict[str, tuple[Candidate, str]] = {}
        # (M, A) → (spec, params, bucket_m, tables): the steady-state call
        # path does one dict probe and one jitted call.
        self._fast: dict[tuple[int, int], tuple] = {}
        # A → the Pallas variants' tables; they depend on the tree and the
        # width alone, so a winner swap never rebuilds them
        self._packed: dict[int, PackedTree] = {}
        # guards promote()/invalidate() against the resolve path; the fast
        # path itself stays lock-free (GIL-atomic dict probes).  _gen counts
        # swaps so a runner built from a pre-swap resolution is never cached
        # over a fresh promotion.
        self._swap_lock = threading.Lock()
        self._gen = 0

    def promote(self, key: str, cand: Candidate) -> None:
        """Atomically swap the winner for bucket ``key`` (background re-tune).

        Callers observe either the old winner or the new one, never a torn
        state: the memo entry and the fast-path table swap under one lock,
        and every variant is exact, so results are identical either way.
        """
        with self._swap_lock:
            self._gen += 1
            self._resolved[key] = (cand, "retune")
            self._fast.clear()
        self._obs.note_swap("tree", key)

    def invalidate(self) -> None:
        """Drop all resolution memos so the next call re-reads the cache."""
        with self._swap_lock:
            self._gen += 1
            self._resolved.clear()
            self._fast.clear()

    def _stamp_d_mu_provenance(self, key: str, entry: TuneEntry) -> None:
        """Re-store an autotuned cache entry with the profiler's measured d_µ
        (cache provenance: a later reader can see what traffic the winner
        was tuned under, and whether d_µ was measured or assumed)."""
        measured = self.profiler.d_mu(key) if self.profiler is not None else None
        if measured is not None:
            self.cache.store(
                key,
                dataclasses.replace(entry, d_mu=measured, d_mu_source="measured"),
            )

    def resolve(self, records) -> tuple[Candidate, str]:
        """Pick the candidate for this batch; returns (candidate, source)
        with source ∈ {"memo", "cache", "autotune", "heuristic"}."""
        shape = WorkloadShape.of(records, self.enc, self.depth)
        backend = backend_tag()
        key = shape.key(backend)
        hit = self._resolved.get(key)
        if hit is not None:
            self._obs.note_resolution("tree", "memo")
            return hit[0], "memo"

        entry = self.cache.lookup(key)
        source = "cache"
        if entry is not None and entry.variant in VARIANTS:
            cand = Candidate.make(entry.variant, **entry.params)
        elif self.autotune:
            with self._obs.tracer.span("tune.measure", cat="tune",
                                       level="tree", bucket=key):
                entry, _ = tune_workload(
                    records,
                    self.enc,
                    cache=self.cache,
                    engines=self.engines,
                    backend=backend,
                    registry=self._obs.registry,
                    **self.measure_kw,
                )
            cand = Candidate.make(entry.variant, **entry.params)
            source = "autotune"
            self._obs.note_agreement(
                "tree", cand,
                lambda: heuristic_candidate(
                    shape, engines=self.engines, **self.heuristic_kw),
            )
            self._stamp_d_mu_provenance(key, entry)
        else:
            kw = dict(self.heuristic_kw)
            d_mu_source = _resolve_d_mu(
                kw, profiler=self.profiler, key=key, measure=self.measure_d_mu,
                sample_fn=lambda: measured_d_mu(
                    self.enc, records, sample=self.d_mu_sample),
            )
            cand = heuristic_candidate(shape, engines=self.engines, **kw)
            source = "heuristic"
            self._obs.note_d_mu(
                "tree", d_mu_source, kw.get("d_mu", default_d_mu(shape)))
            if d_mu_source == "measured":
                prior_kw = dict(self.heuristic_kw)
                prior_kw.pop("d_mu", None)
                self._obs.note_d_mu_agreement(
                    "tree", cand,
                    lambda: heuristic_candidate(
                        shape, engines=self.engines, **prior_kw),
                )
        self._obs.note_resolution("tree", source)
        # setdefault under the lock: if a background promote() landed while
        # we resolved, its winner must not be overwritten with ours (and the
        # returned value is read inside the same critical section — a
        # concurrent invalidate() may clear the dict right after)
        with self._swap_lock:
            resolved = self._resolved.setdefault(key, (cand, source))
        return resolved[0], source

    def _tables(self, a: int) -> PackedTree:
        """The Pallas variants' tables at width ``a``, packed on first use."""
        packed = self._packed.get(a)
        if packed is None:
            packed = self._packed.setdefault(
                a, self._obs.pack("tree", lambda: PackedTree(self.enc, a)))
        return packed

    def _fast_entry(self, cand: Candidate, m: int, a: int) -> tuple:
        """(spec, params, bucket_m, tables) for one resolved candidate: a
        Pallas variant gets the packed tables and its tile, resolved here; a
        jnp variant gets the encoding."""
        spec = get_variant(cand.variant)
        params = cand.param_dict
        bucket_m = WorkloadShape(m, self.enc.n_nodes, a, self.depth).bucket().m
        if spec.engine != "pallas":
            return spec, params, bucket_m, self.enc
        packed = self._tables(a)
        if params.get("block_m") is None:
            params = dict(params, block_m=choose_block_m(
                packed.n_nodes, packed.n_attrs_padded, jump_mode=spec.jump_mode))
        return spec, params, bucket_m, packed

    def __call__(self, records) -> jax.Array:
        """Evaluate the tree over ``records`` (M, A) → (M,) int32 classes,
        through the bucket's resolved variant (bucket-padded, unpadded on
        return); bit-identical to ``eval_serial`` for every resolution."""
        tob = self._obs
        records = tob.to_device(records)
        m, a = records.shape
        fast = self._fast.get((m, a))
        if fast is None:
            with tob.tracer.span("tune.resolve", cat="tune"):
                gen = self._gen
                cand, _ = self.resolve(records)
                fast = self._fast_entry(cand, m, a)
                with self._swap_lock:
                    if gen == self._gen:   # don't cache a pre-swap resolution
                        self._fast[(m, a)] = fast
        spec, params, bucket_m, tables = fast
        records = tob.pad(records, bucket_m)
        with tob.tracer.span("tune.variant", cat="tune", variant=spec.name):
            out = spec.fn(records, tables, max_depth=self.depth, tracer=tob.tracer,
                          pad_bytes=tob.pad_bytes, **params)
            return out if out.shape[0] == m else out[:m]


def tuned_eval(
    records,
    tree: EncodedTree,
    *,
    cache: TuneCache | None = None,
    autotune: bool = False,
    engines: tuple[str, ...] | None = None,
) -> jax.Array:
    """Evaluate ``tree`` over ``records`` with the cached-best variant.

    One-shot convenience wrapper around :class:`TunedEvaluator`; returns the
    (M,) int32 class assignments, bit-identical to ``eval_serial``.
    """
    return TunedEvaluator(tree, cache=cache, autotune=autotune, engines=engines)(records)


# ---------------------------------------------------------------------------
# Forest-level dispatch
# ---------------------------------------------------------------------------


class ForestTunedEvaluator:
    """Reusable tuned dispatcher for one encoded *forest*.

    The forest analogue of :class:`TunedEvaluator`, and the single selection
    point every forest call routes through (``eval_forest_tuned``, the
    ``repro.dist`` executor, ``ForestServeEngine``).  Resolution order per
    (backend, forest-bucket):

      1. in-process memo,
      2. persistent cache (forest bucket keys, see
         :meth:`repro.tune.space.ForestShape.key`),
      3. optional on-miss autotune (``autotune=True`` — measures all three
         candidate families via :func:`repro.tune.measure.tune_forest_workload`),
      4. the §3.6-model family heuristic
         (:func:`repro.tune.heuristic.forest_heuristic_candidate`).

    The winning candidate is one of three families: ``per_tree`` dispatches
    each tree through its own :class:`TunedEvaluator` (the PR 3 path — a
    per-tree variant *vector*); ``vmap`` runs one shared variant stacked
    over the tree axis; ``fused`` launches the stacked Pallas kernel once
    for the whole forest.  All families are exact, so the choice never
    changes results — bit-identical to evaluating tree by tree.
    """

    def __init__(
        self,
        forest,
        *,
        cache: TuneCache | None = None,
        autotune: bool = False,
        engines: tuple[str, ...] | None = None,
        families: tuple[str, ...] | None = None,
        layouts: tuple[str, ...] | None = None,
        measure_kw: dict | None = None,
        measure_d_mu: bool = True,
        d_mu_sample: int = 256,
        heuristic_kw: dict | None = None,
        registry: obs.Registry | None = None,
        tracer: obs.Tracer | None = None,
        profiler=None,
    ):
        from repro.core.forest import EncodedForest  # local: core ↔ tune layering

        self.forest = forest if isinstance(forest, EncodedForest) else EncodedForest(list(forest))
        self.cache = cache if cache is not None else TuneCache()
        self.autotune = autotune
        self.engines = engines
        self._obs = _TuneObs(registry, tracer)
        # a TraversalProfiler keyed by this evaluator's forest-bucket keys:
        # measured d_µ and cascade survival replace the sample/prior fallbacks
        self.profiler = profiler
        self.families = families
        # node-table layout opt-in: None ≡ ("f32",) — quantized layouts only
        # compete (and quant cached winners are only honoured) when a caller
        # passes layouts including "quant".  All quant layouts dispatch may
        # build are universal-mode (exact for every input), so the opt-in is
        # about footprint/latency trade-offs, never about correctness.
        self.layouts = layouts
        self.measure_kw = dict(measure_kw or {})
        self.measure_d_mu = measure_d_mu
        self.d_mu_sample = d_mu_sample
        self.heuristic_kw = dict(heuristic_kw or {})
        from repro.core.tree import tree_depth as _td

        depths = [max(_td(self.forest.tree(i)), 1) for i in range(self.forest.n_trees)]
        self.depth_min = min(depths)
        self.depth_max = max(depths)
        self._resolved: dict[str, tuple[Candidate, str]] = {}
        self._fast: dict[tuple[int, int], object] = {}   # (M, A) → runner
        self._per_tree: list[TunedEvaluator] | None = None
        self._packed: PackedForest | None = None
        self._quant: QuantizedForest | None = None
        self._quant_key: tuple | None = None   # (n_attrs, thr_dtype)
        self._swap_lock = threading.Lock()
        self._gen = 0

    # -- re-tune hooks ------------------------------------------------------

    def promote(self, key: str, cand: Candidate) -> None:
        """Atomically swap the winner for forest bucket ``key``.

        See :meth:`TunedEvaluator.promote` — same contract: in-flight calls
        finish on the old winner, subsequent calls run the new one, results
        are bit-identical throughout.
        """
        with self._swap_lock:
            self._gen += 1
            self._resolved[key] = (cand, "retune")
            self._fast.clear()
        self._obs.note_swap("forest", key)

    def invalidate(self) -> None:
        """Drop all resolution memos so the next call re-reads the cache."""
        with self._swap_lock:
            self._gen += 1
            self._resolved.clear()
            self._fast.clear()

    def _family_allowed(self, variant: str) -> bool:
        """Whether a cached winner's family is within this evaluator's
        ``families`` restriction (a family-restricted evaluator must never
        run another family just because a sibling cached it)."""
        if self.families is None:
            return True
        if variant == PER_TREE_FAMILY:
            return PER_TREE_FAMILY in self.families
        return FOREST_VARIANTS[variant].family in self.families

    def _layout_allowed(self, variant: str) -> bool:
        """Whether a cached winner's node-table layout is within this
        evaluator's ``layouts`` restriction — a default (f32-only) evaluator
        must never run a quantized layout just because a layout-opted-in
        sibling cached it, and vice versa."""
        if variant == PER_TREE_FAMILY:
            layout = "f32"
        else:
            layout = getattr(FOREST_VARIANTS[variant], "layout", "f32")
        allowed = ("f32",) if self.layouts is None else self.layouts
        return layout in allowed

    def _stamp_d_mu_provenance(self, key: str, entry: TuneEntry) -> None:
        """See :meth:`TunedEvaluator._stamp_d_mu_provenance`."""
        measured = self.profiler.d_mu(key) if self.profiler is not None else None
        if measured is not None:
            self.cache.store(
                key,
                dataclasses.replace(entry, d_mu=measured, d_mu_source="measured"),
            )

    # -- resolution ---------------------------------------------------------

    def shape_of(self, records) -> ForestShape:
        """The :class:`ForestShape` of this batch (depths precomputed)."""
        return ForestShape.of(
            records, self.forest, depth_min=self.depth_min, depth_max=self.depth_max
        )

    def resolve(self, records) -> tuple[Candidate, str]:
        """Pick the forest candidate for this batch.

        Returns:
          (candidate, source) with source ∈ {"memo", "cache", "autotune",
          "heuristic"}; after a background re-tune the memo carries the
          promoted winner.
        """
        shape = self.shape_of(records)
        backend = backend_tag()
        key = shape.key(backend)
        hit = self._resolved.get(key)
        if hit is not None:
            self._obs.note_resolution("forest", "memo")
            return hit[0], "memo"

        entry = self.cache.lookup(key)
        source = "cache"
        if (
            entry is not None
            and (entry.variant in FOREST_VARIANTS or entry.variant == PER_TREE_FAMILY)
            and self._family_allowed(entry.variant)
            and self._layout_allowed(entry.variant)
        ):
            cand = Candidate.make(entry.variant, **entry.params)
        elif self.autotune:
            with self._obs.tracer.span("tune.measure", cat="tune",
                                       level="forest", bucket=key):
                entry, _ = tune_forest_workload(
                    records,
                    self.forest,
                    cache=self.cache,
                    engines=self.engines,
                    families=self.families,
                    layouts=self.layouts,
                    backend=backend,
                    autotune_trees=True,   # per-tree family priced at its tuned best
                    # a restricted (family- or layout-filtered) winner must
                    # not overwrite the bucket's unrestricted one
                    store=self.families is None and self.layouts is None,
                    registry=self._obs.registry,
                    **self.measure_kw,
                )
            cand = Candidate.make(entry.variant, **entry.params)
            source = "autotune"
            self._obs.note_agreement(
                "forest", cand,
                lambda: forest_heuristic_candidate(
                    shape, engines=self.engines, families=self.families,
                    **self.heuristic_kw),
            )
            self._stamp_d_mu_provenance(key, entry)
        else:
            kw = dict(self.heuristic_kw)
            d_mu_source = _resolve_d_mu(
                kw, profiler=self.profiler, key=key, measure=self.measure_d_mu,
                sample_fn=lambda: measured_forest_d_mu(
                    self.forest, records, sample=self.d_mu_sample),
            )
            cand = forest_heuristic_candidate(
                shape, engines=self.engines, families=self.families, **kw
            )
            source = "heuristic"
            self._obs.note_d_mu(
                "forest", d_mu_source,
                kw.get("d_mu", default_d_mu(shape.tree_shape())))
            if d_mu_source == "measured":
                prior_kw = dict(self.heuristic_kw)
                prior_kw.pop("d_mu", None)
                self._obs.note_d_mu_agreement(
                    "forest", cand,
                    lambda: forest_heuristic_candidate(
                        shape, engines=self.engines, families=self.families,
                        **prior_kw),
                )
        self._obs.note_resolution("forest", source)
        # same critical-section discipline as TunedEvaluator.resolve: don't
        # clobber a concurrent promote(), don't re-read after unlocking
        with self._swap_lock:
            resolved = self._resolved.setdefault(key, (cand, source))
        return resolved[0], source

    # -- evaluation ---------------------------------------------------------

    def _tree_evaluators(self) -> list[TunedEvaluator]:
        if self._per_tree is None:
            self._per_tree = [
                TunedEvaluator(
                    self.forest.tree(i), cache=self.cache, engines=self.engines,
                    autotune=self.autotune, measure_kw=self.measure_kw,
                    registry=self._obs.registry, tracer=self._obs.tracer,
                )
                for i in range(self.forest.n_trees)
            ]
        return self._per_tree

    def _runner(self, cand: Candidate, m: int, a: int):
        """Build the steady-state callable for one resolved candidate."""
        if cand.variant == PER_TREE_FAMILY:
            evs = self._tree_evaluators()
            return lambda rec: jnp.stack([ev(rec) for ev in evs])
        tob = self._obs
        spec = get_forest_variant(cand.variant)
        params = cand.param_dict
        depth = max(int(self.forest.max_depth), 1)
        bucket_m = ForestShape(
            t=self.forest.n_trees, m=m, n_nodes=self.forest.n_nodes,
            n_attrs=a, depth_min=self.depth_min, depth_max=self.depth_max,
        ).bucket().m
        if getattr(spec, "layout", "f32") == "quant":
            # Universal-mode quantization (no calibration): bit-exact for
            # every input, so a quant winner never changes results.  The
            # threshold dtype is part of the pack, so the memo keys on it.
            qkey = (a, params.get("thr_dtype", "bfloat16"))
            if self._quant is None or self._quant_key != qkey:
                self._quant = tob.pack("forest", lambda: QuantizedForest(
                    self.forest, a, thr_dtype=qkey[1]))
                self._quant_key = qkey
            target = self._quant
        elif spec.family == "fused":
            if self._packed is None or self._packed.n_attrs != a:
                self._packed = tob.pack("forest", lambda: PackedForest(self.forest, a))
            target = self._packed
        else:
            target = self.forest

        def run(rec):
            rec = tob.pad(rec, bucket_m)
            with tob.tracer.span("tune.variant", cat="tune", variant=spec.name):
                out = spec.fn(rec, target, max_depth=depth, tracer=tob.tracer,
                              pad_bytes=tob.pad_bytes, **params)
                return out if out.shape[1] == m else out[:, :m]

        return run

    def __call__(self, records) -> jax.Array:
        """Per-tree class assignments, shape (T, M) int32."""
        records = self._obs.to_device(records)
        m, a = records.shape
        run = self._fast.get((m, a))
        if run is None:
            with self._obs.tracer.span("tune.resolve", cat="tune"):
                gen = self._gen
                cand, _ = self.resolve(records)
                run = self._runner(cand, m, a)
                with self._swap_lock:
                    if gen == self._gen:   # don't cache a pre-swap resolution
                        self._fast[(m, a)] = run
        return run(records)

    # -- class-level dispatch (majority vote vs early-exit cascade) ---------

    def resolve_classes(self, records, n_classes: int) -> tuple[Candidate, str]:
        """Pick the class-level candidate for this batch.

        Same resolution ladder as :meth:`resolve`, but over the *class*
        question — "which class wins the vote?" — whose candidate set is
        the full majority-vote path (``Candidate(MAJORITY_FAMILY)``) plus
        the early-exit cascades.  Keys carry the class count
        (:meth:`ForestShape.classes_key`), and the heuristic extends the
        §3.6 model with a survival-rate term measured on this batch.  Every
        candidate is exact at bound 1.0, so resolution never changes the
        predicted classes.
        """
        shape = self.shape_of(records)
        backend = backend_tag()
        key = shape.classes_key(n_classes, backend)
        hit = self._resolved.get(key)
        if hit is not None:
            self._obs.note_resolution("classes", "memo")
            return hit[0], "memo"

        entry = self.cache.lookup(key)
        source = "cache"
        if entry is not None and (
            entry.variant == MAJORITY_FAMILY or entry.variant in CASCADE_VARIANTS
        ):
            cand = Candidate.make(entry.variant, **entry.params)
        elif self.autotune:
            with self._obs.tracer.span("tune.measure", cat="tune",
                                       level="classes", bucket=key):
                entry, _ = tune_cascade_workload(
                    records,
                    self.forest,
                    n_classes,
                    cache=self.cache,
                    engines=self.engines,
                    backend=backend,
                    registry=self._obs.registry,
                    **self.measure_kw,
                )
            cand = Candidate.make(entry.variant, **entry.params)
            source = "autotune"
        else:
            kw = dict(self.heuristic_kw)
            # profiler measurements are keyed by the forest bucket (the
            # engine's wave key), not the |C-suffixed class key
            forest_key = shape.key(backend)
            d_mu_source = _resolve_d_mu(
                kw, profiler=self.profiler, key=forest_key,
                measure=self.measure_d_mu,
                sample_fn=lambda: measured_forest_d_mu(
                    self.forest, records, sample=self.d_mu_sample),
            )
            survival = kw.pop("survival", None)
            survival_source = "caller"
            if survival is None and self.profiler is not None:
                measured = self.profiler.survival(forest_key)
                if measured is not None:
                    # the profiler reports the mean per-stage survival rate;
                    # expand it geometrically over the deepest stage grid the
                    # heuristic may price (surv_s = rate^s, surv_0 = 1)
                    survival = tuple(
                        min(1.0, float(measured)) ** s for s in range(8))
                    survival_source = "measured"
            if survival is None:
                survival = measured_survival_rate(
                    self.forest, records, n_classes, sample=self.d_mu_sample
                )
                survival_source = "sampled"
            cand = cascade_heuristic_candidate(
                shape, n_classes, survival=survival, engines=self.engines, **kw
            )
            source = "heuristic"
            self._obs.note_d_mu(
                "classes", d_mu_source,
                kw.get("d_mu", default_d_mu(shape.tree_shape())))
            self._obs.survival_provenance.labels(source=survival_source).inc()
        self._obs.note_resolution("classes", source)
        with self._swap_lock:
            resolved = self._resolved.setdefault(key, (cand, source))
        return resolved[0], source

    def _class_runner(self, cand: Candidate, n_classes: int, records):
        """Build the steady-state classes callable for one resolution."""
        from repro.core.forest import majority_vote  # local: core ↔ tune layering

        if cand.variant == MAJORITY_FAMILY:
            return lambda rec: majority_vote(self(rec), n_classes)
        import numpy as np

        spec = get_cascade_variant(cand.variant)
        params = cand.param_dict
        # the evaluator is stateful (packed stage tables, latency EMAs):
        # build once per resolved bucket, calibrate the plan on this batch
        ev = spec.build(
            self.forest,
            n_classes=n_classes,
            stages=int(params.get("stages", 2)),
            bound=1.0,
            block_m=params.get("block_m"),
            calibration=records,
            registry=self._obs.registry,
            tracer=self._obs.tracer,
        )

        def run(rec):
            return jnp.asarray(ev(np.asarray(rec)).classes)

        run.cascade = ev  # exposed for introspection / serve-engine stats
        return run

    def predict(self, records, n_classes: int) -> jax.Array:
        """Majority-vote classes, shape (M,) int32, via class-level dispatch.

        Either the full forest path (``majority_vote`` over
        :meth:`__call__`) or a calibrated early-exit cascade — whichever the
        resolution picked.  Both are exact, so the output always equals
        ``majority_vote(self(records), n_classes)``.
        """
        records = self._obs.to_device(records)
        m, a = records.shape
        key = ("cls", m, a, int(n_classes))
        run = self._fast.get(key)
        if run is None:
            gen = self._gen
            cand, _ = self.resolve_classes(records, n_classes)
            run = self._class_runner(cand, n_classes, records)
            with self._swap_lock:
                if gen == self._gen:   # don't cache a pre-swap resolution
                    self._fast[key] = run
        return run(records)


def tuned_eval_forest(
    records,
    forest,
    *,
    cache: TuneCache | None = None,
    autotune: bool = False,
    engines: tuple[str, ...] | None = None,
) -> jax.Array:
    """Evaluate ``forest`` over ``records`` with the cached-best family.

    One-shot convenience wrapper around :class:`ForestTunedEvaluator`;
    returns the (T, M) int32 per-tree class assignments, bit-identical to
    evaluating each tree with ``eval_serial``.
    """
    return ForestTunedEvaluator(
        forest, cache=cache, autotune=autotune, engines=engines
    )(records)
