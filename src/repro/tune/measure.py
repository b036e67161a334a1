"""Candidate timing: warmup, synchronised runs, medians.

Timing on an async-dispatch runtime (JAX) needs the discipline the paper
applies to its CUDA timings: compile/warm the candidate outside the timed
region, then bracket each timed call with ``jax.block_until_ready`` so host
timestamps measure device completion, and take the *median* over several
iterations so one-off scheduling noise doesn't crown the wrong variant.
"""

from __future__ import annotations

import dataclasses
import logging
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.tree_eval.cascade import MAJORITY_FAMILY, get_cascade_variant
from repro.kernels.tree_eval.ops import (
    PER_TREE_FAMILY,
    PackedForest,
    PackedTree,
    get_forest_variant,
    get_variant,
)
from repro.kernels.tree_eval.quant import QuantizedForest, forest_table_bytes
from repro.tune.cache import TuneCache, TuneEntry
from repro.tune.space import (
    Candidate,
    ForestShape,
    WorkloadShape,
    backend_tag,
    cascade_search_space,
    forest_search_space,
    search_space,
)

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class Measurement:
    candidate: Candidate
    median_ms: float
    samples_ms: tuple[float, ...]
    # Static cost of the candidate's compiled HLO — ``{"flops", "bytes",
    # "roofline_frac"}`` — or None when the candidate has no single compiled
    # program (host-loop cascades) or lowering failed.  See
    # :func:`candidate_cost`.
    cost: dict | None = None
    # Device-resident node-table bytes of the candidate's layout (the packed
    # tables it keeps in HBM), or None for candidates without a packed
    # target (per-tree family).  Sits next to the HLO-cost gauges so layout
    # sweeps can weigh latency against footprint.
    table_bytes: float | None = None
    # What the candidate raised, for a failed measurement (see _failed).
    error: str | None = None

    @property
    def failed(self) -> bool:
        return not self.samples_ms

    @property
    def mad_ms(self) -> float:
        """Median absolute deviation of the samples — the noise floor the
        trajectory store records next to the median."""
        if not self.samples_ms:
            return 0.0
        med = _median(self.samples_ms)
        return _median([abs(s - med) for s in self.samples_ms])


def _median(xs) -> float:
    xs = sorted(xs)
    n = len(xs)
    mid = n // 2
    return xs[mid] if n % 2 else (xs[mid - 1] + xs[mid]) / 2.0


def roofline_fraction(flops: float, bytes_: float, median_ms: float,
                      device_kind: str | None = None) -> float | None:
    """Achieved fraction of the hardware bound for one measured candidate.

    ``max(flops/peak_flops, bytes/hbm_bw)`` is the shortest time the chip
    could possibly take (the roofline floor); dividing by the measured time
    says how close the candidate got.  Peaks come from
    :data:`repro.launch.roofline.DEVICE_PEAKS` for ``device_kind`` (default:
    the first JAX device's).  A device that is not in the table — the CPU
    among them — has no roofline, and the result is None.
    """
    from repro.launch.roofline import device_peaks

    peaks = device_peaks(device_kind)
    if peaks is None:
        return None
    if median_ms <= 0 or median_ms == float("inf"):
        return 0.0
    floor_s = max(flops / peaks.bf16_flops, bytes_ / peaks.hbm_bw)
    return floor_s / (median_ms / 1e3)


def candidate_cost(fn, records, *, median_ms: float | None = None) -> dict | None:
    """FLOPs / bytes / roofline fraction of ``fn(records)``'s compiled HLO.

    Lowers ``jax.jit(fn)`` with ``records`` as a real argument (a zero-arg
    closure would constant-fold the whole program to a literal) and runs the
    trip-count-aware :func:`repro.utils.hlo_cost.analyze` over the compiled
    text.  Tree kernels are compare/gather programs, so ``flops`` (dot/conv
    only) is typically ~0 and ``bytes`` carries the signal — they are
    memory-bound by construction.  Returns None when lowering or analysis
    fails; cost is decoration, never a reason to fail a sweep.
    """
    from repro.utils.hlo_cost import analyze

    try:
        compiled = jax.jit(fn).lower(records).compile()
        cost = analyze(compiled.as_text())
    except Exception:
        return None
    out = {"flops": float(cost.flops), "bytes": float(cost.bytes)}
    frac = None if median_ms is None else roofline_fraction(cost.flops, cost.bytes, median_ms)
    if frac is not None:
        out["roofline_frac"] = frac
    return out


def _note_measurements(registry, level: str, measurements) -> None:
    """Record one sweep's outcomes: per-candidate medians and failure count.

    Levels mirror the dispatch ladder (``tree`` / ``forest`` / ``classes``);
    without an explicit registry the sweep lands in the process default, so
    one-shot functional tuning is visible too.
    """
    r = registry if registry is not None else obs.default_registry()
    measured = r.counter(
        "tune.measurements", "candidates measured per sweep", ("level",))
    failed = r.counter(
        "tune.failed_candidates",
        "candidates that raised during measurement", ("level",))
    ms = r.histogram(
        "tune.measure_ms", "per-candidate median measurement time",
        ("level",)).labels(level=level)
    g_flops = r.gauge(
        "tune.candidate_flops", "compiled-HLO FLOPs of the measured candidate",
        ("level", "variant"))
    g_bytes = r.gauge(
        "tune.candidate_bytes", "compiled-HLO HBM bytes of the measured candidate",
        ("level", "variant"))
    g_roof = r.gauge(
        "tune.roofline_frac",
        "achieved fraction of the device's roofline bound "
        "(launch/roofline.py DEVICE_PEAKS; absent off the table)",
        ("level", "variant"))
    g_tbytes = r.gauge(
        "tune.candidate_table_bytes",
        "node-table bytes the candidate's layout keeps device-resident",
        ("level", "variant"))
    for m in measurements:
        measured.labels(level=level).inc()
        if m.failed:
            failed.labels(level=level).inc()
        else:
            ms.observe(m.median_ms)
        if m.cost is not None:
            v = m.candidate.variant
            g_flops.labels(level=level, variant=v).set(m.cost["flops"])
            g_bytes.labels(level=level, variant=v).set(m.cost["bytes"])
            if "roofline_frac" in m.cost:
                g_roof.labels(level=level, variant=v).set(m.cost["roofline_frac"])
        if m.table_bytes is not None:
            g_tbytes.labels(level=level, variant=m.candidate.variant).set(m.table_bytes)


def time_callable(fn, *, warmup: int = 2, iters: int = 5) -> tuple[float, ...]:
    """Millisecond samples of ``fn()``; each run synchronised on its output.

    Args:
      fn: zero-argument callable returning a jax array/pytree; called
        ``warmup`` times un-timed (compilation, cache warm) then ``iters``
        times with ``jax.block_until_ready`` bracketing each run.
      warmup/iters: the measurement discipline (see module docstring).

    Returns:
      ``iters`` wall-clock samples in milliseconds (device-completion
      times, not dispatch times).
    """
    for _ in range(warmup):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append((time.perf_counter() - t0) * 1e3)
    return tuple(samples)


def interleaved_samples(
    fns: dict[str, object], *, warmup: int = 2, iters: int = 7
) -> dict[str, list[float]]:
    """Millisecond samples per callable, interleaved round-robin.

    On hosts with drifting load, timing A's iterations and then B's lets the
    drift masquerade as a real difference; interleaving puts every
    contender in the same time window, and rotating the within-round order
    each iteration cancels the warm-cache advantage of running later in a
    round.  Sample i of each key comes from the same round, so per-round
    ratios (``a[i]/b[i]``) are drift-free paired statistics.

    Args:
      fns: {label: zero-argument callable} — every contender to time.
      warmup/iters: per-callable warmup runs and timed rounds.

    Returns:
      {label: [ms, ...]} with ``iters`` samples per label, index-aligned
      across labels (sample i of every label came from round i).
    """
    for fn in fns.values():
        for _ in range(warmup):
            jax.block_until_ready(fn())
    samples: dict[str, list[float]] = {k: [] for k in fns}
    keys = list(fns)
    for i in range(iters):
        for k in keys[i % len(keys):] + keys[: i % len(keys)]:
            t0 = time.perf_counter()
            jax.block_until_ready(fns[k]())
            samples[k].append((time.perf_counter() - t0) * 1e3)
    return samples


def interleaved_medians(fns: dict[str, object], *, warmup: int = 2, iters: int = 7) -> dict[str, float]:
    """Median ms per callable over interleaved samples."""
    samples = interleaved_samples(fns, warmup=warmup, iters=iters)
    return {k: _median(v) for k, v in samples.items()}


def bucket_pad_records(records: jax.Array, bucket_m: int) -> jax.Array:
    """Zero-pad the record batch up to the bucket's M.

    Rows past the real M cost the same as real rows, which is exactly what
    the bucket entry must price in.

    Args:
      records: (M, A) float array with M ≤ ``bucket_m``.
      bucket_m: the shape bucket's record count (a power of two).

    Returns:
      (bucket_m, A) array — ``records`` above zero rows; returned as-is
      when M already equals ``bucket_m``.
    """
    m = records.shape[0]
    if m == bucket_m:
        return records
    return jnp.zeros((bucket_m, records.shape[1]), records.dtype).at[:m].set(records)


def _failed(level: str, candidate: Candidate, exc: Exception) -> Measurement:
    """A candidate that raised: logged with its error and returned as a
    failed measurement (counted as ``tune.failed_candidates`` by the sweep).
    Valid candidates do not raise — the search space offers only what the
    device can run — so this is a bug to read, not a quiet loss."""
    err = f"{type(exc).__name__}: {exc}"
    log.warning("tune %s candidate %s %s raised %s", level, candidate.variant,
                candidate.param_dict, err)
    return Measurement(candidate, float("inf"), (), error=err)


def measure_candidate(
    candidate: Candidate,
    records,
    enc,
    *,
    max_depth: int,
    warmup: int = 2,
    iters: int = 5,
) -> Measurement:
    """Median wall time of one candidate; a raising candidate is logged and
    returned as a failed measurement.

    Args:
      candidate: the (variant, params) pair to time.
      records: (M, A) float32 batch, already bucket-padded by the caller.
      enc: the :class:`repro.core.tree.EncodedTree` under test.
      max_depth: static depth bound passed to the variant.
      warmup/iters: :func:`time_callable` discipline.

    Returns:
      A :class:`Measurement`; ``failed`` (empty samples, median ∞, the
      ``error`` text) when the candidate raised.
    """
    spec = get_variant(candidate.variant)
    params = candidate.param_dict

    try:
        # a Pallas variant gets its tables packed here, outside the timed
        # region, as dispatch packs them once per evaluator
        target = PackedTree(enc, records.shape[1]) if spec.engine == "pallas" else enc

        def fn(rec):
            return spec.fn(rec, target, max_depth=max_depth, **params)

        samples = time_callable(lambda: fn(records), warmup=warmup, iters=iters)
    except Exception as exc:
        return _failed("tree", candidate, exc)
    median = _median(samples)
    return Measurement(candidate, median, samples,
                       candidate_cost(fn, records, median_ms=median))


def tune_workload(
    records,
    enc,
    *,
    cache: TuneCache | None = None,
    engines: tuple[str, ...] | None = None,
    warmup: int = 2,
    iters: int = 5,
    backend: str | None = None,
    verbose: bool = False,
    registry: obs.Registry | None = None,
) -> tuple[TuneEntry, list[Measurement]]:
    """Time every valid candidate for this workload and record the winner.

    Records are zero-padded to the shape bucket's M before timing, so the
    stored median prices the bucket (what dispatch will actually run), not
    the un-padded call.  Returns the winning entry (written to ``cache``
    under the bucket key when a cache is given) plus all measurements.
    """
    from repro.core.tree import tree_depth

    backend = backend or backend_tag()
    rec = jnp.asarray(records, jnp.float32)
    shape = WorkloadShape.of(rec, enc)
    rec = bucket_pad_records(rec, shape.bucket().m)
    depth = max(shape.depth, 1)

    measurements = [
        measure_candidate(c, rec, enc, max_depth=depth, warmup=warmup, iters=iters)
        for c in search_space(shape, engines=engines)
    ]
    _note_measurements(registry, "tree", measurements)
    ok = [m for m in measurements if not m.failed]
    if not ok:
        raise RuntimeError(f"no candidate succeeded for shape {shape}")
    best = min(ok, key=lambda m: m.median_ms)
    if verbose:
        for m in sorted(ok, key=lambda m: m.median_ms):
            print(f"  {m.median_ms:10.3f} ms  {m.candidate.variant} {m.candidate.param_dict}")
    entry = TuneEntry(
        variant=best.candidate.variant,
        params=best.candidate.param_dict,
        median_ms=best.median_ms,
        shape=dataclasses.asdict(shape),
        backend=backend,
    )
    if cache is not None:
        cache.store(shape.key(backend), entry)
    return entry, measurements


# ---------------------------------------------------------------------------
# Forest-level measurement
# ---------------------------------------------------------------------------


def _forest_candidate_fn(
    candidate: Candidate, rec, forest, *, depth: int, cache, engines,
    autotune_trees: bool = False, measure_kw: dict | None = None,
):
    """Build the timed callable for one forest candidate as a one-argument
    function of the record batch (warm state outside the timed region:
    per-tree winners resolved — autotuned when ``autotune_trees``, pricing
    the per-tree family at its tuned best — and fused tables packed).
    Taking the batch as an argument keeps the same callable usable for
    :func:`candidate_cost`, where a closed-over batch would constant-fold.

    Returns ``(fn, table_bytes)``: the callable plus the device-resident
    node-table footprint of the candidate's packed layout (None when the
    candidate has no single packed target, i.e. the per-tree family)."""
    if candidate.variant == PER_TREE_FAMILY:
        from repro.tune.dispatch import TunedEvaluator  # local: avoid cycle

        evs = [
            TunedEvaluator(forest.tree(i), cache=cache, engines=engines,
                           autotune=autotune_trees, measure_kw=measure_kw)
            for i in range(forest.n_trees)
        ]
        # Resolve every per-tree winner on the real batch before any jit
        # trace sees the evaluators (resolution itself measures, which must
        # not happen under a tracer).
        for ev in evs:
            ev(rec)
        return (lambda r: jnp.stack([ev(r) for ev in evs])), None
    spec = get_forest_variant(candidate.variant)
    params = candidate.param_dict
    if getattr(spec, "layout", "f32") == "quant":
        # Universal mode (no calibration): bit-exact for every input, so the
        # tuner may hand this layout to dispatch without changing results.
        target = QuantizedForest(
            forest, rec.shape[1],
            thr_dtype=params.get("thr_dtype", "bfloat16"))
    elif spec.family == "fused":
        target = PackedForest(forest, rec.shape[1])
    else:
        target = forest
    tbytes = forest_table_bytes(target) if target is not forest else None
    return (lambda r: spec.fn(r, target, max_depth=depth, **params)), tbytes


def measure_forest_candidate(
    candidate: Candidate,
    records,
    forest,
    *,
    cache: TuneCache | None = None,
    engines: tuple[str, ...] | None = None,
    warmup: int = 2,
    iters: int = 5,
    autotune_trees: bool = False,
) -> Measurement:
    """Median wall time of one forest candidate; a raising candidate is
    logged and returned as a failed measurement.

    Args:
      candidate: a :func:`repro.tune.space.forest_search_space` candidate
        (``Candidate(PER_TREE_FAMILY)`` or a registered forest variant).
      records: (M, A) float32 batch, already bucket-padded by the caller.
      forest: the :class:`repro.core.forest.EncodedForest` under test.
      cache/engines: per-tree resolution inputs for the ``per_tree`` family.
      warmup/iters: :func:`time_callable` discipline.
      autotune_trees: measure the ``per_tree`` family with per-tree
        autotuning (winners measured during warmup, persisted to ``cache``)
        instead of the heuristic — the PR 3 tuned baseline.

    Returns:
      A :class:`Measurement` whose samples bracket device completion.
    """
    depth = max(int(forest.max_depth), 1)
    try:
        fn, table_bytes = _forest_candidate_fn(
            candidate, records, forest, depth=depth, cache=cache, engines=engines,
            autotune_trees=autotune_trees,
            measure_kw={"warmup": warmup, "iters": iters},
        )
        samples = time_callable(lambda: fn(records), warmup=warmup, iters=iters)
    except Exception as exc:
        return _failed("forest", candidate, exc)
    median = _median(samples)
    return Measurement(candidate, median, samples,
                       candidate_cost(fn, records, median_ms=median),
                       table_bytes=table_bytes)


def tune_forest_workload(
    records,
    forest,
    *,
    cache: TuneCache | None = None,
    engines: tuple[str, ...] | None = None,
    families: tuple[str, ...] | None = None,
    layouts: tuple[str, ...] | None = None,
    warmup: int = 2,
    iters: int = 5,
    backend: str | None = None,
    verbose: bool = False,
    autotune_trees: bool = False,
    store: bool = True,
    registry: obs.Registry | None = None,
) -> tuple[TuneEntry, list[Measurement]]:
    """Time every valid forest candidate and record the winning family.

    The forest analogue of :func:`tune_workload`: records are zero-padded to
    the :class:`ForestShape` bucket's M before timing (pricing what dispatch
    will actually run) and every candidate from the three families —
    per-tree variant vector, shared-variant vmap, fused stacked kernel — is
    measured with the same warmup/median discipline.  The winner is stored
    in ``cache`` under the forest bucket key.

    Args:
      records: (M, A) record batch.
      forest: the :class:`repro.core.forest.EncodedForest` to tune for.
      cache: winner store (also consulted by the ``per_tree`` family's
        per-tree resolutions).
      engines/families/layouts: restrict the candidate enumeration
        (``layouts`` defaults to the f32 tables; pass ``("f32", "quant")``
        to let the compact :class:`QuantizedForest` candidates compete).
      warmup/iters/backend/verbose: as in :func:`tune_workload`.
      autotune_trees: give the ``per_tree`` family its tuned best (per-tree
        winners measured and persisted) rather than the heuristic choice.
      store: persist the winner under the forest bucket key.  Callers
        measuring a *restricted* family set pass False — a family-filtered
        winner must not overwrite the bucket's unrestricted one.

    Returns:
      (winning entry, all measurements) — entry.variant is a forest variant
      name or ``"per_tree"``.
    """
    backend = backend or backend_tag()
    rec = jnp.asarray(records, jnp.float32)
    shape = ForestShape.of(rec, forest)
    rec = bucket_pad_records(rec, shape.bucket().m)

    measurements = [
        measure_forest_candidate(
            c, rec, forest, cache=cache, engines=engines, warmup=warmup, iters=iters,
            autotune_trees=autotune_trees,
        )
        for c in forest_search_space(
            shape, engines=engines, families=families, layouts=layouts)
    ]
    _note_measurements(registry, "forest", measurements)
    ok = [m for m in measurements if not m.failed]
    if not ok:
        raise RuntimeError(f"no forest candidate succeeded for shape {shape}")
    best = min(ok, key=lambda m: m.median_ms)
    if verbose:
        for m in sorted(ok, key=lambda m: m.median_ms):
            print(f"  {m.median_ms:10.3f} ms  {m.candidate.variant} {m.candidate.param_dict}")
    entry = TuneEntry(
        variant=best.candidate.variant,
        params=best.candidate.param_dict,
        median_ms=best.median_ms,
        shape=dataclasses.asdict(shape),
        backend=backend,
    )
    if cache is not None and store:
        cache.store(shape.key(backend), entry)
    return entry, measurements


# ---------------------------------------------------------------------------
# Class-level (majority vs cascade) measurement
# ---------------------------------------------------------------------------


def measure_cascade_candidate(
    candidate: Candidate,
    records,
    forest,
    n_classes: int,
    *,
    cache: TuneCache | None = None,
    engines: tuple[str, ...] | None = None,
    warmup: int = 2,
    iters: int = 5,
) -> Measurement:
    """Median wall time of one class-level candidate.

    ``Candidate(MAJORITY_FAMILY)`` prices the full path — the forest-level
    winner followed by ``majority_vote`` — through a warm
    :class:`repro.tune.dispatch.ForestTunedEvaluator`; cascade candidates
    price a warm :class:`CascadeEvaluator` built at bound 1.0 (the only
    bound the tuner may enumerate: every timed candidate must be exact so
    the class-level choice never changes results).  Cascade timings include
    the host-side compaction loop — that *is* the candidate's cost.
    """
    import numpy as np

    from repro.core.forest import majority_vote

    try:
        if candidate.variant == MAJORITY_FAMILY:
            from repro.tune.dispatch import ForestTunedEvaluator  # local: avoid cycle

            fte = ForestTunedEvaluator(forest, cache=cache, engines=engines)
            run = lambda: majority_vote(fte(records), n_classes)  # noqa: E731
        else:
            spec = get_cascade_variant(candidate.variant)
            params = candidate.param_dict
            ev = spec.build(
                forest,
                n_classes=n_classes,
                stages=int(params.get("stages", 2)),
                bound=1.0,
                block_m=params.get("block_m"),
                calibration=records,
            )
            rec_np = np.asarray(records, np.float32)
            run = lambda: ev(rec_np).classes  # noqa: E731
        samples = time_callable(run, warmup=warmup, iters=iters)
    except Exception as exc:
        return _failed("classes", candidate, exc)
    return Measurement(candidate, _median(samples), samples)


def tune_cascade_workload(
    records,
    forest,
    n_classes: int,
    *,
    cache: TuneCache | None = None,
    engines: tuple[str, ...] | None = None,
    warmup: int = 2,
    iters: int = 5,
    backend: str | None = None,
    verbose: bool = False,
    store: bool = True,
    registry: obs.Registry | None = None,
) -> tuple[TuneEntry, list[Measurement]]:
    """Time every class-level candidate and record the winner.

    The class-level analogue of :func:`tune_forest_workload`: the full
    majority-vote path competes against every registered cascade variant
    crossed with the stage grid (see
    :func:`repro.tune.space.cascade_search_space`).  Early-exit fractions —
    and therefore cascade timings — depend on the *actual* record mix, so
    candidates are timed on the un-bucketed batch and the winner is stored
    under the bucketed :meth:`ForestShape.classes_key`.
    """
    backend = backend or backend_tag()
    rec = jnp.asarray(records, jnp.float32)
    shape = ForestShape.of(rec, forest)

    measurements = [
        measure_cascade_candidate(
            c, rec, forest, n_classes,
            cache=cache, engines=engines, warmup=warmup, iters=iters,
        )
        for c in cascade_search_space(shape, n_classes, engines=engines)
    ]
    _note_measurements(registry, "classes", measurements)
    ok = [m for m in measurements if not m.failed]
    if not ok:
        raise RuntimeError(f"no class-level candidate succeeded for shape {shape}")
    best = min(ok, key=lambda m: m.median_ms)
    if verbose:
        for m in sorted(ok, key=lambda m: m.median_ms):
            print(f"  {m.median_ms:10.3f} ms  {m.candidate.variant} {m.candidate.param_dict}")
    entry = TuneEntry(
        variant=best.candidate.variant,
        params=best.candidate.param_dict,
        median_ms=best.median_ms,
        shape=dataclasses.asdict(shape),
        backend=backend,
    )
    if cache is not None and store:
        cache.store(shape.classes_key(n_classes, backend), entry)
    return entry, measurements
