"""Multi-device executor: lower a ShardPlan over a (records × trees) mesh.

Lowering maps the planner's symbols onto ``shard_map``:

  R = plan.record_shards → mesh axis ``"records"``: each device column holds
      M/R records — Procedure 3's ``D[m·p .. m(p+1))`` slicing at mesh level.
  G = plan.tree_shards   → mesh axis ``"trees"``: each device row holds T/G
      stacked tree encodings (the forest analogue of the paper's replicated
      constant-memory tree).
  per-shard kernel       → resolved through ``repro.tune`` at the *shard*
      operating point, forest-first: the ForestShape bucket (M/R records ×
      T/G trees) is consulted for a stored shared-family winner, falling
      back to the per-tree chain (:class:`repro.tune.TunedEvaluator`) at
      the shard record shape — the autotuner stays the single selection
      point; the winning candidate's (algorithm, jump mode, jump count)
      lowers via its array-level formulation
      (:func:`repro.core.eval_speculative.eval_speculative` /
      :func:`repro.core.eval_dataparallel.eval_data_parallel`) inside the
      shard body, vmapped over the local tree axis.

Padding follows the divisibility policy of :mod:`repro.parallel.sharding`:
records pad to a multiple of R with zero rows (sliced off the output), trees
pad to a multiple of G by repeating tree 0 (rows discarded) — both are the
§3.2 phantom-node trick applied to the mesh axes.  All variants are exact,
so any plan returns results bit-identical to ``eval_forest_tuned``; on a
single device the executor *is* ``eval_forest_tuned`` (no ``shard_map`` in
the path at all).
"""

from __future__ import annotations

import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.eval_dataparallel import eval_data_parallel
from repro.core.eval_speculative import eval_speculative
from repro.core.forest import EncodedForest
from repro.dist.plan import ForestWorkload, MeshCostModel, ShardPlan, make_plan, plan_forest
from repro.kernels.tree_eval.ops import get_variant
from repro.parallel import sharding as shd
from repro.parallel.sharding import SHARD_MAP_KW as _SMAP_KW
from repro.parallel.sharding import shard_map as _shard_map


class DistStats:
    """Executor accounting on a :class:`repro.obs.Registry`.

    ``resolve_source`` stays a plain last-write attribute (tests assert on
    the latest provenance); each resolution also lands in the labelled
    ``dist.resolutions{source=...}`` counter so a snapshot shows the full
    cache-hit/heuristic mix, not just the most recent outcome.
    """

    def __init__(self, registry: obs.Registry | None = None):
        self.registry = registry if registry is not None else obs.Registry()
        r = self.registry
        self.m_calls = r.counter("dist.calls", "executor dispatches")
        self.m_records = r.counter("dist.records", "records dispatched")
        self.m_resolutions = r.counter(
            "dist.resolutions", "shard-kernel resolutions by tune provenance",
            ("source",))
        self.resolve_source = ""    # where the shard kernel came from (tune provenance)

    def note_resolution(self, source: str) -> None:
        self.resolve_source = source
        self.m_resolutions.labels(source=source).inc()

    @property
    def calls(self) -> int:
        return int(self.m_calls.value)

    @property
    def records(self) -> int:
        return int(self.m_records.value)


class ShardedForestEvaluator:
    """Reusable sharded dispatcher for one encoded forest.

    Planning is lazy: the first batch supplies M and a d_µ sample, the
    planner picks (R, G) (unless ``plan``/``mesh``/``decomposition`` pins
    it), and subsequent equal-shaped calls replay one jitted ``shard_map``
    program.  ``__call__`` never blocks on the device — callers (stream
    chunker, serve engine, benches) own synchronisation, which is what lets
    transfer overlap evaluation.
    """

    def __init__(
        self,
        forest: "EncodedForest | list",
        *,
        mesh=None,
        plan: ShardPlan | None = None,
        decomposition: str | None = None,
        n_devices: int | None = None,
        mesh_cost: MeshCostModel | None = None,
        cache=None,
        autotune: bool = False,
        engines: tuple[str, ...] | None = None,
        layouts: tuple[str, ...] | None = None,
        registry: obs.Registry | None = None,
        tracer: obs.Tracer | None = None,
        profiler=None,
    ):
        from repro.tune import TuneCache

        self.forest = forest if isinstance(forest, EncodedForest) else EncodedForest(list(forest))
        self.cache = cache if cache is not None else TuneCache()  # one handle, one disk read
        self.autotune = autotune
        self.engines = engines
        # node-table layout opt-in, forwarded to the single-device
        # ForestTunedEvaluator path (shard bodies stay on the f32 tables)
        self.layouts = layouts
        self.obs = registry if registry is not None else obs.Registry()
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        # a TraversalProfiler (serve engine's): measured per-bucket d_µ /
        # survival flow into the forest evaluator's heuristic resolutions
        self.profiler = profiler
        self.mesh_cost = mesh_cost if mesh_cost is not None else MeshCostModel()
        self.decomposition = decomposition
        self._given_mesh = mesh
        self._given_plan = plan
        self._n_devices = n_devices
        self.plan: ShardPlan | None = None
        self.mesh = None
        self.record_sharding = None   # set once planned; exposed for callers
        self.resolved = None          # (Candidate, source) provenance
        self.stats = DistStats(self.obs)
        self._fast: dict[int, tuple] = {}   # M → (fn, m_pad, t_pad, tree_args)
        self._forest_ev = None        # lazy ForestTunedEvaluator (single selection point)
        # swap generation: a _build() racing invalidate_resolution() must not
        # re-install its pre-promotion kernel (same guard as the evaluators)
        self._swap_lock = threading.Lock()
        self._gen = 0

    # -- planning -----------------------------------------------------------

    def _measured_d_mu(self, rec: np.ndarray, sample: int = 128) -> float:
        """Forest d_µ: measured mean over a few trees × a record sample
        (delegates to the shared helper so the planner and the forest
        heuristic read the same measurement)."""
        from repro.tune.heuristic import measured_forest_d_mu

        return measured_forest_d_mu(self.forest, rec, sample=sample)

    def _prepare(self, rec) -> None:
        if self.plan is not None:
            return
        if self._given_plan is not None:
            self.plan = self._given_plan
        elif self._given_mesh is not None:
            sizes = dict(zip(self._given_mesh.axis_names, self._given_mesh.devices.shape))
            wl = ForestWorkload.of(self.forest, rec)
            self.plan = make_plan(
                wl, sizes.get("records", 1), sizes.get("trees", 1), self.mesh_cost
            )
        else:
            host = np.asarray(rec)
            wl = ForestWorkload.of(self.forest, host, d_mu=self._measured_d_mu(host))
            self.plan = plan_forest(
                wl,
                n_devices=self._n_devices,
                mesh_cost=self.mesh_cost,
                decomposition=self.decomposition,
            )
        if self.plan.n_devices > 1:
            self.mesh = self._given_mesh if self._given_mesh is not None else shd.forest_mesh(
                self.plan.record_shards, self.plan.tree_shards
            )
            self.record_sharding = shd.named(self.mesh, P("records", None))

    # -- lowering -----------------------------------------------------------

    def _forest_evaluator(self):
        """The lazily built :class:`repro.tune.ForestTunedEvaluator`.

        One evaluator serves both roles: the whole single-device path (the
        plain tuned forest call, all three candidate families available)
        and, on a mesh, the depth-profile metadata the per-shard resolution
        keys its forest buckets with.
        """
        if self._forest_ev is None:
            from repro.tune import ForestTunedEvaluator

            self._forest_ev = ForestTunedEvaluator(
                self.forest,
                cache=self.cache,
                autotune=self.autotune,
                engines=self.engines,
                layouts=self.layouts,
                registry=self.obs,
                tracer=self.tracer,
                profiler=self.profiler,
            )
        return self._forest_ev

    def invalidate_resolution(self) -> None:
        """Drop kernel-resolution state; the next call re-reads the tune cache.

        The serve engines' background re-tune promotes a freshly measured
        winner by writing it to the shared cache and calling this — an
        atomic swap from the caller's view (in-flight calls finish on the
        old kernel, subsequent calls resolve the new one).  The (R, G) plan
        is kept: re-planning is a separate concern (see ROADMAP).
        """
        with self._swap_lock:
            self._gen += 1
            self._fast.clear()
        if self._forest_ev is not None:
            self._forest_ev.invalidate()

    def retune(self, records, *, warmup: int = 1, iters: int = 3):
        """Re-measure the kernel choice at this executor's operating point.

        The measurement must land under the key the next resolution will
        actually probe, which depends on the plan:

        * one device — the full forest-family sweep at the batch shape; the
          winner lands under the forest bucket key the
          :class:`~repro.tune.ForestTunedEvaluator` resolves;
        * a mesh — the shared (vmap) candidates are timed at the *shard*
          operating point (M/R records × T/G trees, the shapes the shard
          bodies really run) and the winner is stored under the exact
          shard-shape key :meth:`_shard_kernel` looks up on its next build.

        Called from the serve engines' background re-tune worker; follow
        with :meth:`invalidate_resolution` to promote the stored winner.

        Returns:
          The winning :class:`repro.tune.TuneEntry`.
        """
        from repro.tune.measure import tune_forest_workload
        from repro.tune.space import ForestShape

        rec = np.asarray(records, np.float32)
        self._prepare(jnp.asarray(rec))
        if self.plan.n_devices == 1:
            entry, _ = tune_forest_workload(
                rec, self.forest, cache=self.cache, engines=self.engines,
                warmup=warmup, iters=iters, autotune_trees=True,
            )
            return entry

        plan, forest = self.plan, self.forest
        m_pad = shd.pad_to_multiple(max(rec.shape[0], plan.record_shards), plan.record_shards)
        m_shard = m_pad // plan.record_shards
        t_shard = shd.pad_to_multiple(forest.n_trees, plan.tree_shards) // plan.tree_shards
        sample = np.zeros((m_shard, rec.shape[1]), np.float32)
        rows = min(rec.shape[0], m_shard)
        sample[:rows] = rec[:rows]
        # forest.tree(i) returns the already common-padded encoding, so the
        # sub-forest keeps the full forest's node count
        sub = EncodedForest([forest.tree(i % forest.n_trees) for i in range(t_shard)])
        entry, _ = tune_forest_workload(
            sample, sub, cache=None, engines=self.engines, families=("vmap",),
            warmup=warmup, iters=iters, store=False,
        )
        fev = self._forest_evaluator()
        fshape = ForestShape(
            t=t_shard, m=m_shard, n_nodes=int(forest.n_nodes), n_attrs=int(rec.shape[1]),
            depth_min=fev.depth_min, depth_max=fev.depth_max,
        )
        self.cache.store(fshape.key(), entry)
        return entry

    def _shard_kernel(self, m_shard: int, t_shard: int, n_attrs: int, rec_host: np.ndarray):
        """Resolve the per-shard kernel through repro.tune; return array fn.

        Resolution is forest-first: a :class:`repro.tune.space.ForestShape`
        bucket at the shard operating point (M/R records × T/G trees) is
        looked up in the shared cache, and a stored shared-family winner
        (vmap/fused) supplies the algorithm, jump mode and jump count.  On a
        miss — or a ``per_tree`` winner, which has no single-kern lowering
        inside a ``shard_map`` body — resolution falls back to the per-tree
        chain at the shard record shape (memo → cache → autotune →
        heuristic), exactly the PR 3 behaviour.  Either way the winning
        candidate lowers via its algorithm's array-level formulation
        (:func:`repro.core.eval_speculative.eval_speculative` /
        :func:`repro.core.eval_dataparallel.eval_data_parallel`) — the
        kernel launch itself is per-device work that ``shard_map`` bodies
        express as plain traced ops — vmapped over the local tree axis.
        """
        from repro.kernels.tree_eval.ops import FOREST_VARIANTS, get_forest_variant
        from repro.tune import TunedEvaluator
        from repro.tune.space import Candidate, ForestShape, backend_tag

        depth = max(int(self.forest.max_depth), 1)
        fev = self._forest_evaluator()
        fshape = ForestShape(
            t=t_shard, m=m_shard, n_nodes=int(self.forest.n_nodes), n_attrs=n_attrs,
            depth_min=fev.depth_min, depth_max=fev.depth_max,
        )
        entry = self.cache.lookup(fshape.key(backend_tag()))
        if entry is not None and entry.variant in FOREST_VARIANTS:
            spec = get_forest_variant(entry.variant)
            cand = Candidate.make(entry.variant, **entry.params)
            self.resolved = (cand, "cache")
            self.stats.note_resolution("cache")
            if spec.algorithm == "data_parallel":
                return partial(eval_data_parallel, max_depth=depth)
            return partial(
                eval_speculative,
                max_depth=depth,
                jumps_per_round=int(entry.params.get("jumps_per_round", 2)),
                use_onehot_matmul=(spec.jump_mode == "onehot"),
            )

        sample = np.zeros((m_shard, n_attrs), np.float32)
        rows = min(rec_host.shape[0], m_shard)
        sample[:rows] = rec_host[:rows]
        ev = TunedEvaluator(
            self.forest.tree(0),
            cache=self.cache,
            autotune=self.autotune,
            engines=self.engines,
        )
        ev.depth = depth
        cand, source = ev.resolve(sample)
        self.resolved = (cand, source)
        self.stats.note_resolution(source)

        spec = get_variant(cand.variant)
        params = cand.param_dict
        if spec.algorithm == "data_parallel":
            return partial(eval_data_parallel, max_depth=depth)
        return partial(
            eval_speculative,
            max_depth=depth,
            jumps_per_round=int(params.get("jumps_per_round", 2)),
            use_onehot_matmul=(spec.jump_mode == "onehot"),
        )

    def _build(self, m: int, n_attrs: int, rec_host: np.ndarray) -> tuple:
        plan, mesh, forest = self.plan, self.mesh, self.forest
        m_pad = shd.pad_to_multiple(max(m, plan.record_shards), plan.record_shards)
        t_pad = shd.pad_to_multiple(forest.n_trees, plan.tree_shards)

        def pad_t(x, dtype):
            x = np.asarray(x)
            if t_pad > x.shape[0]:
                x = np.concatenate([x, np.repeat(x[:1], t_pad - x.shape[0], axis=0)])
            return jax.device_put(
                jnp.asarray(x, dtype), shd.named(mesh, P("trees", None))
            )

        tree_args = (
            pad_t(forest.attr_idx, jnp.int32),
            pad_t(forest.threshold, jnp.float32),
            pad_t(forest.child, jnp.int32),
            pad_t(forest.class_val, jnp.int32),
        )
        kern = self._shard_kernel(
            m_pad // plan.record_shards, t_pad // plan.tree_shards, n_attrs, rec_host
        )

        def body(r, ai, ti, ci, ki):
            # r: (M/R, A) local records; tree tables: (T/G, N) local stack
            return jax.vmap(lambda a_, t_, c_, k_: kern(r, a_, t_, c_, k_))(ai, ti, ci, ki)

        smap = _shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P("records", None),
                P("trees", None),
                P("trees", None),
                P("trees", None),
                P("trees", None),
            ),
            out_specs=P("trees", "records"),
            **_SMAP_KW,
        )
        n_trees = forest.n_trees

        def run(r, ai, ti, ci, ki):
            # Divisibility pad, shard_map and the output slice are traced
            # into ONE program: a streamed chunk costs a single dispatch, not
            # a pad program + an eval program + a slice program.  That fixed
            # per-chunk overhead is what made chunked streaming lose to the
            # monolithic call on transfer-free backends.
            if m_pad != m:
                r = jnp.zeros((m_pad, r.shape[1]), r.dtype).at[:m].set(r)
            return smap(r, ai, ti, ci, ki)[:n_trees, :m]

        # Donate the records buffer where donation is real (XLA CPU ignores
        # it with a warning): streamed chunks are single-use by contract, so
        # their pages can be recycled for the padded copy / the output.
        donate = (0,) if jax.default_backend() != "cpu" else ()
        fn = jax.jit(run, donate_argnums=donate)
        return fn, m_pad, t_pad, tree_args

    # -- evaluation ---------------------------------------------------------

    def __call__(self, records) -> jax.Array:
        """Evaluate the forest over a record batch across the mesh.

        Args:
          records: (M, A) float array (converted to float32 on device).

        Returns:
          (T, M) int32 per-tree class assignments — *asynchronously*: the
          result is not blocked on the device, so callers (stream chunker,
          serve engines, benches) own synchronisation, which is what lets
          chunk transfer overlap evaluation.

        On non-CPU backends the device records buffer is donated to the
        evaluation (chunks are single-use by the streaming contract); pass a
        fresh array — or host data, converted here — per call.
        """
        self._prepare(records)
        m = records.shape[0]
        self.stats.m_calls.inc()
        self.stats.m_records.inc(int(m))

        if self.plan.n_devices == 1:
            # single-device fallback: the plain forest-tuned path, no
            # shard_map.  The ForestTunedEvaluator is built once — its
            # internal memo makes steady-state calls (serve waves, stream
            # chunks) pure dict probes, and the fused stacked-kernel
            # candidate stays in play, same as eval_forest_tuned.  It
            # copies host records to the device itself (``tune.h2d``).
            with self.tracer.span("kernel.dispatch", cat="kernel",
                                  records=int(m), devices=1):
                return self._forest_evaluator()(records)

        if not (isinstance(records, jax.Array) and records.dtype == jnp.float32):
            records = jnp.asarray(records, jnp.float32)

        fast = self._fast.get(m)
        if fast is None:
            gen = self._gen
            with self.tracer.span("dist.build", cat="dist", records=int(m),
                                  devices=self.plan.n_devices):
                fast = self._build(m, int(records.shape[1]), np.asarray(records))
            with self._swap_lock:
                if gen == self._gen:   # don't cache a pre-swap resolution
                    self._fast[m] = fast
        fn, _m_pad, _t_pad, tree_args = fast
        # fn pads, reshards, evaluates and slices in one program — one
        # asynchronous dispatch per call, whatever sharding the input has
        with self.tracer.span("kernel.dispatch", cat="kernel", records=int(m),
                              devices=self.plan.n_devices):
            return fn(records, *tree_args)   # (n_trees, m)
