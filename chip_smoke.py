#!/usr/bin/env python3
"""Chip smoke: drive the tree and forest serving paths once on a TPU.

    python chip_smoke.py             # one chip: tree, forest and anytime phases
    python chip_smoke.py --chips 4   # four chips: the sharded forest path only

One process drives every chip.  Each phase serves requests of 65,536
records (the paper's 256×256 image) through the engines a user calls
(``TreeServeEngine``, ``ForestServeEngine``) and checks:

* classes bit-identical to the host reference (``eval_serial``, Procedure
  2, and its vectorised form over each tree of a forest);
* the resolved kernel is a Pallas one, and the compiled wave program holds
  a ``tpu_custom_call``;
* no tuner candidate and no shadow-profile pass raised.

The ``--chips 4`` path serves the forest on a four-chip ``forest_mesh``
under the planner's plan and the pinned (4,1), (1,4) and (2,2)
decompositions, checks each class-exactly against the same forest on one
chip, and checks that every device holds shards.

Times printed here are smoke numbers (one process, first calls included),
not benchmark numbers.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU,
or without the repo's ``src/`` next to this file, the script exits non-zero
and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
RECORDS = 65_536           # one 256×256 image of the paper's workload
FOREST_TREES = 64
FOREST_DEPTH = 8           # perfect trees: 511 nodes, padded to N = 512
N_ATTRS = 19
N_CLASSES = 7


class SmokeFailure(AssertionError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# workloads and references (host numpy, independent of the device)
# ---------------------------------------------------------------------------


def segmentation_tree():
    """The paper's classifier: CART on the synthetic segmentation set, as
    ``examples/segmentation_service.py`` trains it."""
    from repro.core import CartConfig, breadth_first_encode, train_cart
    from repro.data.segmentation import make_segmentation

    data = make_segmentation(seed=0)
    root = train_cart(data.x_train, data.y_train, N_CLASSES,
                      CartConfig(max_depth=12, min_samples_split=8, min_gain=4e-3))
    return data, breadth_first_encode(root)


def images(data, n: int, n_records: int) -> list:
    from repro.data.segmentation import replicated_dataset

    return [replicated_dataset(data, n_records, seed=i + 1)[0] for i in range(n)]


def seeded_forest(n_trees: int = FOREST_TREES, max_depth: int = FOREST_DEPTH):
    from repro.core import EncodedForest, breadth_first_encode, random_tree

    return EncodedForest([
        breadth_first_encode(random_tree(n_attrs=N_ATTRS, n_classes=N_CLASSES,
                                         max_depth=max_depth, seed=i))
        for i in range(n_trees)
    ])


def forest_reference(forest, records):
    """Majority-vote classes from per-tree host descents (ties → lowest
    class, as ``majority_vote``'s argmax)."""
    import numpy as np

    from repro.core import eval_serial, eval_serial_vectorized_host

    depth = max(int(forest.max_depth), 1)
    per_tree = np.stack([eval_serial_vectorized_host(forest.tree(t), records, depth)
                         for t in range(forest.n_trees)])
    # the vectorised descent is Procedure 2 itself on a slice of every tree
    head = records[:256]
    for t in range(forest.n_trees):
        _check(np.array_equal(per_tree[t, :256], eval_serial(forest.tree(t), head)),
               f"vectorised host descent != eval_serial on tree {t}")
    votes = np.zeros((records.shape[0], N_CLASSES), np.int64)
    rows = np.arange(records.shape[0])
    for t in range(forest.n_trees):
        np.add.at(votes, (rows, per_tree[t]), 1)
    return votes.argmax(axis=1).astype(np.int32)


# ---------------------------------------------------------------------------
# checks shared by the phases
# ---------------------------------------------------------------------------


def counters(registry, prefix: str) -> float:
    from repro import obs

    return sum(v for k, v in obs.snapshot(registry)["counters"].items()
               if k.startswith(prefix))


def check_health(phase: str, engine) -> dict:
    """No tuner candidate, shadow-profile pass or re-tune raised."""
    from repro import obs

    if engine.profiler is not None:
        engine.profiler.drain(timeout=600)
    if engine.retuner is not None:
        engine.retuner.drain(timeout=600)
    out = {
        "tune_failures": counters(engine.obs, "tune.failed_candidates")
        + counters(obs.default_registry(), "tune.failed_candidates"),
        "profiler_errors": counters(engine.obs, "prof.errors"),
        "retune_failures": counters(engine.obs, "serve.retune.failed"),
    }
    _say(phase, "health " + json.dumps(out))
    for k, v in out.items():
        _check(v == 0, f"{phase}: {k} = {v}")
    return out


def check_program(phase: str, lowered, *, compiled_kernels: bool) -> float:
    """Compile the wave program; it must hold a Pallas TPU kernel."""
    t0 = time.perf_counter()
    text = lowered.compile().as_text()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in text
    _say(phase, f"wave program compile {compile_s:.3f} s, tpu_custom_call={has_kernel}")
    if compiled_kernels:
        _check(has_kernel, f"{phase}: no tpu_custom_call in the compiled wave program")
    return compile_s


def serve(phase: str, engine, batches, make_request) -> tuple[list, list]:
    """One request per wave; returns (requests, wave latencies in ms)."""
    reqs, lat = [], []
    for i, b in enumerate(batches):
        r = make_request(uid=i, records=b)
        t0 = time.perf_counter()
        engine.run([r])
        lat.append((time.perf_counter() - t0) * 1e3)
        reqs.append(r)
    _say(phase, "smoke wave latencies, first includes compile (not a benchmark) ms: "
         + ", ".join(f"{x:.3f}" for x in lat))
    return reqs, lat


def _tune_cache(tmp: str):
    from repro.tune import TuneCache

    # a fresh cache per phase: every bucket resolves cold, as on a new host
    return TuneCache(os.path.join(tmp, "tune.json"))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def tree_phase(*, n_records: int = RECORDS, n_requests: int = 3, engines=None,
               compiled_kernels: bool = True) -> dict:
    """CART tree served by TreeServeEngine; classes vs eval_serial."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import eval_serial
    from repro.kernels.tree_eval.ops import get_variant
    from repro.serve import TreeRequest, TreeServeEngine

    phase = "tree"
    data, enc = segmentation_tree()
    batches = images(data, n_requests, n_records)
    with tempfile.TemporaryDirectory() as tmp:
        eng = TreeServeEngine(enc, max_batch=n_records, cache=_tune_cache(tmp),
                              engines=engines)
        reqs, lat = serve(phase, eng, batches, TreeRequest)
        for r in reqs:
            _check(np.array_equal(r.out, eval_serial(enc, r.records)),
                   f"{phase}: request {r.uid} classes differ from eval_serial")
        _say(phase, f"classes bit-identical to eval_serial on {len(reqs)} x {n_records} records")
        cand, _ = eng._eval.resolve(batches[0])
        spec = get_variant(cand.variant)
        _say(phase, f"tree N={enc.n_nodes} depth={eng._eval.depth}; resolved "
             f"{cand.variant} {cand.param_dict} (engine {spec.engine})")
        if compiled_kernels:
            _check(spec.engine == "pallas", f"{phase}: resolved a {spec.engine} kernel")
        compile_s = check_program(
            phase, jax.jit(lambda r: eng._eval(r)).lower(jnp.asarray(batches[0])),
            compiled_kernels=compiled_kernels)
        health = check_health(phase, eng)
    return {"phase": phase, "candidate": cand.variant, "compile_s": compile_s,
            "wave_ms": lat, **health}


def _forest_engine_candidates(eng) -> list:
    """(variant, engine) of every forest bucket the engine resolved."""
    from repro.kernels.tree_eval.ops import FOREST_VARIANTS, PER_TREE_FAMILY, get_variant

    fev = eng._eval._forest_evaluator()
    out = []
    for cand, _src in fev._resolved.values():
        if cand.variant == PER_TREE_FAMILY:
            for ev in fev._tree_evaluators():
                out += [(c.variant, get_variant(c.variant).engine)
                        for c, _ in ev._resolved.values()]
        else:
            out.append((cand.variant, FOREST_VARIANTS[cand.variant].engine))
    return out


def forest_phase(*, n_records: int = RECORDS, n_waves: int = 2, n_trees: int = FOREST_TREES,
                 max_depth: int = FOREST_DEPTH, engines=None,
                 compiled_kernels: bool = True) -> dict:
    """Seeded forest served by ForestServeEngine; majority classes vs host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import ForestServeEngine, TreeRequest

    phase = "forest"
    data, _ = segmentation_tree()
    forest = seeded_forest(n_trees, max_depth)
    batches = images(data, n_waves, n_records)
    with tempfile.TemporaryDirectory() as tmp:
        eng = ForestServeEngine(forest, n_classes=N_CLASSES, max_batch=n_records,
                                cache=_tune_cache(tmp), engines=engines)
        reqs, lat = serve(phase, eng, batches, TreeRequest)
        for r in reqs:
            _check(np.array_equal(r.out, forest_reference(forest, r.records)),
                   f"{phase}: wave {r.uid} classes differ from the host reference")
        _say(phase, f"classes bit-identical to the host reference on {len(reqs)} x "
             f"{n_records} records (T={forest.n_trees}, N={forest.n_nodes})")
        cands = _forest_engine_candidates(eng)
        _say(phase, f"plan {eng.plan.record_shards}x{eng.plan.tree_shards}; resolved "
             + ", ".join(f"{v} ({e})" for v, e in sorted(set(cands))))
        _check(bool(cands), f"{phase}: nothing resolved")
        if compiled_kernels:
            _check(all(e == "pallas" for _, e in cands), f"{phase}: non-Pallas kernel resolved")
        fev = eng._eval._forest_evaluator()
        m, _a = next(k for k in fev._fast if len(k) == 2)
        compile_s = check_program(
            phase, jax.jit(lambda r: fev(r)).lower(jnp.asarray(batches[0][:m])),
            compiled_kernels=compiled_kernels)
        health = check_health(phase, eng)
    return {"phase": phase, "candidates": sorted({v for v, _ in cands}),
            "compile_s": compile_s, "wave_ms": lat, **health}


def anytime_phase(*, n_records: int = RECORDS, n_trees: int = FOREST_TREES,
                  max_depth: int = FOREST_DEPTH, engines=None,
                  compiled_kernels: bool = True) -> dict:
    """One wave through the anytime cascade (fused vote kernels)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import AnytimePolicy, ForestServeEngine, TreeRequest

    phase = "anytime"
    data, _ = segmentation_tree()
    forest = seeded_forest(n_trees, max_depth)
    batches = images(data, 1, n_records)
    with tempfile.TemporaryDirectory() as tmp:
        # an SLO no stage can miss: every answer is final, so exact
        eng = ForestServeEngine(forest, n_classes=N_CLASSES, max_batch=n_records,
                                cache=_tune_cache(tmp), engines=engines,
                                anytime=AnytimePolicy(slo_ms=1e9))
        reqs, lat = serve(phase, eng, batches, TreeRequest)
        _check(eng.stats.anytime_truncations == 0, f"{phase}: the SLO truncated a wave")
        for r in reqs:
            _check(np.array_equal(r.out, forest_reference(forest, r.records)),
                   f"{phase}: wave {r.uid} classes differ from the host reference")
        cascade = eng._cascade
        _say(phase, f"classes bit-identical to the host reference; {cascade.plan.n_stages} "
             f"stages of sizes {cascade.plan.stage_sizes}; stage kernels "
             f"{cascade.engine}/{cascade.algorithm}/{cascade.jump_mode}")
        if compiled_kernels:
            _check(cascade.engine == "pallas", f"{phase}: cascade engine {cascade.engine}")
        # stage 0 sees the whole wave: its vote program at the wave's shape
        stage0 = jax.jit(cascade._stages[0]).lower(
            jax.ShapeDtypeStruct((n_records, N_ATTRS), jnp.float32))
        compile_s = check_program(phase, stage0, compiled_kernels=compiled_kernels)
        health = check_health(phase, eng)
    return {"phase": phase, "candidate": f"cascade/{cascade.engine}/{cascade.algorithm}",
            "compile_s": compile_s, "wave_ms": lat, **health}


def mesh_phase(*, n_records: int = RECORDS, n_waves: int = 2, n_trees: int = FOREST_TREES,
               max_depth: int = FOREST_DEPTH, n_devices: int = 4) -> dict:
    """The forest on a four-chip mesh, each decomposition vs one chip."""
    import jax
    import numpy as np

    from repro.parallel.sharding import forest_mesh
    from repro.serve import ForestServeEngine, TreeRequest

    phase = "mesh"
    devices = jax.devices()
    _check(len(devices) >= n_devices, f"{phase}: {len(devices)} devices, need {n_devices}")
    data, _ = segmentation_tree()
    forest = seeded_forest(n_trees, max_depth)
    batches = images(data, n_waves, n_records)

    def run(label, **kw):
        with tempfile.TemporaryDirectory() as tmp:
            eng = ForestServeEngine(forest, n_classes=N_CLASSES, max_batch=n_records,
                                    cache=_tune_cache(tmp), **kw)
            reqs, lat = serve(f"{phase}:{label}", eng, batches, TreeRequest)
            check_health(f"{phase}:{label}", eng)
        return eng, [r.out for r in reqs], lat

    _one, want, _ = run("1 chip", mesh=forest_mesh(1, 1, devices[:1]))
    for b, w in zip(batches, want):
        _check(np.array_equal(w, forest_reference(forest, b)),
               f"{phase}: one-chip classes differ from the host reference")
    out = {"phase": phase, "runs": []}
    pinned = [(4, 1), (1, 4), (2, 2)]
    for label, kw in [("planner", {})] + [
        (f"{r}x{g}", {"mesh": forest_mesh(r, g, devices[:n_devices])}) for r, g in pinned
    ]:
        eng, got, lat = run(label, **kw)
        plan = eng.plan
        for i, (g, w) in enumerate(zip(got, want)):
            _check(np.array_equal(g, w), f"{phase}:{label}: wave {i} differs from one chip")
        used: set = set()
        for fn_args in eng._eval._fast.values():
            for x in fn_args[3]:
                used |= {s.device for s in x.addressable_shards}
        resolved = eng._eval.resolved
        _say(f"{phase}:{label}", f"plan {plan.record_shards}x{plan.tree_shards} "
             f"({plan.decomposition}); classes equal one chip; tables on "
             f"{len(used)} devices; shard kernel "
             f"{resolved[0].variant if resolved else 'single-device path'}")
        if label != "planner":
            _check(used == set(devices[:n_devices]),
                   f"{phase}:{label}: shards on {len(used)} of {n_devices} devices")
        out["runs"].append({"label": label, "plan": [plan.record_shards, plan.tree_shards],
                            "devices_used": len(used), "wave_ms": lat})
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _import_repro() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "serve", "engine.py")):
        raise SystemExit(f"chip_smoke: the repo's src/ is not next to {__file__}")
    if src not in sys.path:
        sys.path.insert(0, src)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded forest path")
    args = ap.parse_args(argv)
    _import_repro()

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache(ROOT)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing is run on the CPU", file=sys.stderr)
        return 1
    print(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"compile cache {cache_dir}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            results = [mesh_phase()]
        else:
            results = [tree_phase(), forest_phase(), anytime_phase()]
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s: "
          + json.dumps(results), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
