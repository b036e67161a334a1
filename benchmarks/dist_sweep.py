"""Sharded-forest decomposition sweep: plan-predicted vs measured crossover.

For each workload × mesh shape this bench pins the (records × trees)
factorization, runs the ``repro.dist`` executor, and records the planner's
predicted cost (model units — rank-valid, not milliseconds) next to the
measured median.  The interesting question is the *crossover*: does the
decomposition the §3.6-extended model ranks first actually win on the
forced-8-host-device mesh?  The JSON records both winners per workload so
the agreement is diffable across PRs.

A streaming entry per workload times the chunked (double-buffered) path on
the planner's chosen plan against the monolithic call.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.dist_sweep

Run with ``JAX_PLATFORMS=cpu`` and without the flag, it re-execs itself in
a subprocess with 8 forced host devices (jax locks the device count at
first init).  The decision is read from the environment before JAX is
imported; on a host with real devices it never re-execs (the parent would
hold the chips the child needs), and it needs 8 devices there.

Emits ``results/BENCH_dist.json``.
"""

from __future__ import annotations

import os
import subprocess
import sys

N_DEVICES = 8
_CHILD_ENV = "REPRO_DIST_SWEEP_CHILD"

# (records, trees) mesh factorizations of 8: all three decomposition
# families across four mesh shapes.
MESHES = [(8, 1), (4, 2), (2, 4), (1, 8)]

# Distinct operating points: record-heavy (the paper's segmentation scale)
# and tree-heavy (wide forests, e.g. top-k routing ensembles).
WORKLOADS = [
    # name, trees (count, max_depth), M, A
    ("record_heavy_t8_m32768", 8, 8, 32768, 19),
    ("balanced_t16_m4096", 16, 6, 4096, 19),
    ("tree_heavy_t64_m512", 64, 5, 512, 19),
]


def _should_reexec() -> bool:
    """Re-exec only a CPU-only run that lacks the forced device count."""
    if os.environ.get(_CHILD_ENV):
        return False
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        return False
    return "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")


def _reexec_with_devices() -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={N_DEVICES}"
    ).strip()
    env[_CHILD_ENV] = "1"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    subprocess.run(
        [sys.executable, "-m", "benchmarks.dist_sweep"], env=env, cwd=repo, check=True
    )


def _sweep(iters: int, warmup: int) -> dict:
    import dataclasses
    import zlib

    import jax
    import numpy as np

    from benchmarks.common import time_fn, write_bench_json
    from repro.core import EncodedForest, breadth_first_encode, random_tree
    from repro.dist import (
        ForestWorkload,
        MeshCostModel,
        ShardedForestEvaluator,
        StreamingChunker,
        make_plan,
        plan_forest,
    )
    from repro.tune import TuneCache

    mesh_cost = MeshCostModel()
    entries, summaries = [], []
    for name, n_trees, depth, m, a in WORKLOADS:
        trees = [
            breadth_first_encode(
                random_tree(n_attrs=a, n_classes=7, max_depth=2 + (i % depth), seed=i)
            )
            for i in range(n_trees)
        ]
        forest = EncodedForest(trees)
        rec = np.random.default_rng(zlib.crc32(name.encode())).normal(size=(m, a)).astype(np.float32)
        cache = TuneCache()  # shared across plans: per-shard winners accumulate
        wl = ForestWorkload.of(forest, rec)
        print(f"\n[{name}] {wl}")

        measured: dict[tuple[int, int], float] = {}
        for r, g in MESHES:
            if r > m or g > n_trees:
                print(f"  mesh ({r},{g}): infeasible for this workload, skipped")
                continue
            plan = make_plan(wl, r, g, mesh_cost)
            ev = ShardedForestEvaluator(forest, plan=plan, cache=cache)
            # fetch to host so the monolithic timing is apples-to-apples with
            # the streaming path, whose eval() returns host arrays
            t = time_fn(
                f"{name}/mesh{r}x{g}",
                lambda: np.asarray(jax.block_until_ready(ev(rec))),
                iters=iters,
                warmup=warmup,
                workload=name,
                mesh=[r, g],
                decomposition=plan.decomposition,
                predicted_model_units=round(plan.predicted, 3),
                shard_algorithm=plan.algorithm,
            )
            measured[(r, g)] = t.median_us / 1e3
            print(
                f"  mesh ({r},{g}) {plan.decomposition:8s} "
                f"predicted {plan.predicted:12.1f} u  measured {t.median_us/1e3:9.3f} ms"
            )
            entries.append({
                "workload": name,
                "mesh": [r, g],
                "decomposition": plan.decomposition,
                "shard_algorithm": plan.algorithm,
                "predicted_model_units": round(plan.predicted, 3),
                "measured_ms": round(t.median_us / 1e3, 6),
            })

        chosen = plan_forest(wl, N_DEVICES, mesh_cost=mesh_cost)
        pred_key = (chosen.record_shards, chosen.tree_shards)
        meas_key = min(measured, key=measured.get)
        feasible = {
            (r, g): make_plan(wl, r, g, mesh_cost).predicted for (r, g) in measured
        }
        pred_among_meshes = min(feasible, key=feasible.get)
        summaries.append({
            "workload": name,
            "workload_shape": dataclasses.asdict(wl),
            "planner_choice": {
                "mesh": list(pred_key),
                "decomposition": chosen.decomposition,
                "predicted_model_units": round(chosen.predicted, 3),
            },
            "predicted_winner_mesh": list(pred_among_meshes),
            "measured_winner_mesh": list(meas_key),
            "crossover_agreement": pred_among_meshes == meas_key,
        })
        print(
            f"  predicted winner {pred_among_meshes}, measured winner {meas_key}"
            f" -> {'AGREE' if pred_among_meshes == meas_key else 'DISAGREE'}"
        )

        # streaming chunker on the measured-best mesh: overlapped vs monolithic
        best_plan = make_plan(wl, *meas_key, mesh_cost)
        ev = ShardedForestEvaluator(forest, plan=best_plan, cache=cache)
        chunker = StreamingChunker(ev, chunk_records=max(m // 4, 1))
        # warmup must cover the coalescing ladder (two evals per explored
        # size: one compile, one measurement) so iters time the steady state
        t_stream = time_fn(
            f"{name}/stream",
            lambda: chunker.eval(rec),
            iters=iters,
            warmup=max(warmup, 6),
            workload=name,
            mesh=list(meas_key),
            mode="stream_chunked",
        )
        # re-time the monolithic call back-to-back on the *same* evaluator
        # (same compiled program, same machine state) — the mesh-loop number
        # above was taken minutes earlier and drifts by more than the
        # chunked-vs-monolithic difference
        t_mono = time_fn(
            f"{name}/monolithic",
            lambda: np.asarray(jax.block_until_ready(ev(rec))),
            iters=iters,
            warmup=warmup,
            workload=name,
            mesh=list(meas_key),
            mode="monolithic",
        )
        entries.append({
            "workload": name,
            "mesh": list(meas_key),
            "decomposition": best_plan.decomposition,
            "mode": "stream_chunked",
            "chunk_records": chunker.chunk_records,
            "measured_ms": round(t_stream.median_us / 1e3, 6),
            "monolithic_ms": round(t_mono.median_us / 1e3, 6),
            "chunk_ms_median": round(float(np.median(chunker.stats.chunk_ms)), 6),
            "overlap_ratio_mean": round(float(np.mean(chunker.stats.overlap_ratio)), 4),
            "coalesced_chunk_records": int(chunker.stats.coalesced_chunk_records
                                           or chunker.chunk_records),
        })
        print(
            f"  stream ({chunker.chunk_records}/chunk, coalesced to "
            f"{chunker.stats.coalesced_chunk_records or chunker.chunk_records}) "
            f"{t_stream.median_us/1e3:9.3f} ms"
            f" vs monolithic {t_mono.median_us/1e3:9.3f} ms"
        )

    from benchmarks import common

    common.drain_records()  # time_fn entries are folded into our richer JSON
    n_agree = sum(s["crossover_agreement"] for s in summaries)
    path = write_bench_json(
        "dist",
        entries,
        n_devices=N_DEVICES,
        mesh_shapes=[list(x) for x in MESHES],
        summaries=summaries,
        crossover_agreement=f"{n_agree}/{len(summaries)}",
    )
    print(f"\npredicted/measured decomposition winners agree on "
          f"{n_agree}/{len(summaries)} workloads")
    print(f"wrote {path}")
    return {"entries": entries, "summaries": summaries, "path": str(path)}


def main(iters: int = 7, warmup: int = 2) -> dict | None:
    if _should_reexec():
        print(f"re-exec with {N_DEVICES} forced host devices ...")
        _reexec_with_devices()
        return None
    import jax

    if jax.device_count() < N_DEVICES:
        raise SystemExit(
            f"dist_sweep needs {N_DEVICES} devices, found {jax.device_count()} "
            f"(JAX_PLATFORMS=cpu re-execs with forced host devices)"
        )
    return _sweep(iters, warmup)


if __name__ == "__main__":
    main()
