"""Benchmark harness: one module per paper table/figure + framework benches.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run table1     # one

Each bench also emits a machine-readable ``results/BENCH_<name>.json``
(per-variant median ms + metadata, collected by ``common.time_fn``) so perf
is tracked across PRs, not just eyeballed in stdout tables.
"""

from __future__ import annotations

import sys
import time


BENCHES = ["table1", "fig4", "analysis", "m_sweep", "geometry", "moe_router", "tune",
           "cascade", "dist_sweep", "obs", "profile", "layout"]


def _run(name: str) -> None:
    from benchmarks import common

    t0 = time.perf_counter()
    print(f"\n=== {name} " + "=" * max(1, 66 - len(name)))
    common.drain_records()  # start the bench with an empty perf buffer
    if name == "table1":
        from benchmarks.table1_eval_times import main
        main(iters=10)
    elif name == "fig4":
        from benchmarks.fig4_kernel_times import main
        main(iters=10)
    elif name == "analysis":
        from benchmarks.analysis_curves import main
        main()
    elif name == "m_sweep":
        from benchmarks.m_sweep import main
        main()
    elif name == "geometry":
        from benchmarks.geometry_sweep import main
        main()
    elif name == "moe_router":
        from benchmarks.moe_router_bench import main
        main()
    elif name == "tune":
        from benchmarks.tune_sweep import main
        main()
    elif name == "cascade":
        from benchmarks.cascade_sweep import main
        main()
    elif name == "dist_sweep":
        from benchmarks.dist_sweep import main
        main()
    elif name == "obs":
        from benchmarks.obs_overhead import main
        main()
    elif name == "profile":
        from benchmarks.profile_sweep import main
        main()
    elif name == "layout":
        from benchmarks.layout_sweep import main
        main()
    else:
        raise SystemExit(f"unknown bench {name!r}; available: {BENCHES}")
    entries = common.drain_records()
    if entries and name not in ("tune", "cascade", "dist_sweep", "obs", "profile", "layout"):  # richer reports
        path = common.write_bench_json(name, entries)
        print(f"--- wrote {path}")
    print(f"--- {name} done in {time.perf_counter() - t0:.1f}s")


def main() -> None:
    import os

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    names = sys.argv[1:] or BENCHES
    for n in names:
        _run(n)


if __name__ == "__main__":
    main()
