#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: its knee.

    python3 benchmarks/chip/tools/sweep.py --workload seg_cart.stream \
        --seed 5 --seconds 15 --rates 4,6,8,10,12

One process, one set-up: first a closed loop (back-to-back requests of the
mix's sizes) for ``--seconds`` gives the service rate; then the open loop at
each rate of ``--rates`` for ``--seconds`` each.  A rate is sustained when
the backlog does not grow: the mean latency of the window's last quarter
exceeds that of its first quarter by less than two service times, and p95
stays under five service times.  Each line of output is one JSON object; the knee is written into
the traffic file by hand, as a number, at 4/5 of the highest sustained rate.

Every plan here goes through ``chipbench.traffic.plan``, so a mix of
variable sizes sends ``log_blocks``: each block of consecutive requests
holds the same sizes for every seed, only their order drawn from it, and
rates measured on different seeds count the same records.  Sizes drawn one
by one would move a rate by the seed's share of large requests, not by
what the server sustains.  The closed loop's pool is the least whole
number of blocks that holds 8 requests.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from chipbench import harness, traffic  # noqa: E402


def summary(served, start) -> dict:
    lat = [s.latency_s * 1e3 for s in served if s.error is None]
    q = max(1, len(lat) // 4)
    first, last = sum(lat[:q]) / q, sum(lat[-q:]) / q
    out = harness.e2e_metrics(served, start, 0.0)
    out.pop("setup_s")
    out.update(n=len(lat), first_quarter_ms=first, last_quarter_ms=last,
               growth=last / first)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    root = harness.find_root()
    sys.path.insert(0, os.path.join(root, "src"))
    os.environ["TPU_LOG_DIR"] = os.path.join(HERE, "out", "tpu_logs")
    import jax
    import jax.monitoring

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(HERE, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell = harness.load_cell(root, args.workload)
    compiles = harness.Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    with tempfile.TemporaryDirectory() as tmp:
        sess = harness.prepare(cell, args.seed, cache_path=os.path.join(tmp, "tune.json"),
                               compiles=compiles, trace=False)
        print(json.dumps({"setup": sess.split}), flush=True)
        block = traffic.block_len(cell.mix["records"])
        closed = dict(cell.mix, loop="closed", pool=-(-8 // block) * block)
        plan = traffic.plan(closed, args.seed, args.seconds)
        served, start = harness.window(sess.adapter, plan, sess.requests(plan),
                                       args.seconds, False)
        cap = summary(served, start)
        print(json.dumps({"closed_loop": cap}), flush=True)
        service_ms = cap["latency_p50_ms"]
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = dict(cell.mix, loop="open", rate_per_s=rate)
            plan = traffic.plan(mix, args.seed + int(rate * 1000), args.seconds)
            t = time.perf_counter()
            served, start = harness.window(sess.adapter, plan, sess.requests(plan),
                                           args.seconds, False)
            row = summary(served, start)
            row.update(rate_per_s=rate, wall_s=time.perf_counter() - t,
                       sustained=(row["last_quarter_ms"] - row["first_quarter_ms"] < 2 * service_ms
                                  and row["latency_p95_ms"] < 5 * service_ms),
                       compiles=compiles.count)
            print(json.dumps({"open_loop": row}), flush=True)
        sess.adapter.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
