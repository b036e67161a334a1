#!/usr/bin/env python3
"""Read the control of a cell: the reference in bfloat16, in the program's place.

    python3 benchmarks/chip/tools/control.py --workload seg_cart.stream \
        --seeds 11,12,13 --seconds 30

For each seed the window's requests are made as a run makes them, at the
cell's own size; the control (``reference.classify(..., dtype=BF16)``:
records and thresholds rounded to bfloat16, the nearest precision below
the float32 the configurations state) answers them in the program's place,
and ``harness.check`` compares its answers with the float32 reference as it
compares the program's.  An open loop answers every request of the window;
a closed loop as many as the mix checks.  One JSON line per seed.  The
control needs no chip; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from chipbench import harness, reference, traffic  # noqa: E402


def control_checks(cell: harness.Cell, seed: int, seconds: float) -> dict:
    """The numbers a run would compare, with the control answering."""
    cfg = cell.config
    model = harness.load_part("generators", cfg["model"]["generator"]).build(cfg, seed)
    source = harness.load_part("generators", cfg["records"]["generator"]).Source(cfg)
    plan = traffic.plan(cell.mix, seed, seconds)
    k = len(plan.sizes)
    n = len(plan.due_s) if plan.loop == "open" else min(plan.check_requests or k, k)
    requests = [source.draw(traffic.rng(seed, traffic.WINDOW, i), plan.sizes[i])
                for i in range(min(n, k))]
    answers = [reference.classify(model, r, dtype=reference.BF16)[0] for r in requests]
    served = [harness.Served(i % k, requests[i % k].shape[0], None, 0.0, 0.0, answers[i % k])
              for i in range(n)]
    checks, _ = harness.check(served, requests, model, range(n))
    return dict(checks, requests=n, records=sum(s.records for s in served))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(harness.find_root(), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        row = control_checks(cell, seed, args.seconds)
        row.update(workload=args.workload, seed=seed, seconds=time.perf_counter() - t)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
