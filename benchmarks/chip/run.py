#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 benchmarks/chip/run.py --workload seg_cart.stream --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout: the cells, configurations, traffic mixes
and metrics are named in ``BENCHMARK.json`` and found as files under
``benchmarks/chip/`` (see ``chipbench/harness.py``); the system under test
is the checkout's ``src/repro``.  The run makes its model and inputs from
``--seed``, warms up every shape the cell uses, measures for ``--seconds``
and checks the classes returned against the plain reference.  With
``--trace 0`` the last stdout line holds the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the JAX profiler and the line
holds the per-layer metrics read from its trace.  The numbers compared
with their limits are the last lines on stderr.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from chipbench import harness  # noqa: E402
from chipbench.work import UnknownDevice, peaks  # noqa: E402

# JAX's persistent compile cache: where JAX_COMPILATION_CACHE_DIR says, else
# at this fixed path in the checkout (the path is part of what a later run
# looks up, so it never moves)
COMPILE_CACHE = os.path.join(BENCH_DIR, ".jax_cache")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(BENCH_DIR, "out"),
                    help="directory for the run's trace and logs")
    return ap.parse_args(argv)


def chips(need: int) -> dict:
    """The devices JAX sees; raises NoChip without ``need`` TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r}); "
                     "nothing is run on the CPU")
    if len(devices) < need:
        raise NoChip(f"the cell needs {need} chips, JAX found {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def use_src(root: str) -> None:
    src = os.path.join(root, "src")
    if not os.path.isfile(os.path.join(src, "repro", "serve", "engine.py")):
        raise FileNotFoundError(f"no src/repro in the checkout {root}")
    if src not in sys.path:
        sys.path.insert(0, src)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        root = harness.find_root()
        cell = harness.load_cell(root, args.workload)
        use_src(root)
    except (FileNotFoundError, KeyError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    tune_dir = tempfile.mkdtemp(prefix="chipbench-tune-")
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(tune_dir, "tune.json")
    # the TPU runtime otherwise logs to a fixed path under /tmp
    os.makedirs(args.out, exist_ok=True)
    os.environ["TPU_LOG_DIR"] = os.path.join(os.path.abspath(args.out), "tpu_logs")
    try:
        import jax
        import jax.monitoring

        device = chips(cell.chips)
        peak = peaks(device["kind"])
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compiles = harness.Compiles()
        jax.monitoring.register_event_duration_secs_listener(compiles)
        out_dir = os.path.join(args.out, f"{args.workload}-{args.seed}-t{args.trace}")
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir)
        harness.say(f"{args.workload} seed {args.seed} on {device['kind']} "
                    f"x{device['count']}, jax {jax.__version__}, compile cache {cache_dir}")
        result = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), t0=T0, device=device,
            peaks=peak, out_dir=out_dir, cache_path=os.environ["REPRO_TUNE_CACHE"],
            compiles=compiles)
    except (NoChip, UnknownDevice) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    finally:
        shutil.rmtree(tune_dir, ignore_errors=True)
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
