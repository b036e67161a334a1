"""CPU tests of the readers of the program's dispatch phases
(``dispatch_host_ms``, ``serve_batch_ms``): on a
hand-made trace, on one of a program that does not split its dispatch, and
on a small trace recorded on a TPU v5e.

The recorded trace (``testdata/seg_cart_stream_spans.json``) is the
``trace.json`` of a ``run.py --trace 1`` run of ``seg_cart.stream``, cut to
the first requests of its window: their host spans, the device ops that
start inside them, and a ``bench.window`` span shrunk to cover just them.
"""

from __future__ import annotations

import json
import os

import pytest

from chipbench import harness, tracing, work

MS = 1e6  # ns
READERS = ("dispatch_host_ms", "serve_batch_ms")
PHASES = {"tune.h2d", "kernel.pack", "kernel.prep", "kernel.launch", "kernel.wait",
          "kernel.d2h", "serve.batch", "serve.hooks"}
KERNEL = ('%tree_eval_speculative_gather.1 = s32[1,65536,1]{2,1,0:T(8,128)} custom-call('
          'f32[65536,128] %records.1), custom_call_target="tpu_custom_call"')


def _wave(t0, batch, h2d, pack, prep, launch, wait, d2h):
    """One request's spans from ``t0`` (ms): the phases run back to back
    inside ``kernel.dispatch``, with 1 ms of the dispatch's own time after
    the copy back."""
    th, spans, t = "python3", [], t0 + 1
    spans.append([th, "serve.batch", t * MS, batch * MS])
    t += batch + 1
    d0 = t
    spans.append([th, "tune.h2d", t * MS, h2d * MS])
    t += h2d
    v0 = t
    for name, d in (("kernel.pack", pack), ("kernel.prep", prep), ("kernel.launch", launch)):
        spans.append([th, name, t * MS, d * MS])
        t += d
    spans.append([th, "tune.variant", v0 * MS, (t - v0) * MS])
    for name, d in (("kernel.wait", wait), ("kernel.d2h", d2h)):
        spans.append([th, name, t * MS, d * MS])
        t += d
    t += 1
    spans.append([th, "kernel.dispatch", d0 * MS, (t - d0) * MS])
    spans.append([th, "serve.hooks", t * MS, 2 * MS])
    t += 3
    spans.append([th, "serve.wave", (t0 + 1) * MS, (t - t0 - 1) * MS])
    spans.append([th, "bench.request", t0 * MS, (t + 1 - t0) * MS])
    return spans


def _trace():
    spans = [["python3", "bench.window", 0, 100 * MS]]
    # request 1 from 10 ms: kernel.dispatch 14..34; request 2 from 50 ms: 53..69
    spans += _wave(10, batch=2, h2d=4, pack=2, prep=2, launch=2, wait=7, d2h=2)
    spans += _wave(50, batch=1, h2d=2, pack=2, prep=1, launch=2, wait=6, d2h=2)
    return {
        "host_spans": spans,
        "device_ops": [
            [0, "%copy.1 = f32[65536,19] copy(f32[65536,19] %a)", 15 * MS, 2 * MS],
            [0, KERNEL, 23 * MS, 7.5 * MS],      # launch 22..24, wait 24..31
            [0, KERNEL, 59 * MS, 6.5 * MS],      # launch 58..60, wait 60..66
        ],
    }


def _read(raw):
    ctx = harness.TraceContext(tracing.Reduced(raw), least_s=0.001,
                               peaks=work.peaks("TPU v5 lite"))
    return {m: harness.load_part("metrics", m).read(ctx) for m in READERS}


def test_readers_on_a_hand_made_trace():
    read = _read(_trace())
    # dispatch less copy, wait and copy back: (20 - 13) and (16 - 10) ms
    assert read["dispatch_host_ms"] == pytest.approx((7 + 6) / 2)
    assert read["serve_batch_ms"] == pytest.approx((2 + 1) / 2)


def test_idle_time_goes_to_the_phases():
    red = tracing.Reduced(_trace())
    gaps = dict(red.idle_gaps(top=50))
    assert PHASES <= set(gaps)
    # the dispatch's own 1 ms after each copy back, and nothing else
    assert gaps["kernel.dispatch"] == pytest.approx(0.002)
    # each wait outlasts its kernel by 0.5 ms
    assert gaps["kernel.wait"] == pytest.approx(0.0005 + 0.0005)
    assert gaps["kernel.launch"] == pytest.approx(0.001 + 0.001)   # until the kernel starts
    assert gaps["tune.h2d"] == pytest.approx(0.002 + 0.002)    # the copy op covers 2 of 4 ms


def test_readers_read_nothing_where_the_dispatch_is_not_split():
    raw = _trace()
    raw["host_spans"] = [s for s in raw["host_spans"]
                         if s[1] in ("bench.window", "bench.request", "serve.wave",
                                     "kernel.dispatch")]
    assert _read(raw) == dict.fromkeys(READERS)


# ---------------------------------------------------------------------------
# the recorded trace
# ---------------------------------------------------------------------------


def _recorded():
    path = os.path.join(harness.BENCH_DIR, "testdata", "seg_cart_stream_spans.json")
    with open(path) as f:
        return json.load(f)


def test_readers_on_the_recorded_trace():
    raw = _recorded()
    red = tracing.Reduced(raw)
    assert len(red.requests) >= 3
    read = _read(raw)
    assert all(v is not None and v > 0 for v in read.values()), read
    waves = [(e - s) * 1e-6 for s, e in red.requests]
    assert sum(read.values()) < max(waves)
    # every request ran every phase of a steady-state wave
    names = {n for n, _, _ in red.main}
    assert PHASES | {"kernel.dispatch", "tune.variant", "serve.wave"} <= names


def test_kernels_run_between_their_launch_and_the_end_of_their_wait():
    """The clock check.  The profiler puts the device's events on the host's
    clock only to about a millisecond, and the offset differs from run to
    run (in the recorded run some kernels appear to start up to 1.14 ms
    before their launch, and every wait ends 1.7-1.9 ms after its kernel;
    in a 30 s run of the same cell 0.13 ms and 0.7-1.1 ms).  So the check
    is that one shift of the device's timeline, the same for every event
    and under 2 ms, puts each kernel after the start of its
    ``kernel.launch`` and before the end of its ``kernel.wait``."""
    raw = _recorded()
    kernels = sorted((s, s + d) for _, n, s, d in raw["device_ops"] if tracing.is_kernel(n))
    launches = sorted(s for _, n, s, _ in raw["host_spans"] if n == "kernel.launch")
    waits = sorted(s + d for _, n, s, d in raw["host_spans"] if n == "kernel.wait")
    assert len(kernels) == len(launches) == len(waits) >= 3
    lead = max(l0 - k0 for (k0, _), l0 in zip(kernels, launches))   # least shift needed
    slack = min(w1 - k1 for (_, k1), w1 in zip(kernels, waits))     # most shift allowed
    assert lead <= slack and lead < 2 * MS
    assert {tracing.op_name(n) for _, n, _, _ in raw["device_ops"] if tracing.is_kernel(n)} \
        <= {"tree_eval_speculative_gather", "tree_eval_speculative_onehot",
            "tree_eval_data_parallel"}


def test_recorded_idle_time_is_labelled_by_phase():
    red = tracing.Reduced(_recorded())
    gaps = dict(red.idle_gaps(top=50))
    assert {"tune.h2d", "kernel.pack", "kernel.prep", "kernel.launch", "kernel.wait",
            "kernel.d2h", "serve.batch", "serve.hooks"} <= set(gaps)
    assert gaps.get("kernel.dispatch", 0.0) < 0.05 * sum(gaps.values())
