"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``configs/<config>.json``: sizes, the model and record generators
  (``generators/<name>.py``) and the engine adapter (``engines/<name>.py``)
  with the options a user of that deployment sets;
* ``traffic/<mix>.json``: parameters for :mod:`chipbench.traffic`;
* ``metrics/<metric>.py``: a reader ``read(ctx) -> float | None`` of one
  per-layer metric; a metric ``name.suffix`` is read by ``name.py`` and the
  suffix names the cells it is reported in.

The window drives the engine from one thread.  An open loop sends each
request when it falls due (or at once, if the server is still busy with
earlier ones) and times it from its due time; a closed loop sends the next
request when the last one returns and times it from its send time.  The
reference checks the classes returned to the client once the window has
closed and the engine is freed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import time

import numpy as np

from chipbench import reference, tracing, traffic
from chipbench.work import Peaks, wave_work

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SETTLE_WAVES = 4          # compile-free waves that end set-up
MAX_WARMUP_WAVES = 256
DRAIN_TIMEOUT_S = 900.0
CHECK_LIMITS = {"mismatched_classes": 0, "unanswered_requests": 0}


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def find_root(start: str = BENCH_DIR) -> str:
    """The checkout: the nearest directory above the benchmark holding
    ``BENCHMARK.json``."""
    d = start
    while True:
        if os.path.isfile(os.path.join(d, "BENCHMARK.json")):
            return d
        up = os.path.dirname(d)
        if up == d:
            raise FileNotFoundError("no BENCHMARK.json above " + start)
        d = up


def load_part(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark, as a module."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: tuple
    per_layer: tuple


def load_cell(root: str, workload: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", f"{w['traffic']}.json")) as f:
        mix = json.load(f)

    def here(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(workload, int(w["chips"]), config, mix,
                tuple(m for m in bench["end_to_end"] if here(m)),
                tuple(m for m in bench["per_layer"] if here(m)))


# ---------------------------------------------------------------------------
# statistics over all requests
# ---------------------------------------------------------------------------


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p``% of
    the values at or below it."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of no values")
    return s[max(1, math.ceil(p / 100.0 * len(s))) - 1]


@dataclasses.dataclass
class Served:
    """One request of the window, as the client saw it (perf_counter s)."""

    index: int                 # into the pool of distinct requests
    records: int
    due: float | None
    sent: float
    done: float | None = None
    out: np.ndarray | None = None
    error: str | None = None

    @property
    def latency_s(self) -> float:
        return self.done - (self.due if self.due is not None else self.sent)


def e2e_metrics(served: list[Served], start: float, setup_s: float) -> dict:
    """End-to-end numbers over every request of the window."""
    ok = [r for r in served if r.done is not None and r.error is None]
    out = {"setup_s": setup_s}
    if ok:
        lat = [r.latency_s * 1e3 for r in ok]
        out["records_per_s"] = sum(r.records for r in ok) / (max(r.done for r in ok) - start)
        out["latency_p50_ms"] = percentile(lat, 50)
        out["latency_p95_ms"] = percentile(lat, 95)
    return out


def generator_lateness_ms(served: list[Served]) -> list[float]:
    """How late each open-loop send came after its due time, for the sends
    that found the server idle (the rest waited for it, and that wait is
    the server's)."""
    out, prev_done = [], -math.inf
    for r in served:
        if r.due is not None and prev_done <= r.due:
            out.append((r.sent - r.due) * 1e3)
        if r.done is not None:
            prev_done = r.done
    return out


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


class Compiles:
    """Counts the programs JAX compiles or loads from its cache, and the
    functions it traces to a jaxpr (which a cache hit does not spare)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        self.count = 0
        self.traces = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1
        elif event == self.TRACE:
            self.traces += 1


class Adapter:
    """The engine of a configuration behind the surface the harness drives."""

    def __init__(self, cell: Cell, model, *, cache_path: str, trace: bool):
        from repro import obs

        eng = cell.config["engine"]
        self._mod = load_part("engines", eng["kind"])
        self.registry = obs.Registry()
        tracer = obs.Tracer(capacity=1 << 16, jax_annotations=True) if trace else None
        self.engine = self._mod.make(model, dict(eng["options"]), cache_path=cache_path,
                                     registry=self.registry, tracer=tracer)
        self.cache_path = cache_path

    def serve(self, uid: int, records: np.ndarray) -> np.ndarray:
        return self._mod.serve(self.engine, uid, records)

    def counter(self, prefix: str) -> float:
        from repro import obs

        return sum(v for k, v in obs.snapshot(self.registry)["counters"].items()
                   if k.startswith(prefix))

    def drain(self) -> None:
        for part in ("retuner", "profiler"):
            worker = getattr(self.engine, part, None)
            if worker is not None:
                worker.drain(timeout=DRAIN_TIMEOUT_S)

    def resolved(self) -> str:
        """The winners the tuner stored, read from its cache file."""
        try:
            with open(self.cache_path) as f:
                entries = json.load(f).get("entries", {})
        except (OSError, ValueError):
            return "no winner stored"
        return "; ".join(f"{k} -> {v.get('variant')} {v.get('params')}"
                         for k, v in sorted(entries.items())) or "no winner stored"

    def close(self) -> None:
        self.drain()
        self.engine = None
        gc.collect()


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def annotate(name: str, on: bool):
    if on:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation(name):
            yield
    else:
        yield


class Answers:
    """Where the client keeps the classes of the window's requests.

    Rows are allocated and written before the window opens, so keeping an
    answer is a copy into memory that is already mapped, and the engine's
    own output buffer is freed at once, as it is for a client that consumes
    its answers.  Holding every output buffer instead grows the process by
    a fresh buffer per request, and the page faults that costs land in the
    measured latencies.  An answer that is not a row of int32 classes is
    kept as it came, for the check to judge.
    """

    def __init__(self, rows: int, width: int):
        self.rows, self.width = max(1, rows), width
        self.blocks = [self._block()]
        self.used = 0

    def _block(self) -> np.ndarray:
        return np.full((self.rows, self.width), -1, np.int32)

    def keep(self, out) -> np.ndarray:
        out = np.asarray(out)
        if out.dtype != np.int32 or out.ndim != 1 or out.shape[0] > self.width:
            return out
        if self.used == self.rows:      # a closed loop ran past the estimate
            self.blocks.append(self._block())
            self.used = 0
        row = self.blocks[-1][self.used, :out.shape[0]]
        self.used += 1
        np.copyto(row, out)
        return row


def serve_one(adapter, uid: int, records: np.ndarray, served: Served, trace: bool,
              answers: Answers) -> None:
    try:
        with annotate("bench.request", trace):
            served.out = answers.keep(adapter.serve(uid, records))
    except Exception as e:  # a failed request counts as failed, the run goes on
        served.error = f"{type(e).__name__}: {e}"
        print(f"bench: request {uid} raised {served.error}", file=sys.stderr, flush=True)
    served.done = time.perf_counter()


def warm_up(adapter, source, cell: Cell, seed: int, compiles: Compiles) -> dict:
    """Waves of the cell's own shapes until the hot-bucket re-tune has run
    and drained, one whole block of the mix's sizes has been served, and
    ``SETTLE_WAVES`` waves in a row compiled nothing."""
    n_max = MAX_WARMUP_WAVES
    sizes = traffic.warmup_sizes(cell.mix, seed, n_max)
    n_min = traffic.block_len(cell.mix["records"])
    n = 0
    split = {}

    def one():
        nonlocal n
        records = source.draw(traffic.rng(seed, traffic.WARMUP, n), sizes[n])
        adapter.serve(-1 - n, records)
        n += 1

    t = time.perf_counter()
    one()
    split["first_wave_s"] = time.perf_counter() - t
    t = time.perf_counter()
    retuner = getattr(adapter.engine, "retuner", None)
    if retuner is not None:
        while adapter.counter("serve.retune.launched") < 1 and n < n_max:
            one()
        split["waves_to_retune_s"] = time.perf_counter() - t
        t = time.perf_counter()
    adapter.drain()
    split["retune_drain_s"] = time.perf_counter() - t
    t = time.perf_counter()
    quiet = 0
    while (quiet < SETTLE_WAVES or n < n_min) and n < n_max:
        before = compiles.count
        one()
        quiet = quiet + 1 if compiles.count == before else 0
    adapter.drain()
    split["settle_s"] = time.perf_counter() - t
    split["warmup_waves"] = n
    if quiet < SETTLE_WAVES:
        say(f"set-up ended after {n} waves with programs still compiling")
    return split


def answer_store(plan: traffic.Plan) -> Answers:
    """Room for the window's answers: every send of an open loop; a closed
    loop's count is not known ahead, so it starts with room for 256."""
    rows = len(plan.due_s) if plan.loop == "open" else 256
    return Answers(rows, max(plan.sizes))


def window(adapter, plan: traffic.Plan, requests: list, seconds: float, trace: bool,
           answers: Answers | None = None) -> tuple:
    """Drive the window; returns (served requests, window start)."""
    answers = answers if answers is not None else answer_store(plan)
    served = []
    with annotate("bench.window", trace):
        start = time.perf_counter()
        if plan.loop == "open":
            for i, due_s in enumerate(plan.due_s):
                k = i % len(requests)
                due = start + due_s
                now = time.perf_counter()
                if now < due:
                    with annotate("bench.wait", trace):
                        time.sleep(due - now)
                s = Served(k, requests[k].shape[0], due, time.perf_counter())
                serve_one(adapter, i, requests[k], s, trace, answers)
                served.append(s)
        else:
            i = 0
            while time.perf_counter() - start < seconds:
                k = i % len(requests)
                s = Served(k, requests[k].shape[0], None, time.perf_counter())
                serve_one(adapter, i, requests[k], s, trace, answers)
                served.append(s)
                i += 1
    return served, start


def check(served: list[Served], requests: list, model, positions) -> tuple[dict, dict]:
    """Compare the classes returned by the served requests at ``positions``
    with the reference; every request that never answered counts too.

    Returns (checks, reference comparisons per record by request index)."""
    unanswered = sum(1 for s in served if s.out is None or s.error is not None)
    mismatched = 0
    comps: dict[int, float] = {}
    ref: dict[int, np.ndarray] = {}
    for s in (served[p] for p in positions):
        if s.out is None or s.error is not None:
            continue
        if s.index not in ref:
            ref[s.index], c = reference.classify(model, requests[s.index])
            comps[s.index] = float(c.mean())
        want = ref[s.index]
        got = np.asarray(s.out)
        mismatched += (int(np.count_nonzero(got != want)) if got.shape == want.shape
                       else want.shape[0])
    return {"mismatched_classes": mismatched, "unanswered_requests": unanswered}, comps


@dataclasses.dataclass
class Session:
    """A cell's model, record source and warmed-up engine."""

    cell: Cell
    seed: int
    model: reference.Model
    source: object
    adapter: object
    split: dict

    def requests(self, plan: traffic.Plan) -> list:
        return [self.source.draw(traffic.rng(self.seed, traffic.WINDOW, i), n)
                for i, n in enumerate(plan.sizes)]


def prepare(cell: Cell, seed: int, *, cache_path: str, compiles: Compiles, trace: bool,
            make_adapter=Adapter) -> Session:
    """Build the model, the record source and the engine, and warm it up."""
    cfg = cell.config
    t = time.perf_counter()
    model = load_part("generators", cfg["model"]["generator"]).build(cfg, seed)
    source = load_part("generators", cfg["records"]["generator"]).Source(cfg)
    split = {"model_s": time.perf_counter() - t}
    t = time.perf_counter()
    adapter = make_adapter(cell, model, cache_path=cache_path, trace=trace)
    split["engine_s"] = time.perf_counter() - t
    split.update(warm_up(adapter, source, cell, seed, compiles))
    return Session(cell, seed, model, source, adapter, split)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, t0: float,
             device: dict, peaks: Peaks, out_dir: str, cache_path: str,
             compiles: Compiles, make_adapter=Adapter) -> dict:
    """One run; returns the result object (printed by the caller)."""
    sess = prepare(cell, seed, cache_path=cache_path, compiles=compiles, trace=trace,
                   make_adapter=make_adapter)
    model, adapter, split = sess.model, sess.adapter, sess.split
    t = time.perf_counter()
    plan = traffic.plan(cell.mix, seed, seconds)
    requests = sess.requests(plan)
    answers = answer_store(plan)
    split["inputs_s"] = time.perf_counter() - t
    say(f"resolved: {adapter.resolved()}")
    if trace:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # annotations only: less cost on the request path
        trace_dir = os.path.join(out_dir, "trace")
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t0
    say("set-up split s: " + json.dumps({k: round(v, 3) for k, v in split.items()}))
    c0, j0 = compiles.count, compiles.traces
    r0 = adapter.counter("serve.retune.launched")
    served, start = window(adapter, plan, requests, seconds, trace, answers)
    in_window = {"compiles": compiles.count - c0, "traces": compiles.traces - j0,
                 "retunes": adapter.counter("serve.retune.launched") - r0}
    if trace:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        say(f"profiler stopped and wrote its trace in {time.perf_counter() - t:.3f} s")
    say(f"in the window: {json.dumps(in_window)}")
    service = [(s.done - s.sent) * 1e3 for s in served if s.done is not None]
    if service:
        say(f"service ms (send to answer) over {len(service)} requests: "
            f"p50 {percentile(service, 50)} p95 {percentile(service, 95)}"
            + (" (under the profiler)" if trace else ""))
        slow = sorted((s for s in served if s.done is not None),
                      key=lambda s: s.sent - s.done)[:3]
        say("slowest requests (s into the window, service ms): " + ", ".join(
            f"{s.sent - start:.3f} {(s.done - s.sent) * 1e3:.3f}" for s in slow))
    late = generator_lateness_ms(served)
    if late:
        say(f"generator lateness ms over {len(late)} idle-server sends: "
            f"p50 {percentile(late, 50)} p95 {percentile(late, 95)} max {max(late)}")
    device = dict(device, memory_peak_bytes=device_memory_peak())
    adapter.close()
    del adapter, sess
    gc.collect()
    t = time.perf_counter()
    positions = traffic.check_positions(plan, seed, len(served))
    checks, comps = check(served, requests, model, positions)
    say(f"reference check of {len(positions)} of {len(served)} requests took "
        f"{time.perf_counter() - t:.3f} s")
    answered = [s for s in served if s.out is not None and s.error is None]
    result = {
        "correct": all(checks[k] <= CHECK_LIMITS[k] for k in CHECK_LIMITS),
        "attempted": len(served),
        "failed": len(served) - len(answered),
        "metrics": {},
        "device": device,
    }
    if not trace:
        values = e2e_metrics(served, start, setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        per_rec = float(np.mean(list(comps.values()))) if comps else 0.0
        works = [wave_work(s.records, comps.get(s.index, per_rec) * s.records,
                           n_attrs=model.n_attrs, tree_nodes=model.n_nodes) for s in answered]
        least = sum(w.least_s(peaks) for w in works)
        if works:
            say(f"least time {least:.6g} s for {len(works)} requests, bound by "
                f"{works[0].bound(peaks)}")
        t = time.perf_counter()
        raw = tracing.load_xplane(tracing.find_xplane(trace_dir))
        with open(os.path.join(out_dir, "trace.json"), "w") as f:
            json.dump(raw, f)
        red = tracing.Reduced(raw, n_devices=cell.chips)
        say(f"trace read in {time.perf_counter() - t:.3f} s")
        ctx = TraceContext(red, least, peaks)
        for m in cell.per_layer:
            v = load_part("metrics", m["name"].split(".")[0]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"].update(busy_s=red.busy_s(), window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.device_ops(), "idle_gaps": red.idle_gaps()}
        say(f"trace read and reduced in {time.perf_counter() - t:.3f} s")
    result["checks"] = {k: {"value": checks[k], "limit": CHECK_LIMITS[k]} for k in CHECK_LIMITS}
    return result


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """What a per-layer metric reader gets."""

    reduced: tracing.Reduced
    least_s: float          # least time the peaks allow for the window's requests
    peaks: Peaks


def device_memory_peak() -> int:
    """Peak bytes in use on the fullest device, where the backend reports it."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
