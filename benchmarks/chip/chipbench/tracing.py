"""From a profiler trace to what the per-layer metrics read.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
a small JSON-able dict on one clock:

* ``device_ops``: ``[device, name, start_ns, dur_ns]`` for every event on
  a TPU plane's ``XLA Ops`` line (the name is the HLO instruction);
* ``host_spans``: ``[thread, name, start_ns, dur_ns]`` for every host event
  whose name starts with one of ``SPAN_PREFIXES``: the program's
  ``repro.obs`` spans, bridged onto the profiler by ``TraceAnnotation``,
  and the benchmark's own ``bench.*`` spans.

``Reduced`` then holds the window, the requests in it (one wave each) and
the device's busy intervals, with the interval arithmetic the metric
readers share.  The reduction never looks at which kernel variant ran.
"""

from __future__ import annotations

import bisect
import collections
import glob
import heapq
import os

SPAN_PREFIXES = ("bench.", "serve.", "stream.", "kernel.", "dist.", "tune.", "prof.",
                 "cascade.")
WINDOW_SPAN = "bench.window"
REQUEST_SPAN = "bench.request"   # the harness's span around one engine call: one wave
DEVICE_OPS_LINE = "XLA Ops"
# a Pallas kernel on the TPU's ops line is a custom call to this target; the
# event's name is the HLO instruction, ``%<op> = <shape> custom-call(...)``
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def is_kernel(name: str) -> bool:
    return KERNEL_TARGET in name


def op_name(name: str) -> str:
    """``%_tree_eval_padded.1 = s32[...] custom-call(...)`` -> ``_tree_eval_padded``:
    the instruction's name without its number, the same in every program."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """The device ops and host spans of one profiler trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, host_spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    device_ops += [[dev, e.name, float(e.start_ns), float(e.duration_ns)]
                                   for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        host_spans.append([line.name, e.name, float(e.start_ns),
                                           float(e.duration_ns)])
    return {"device_ops": device_ops, "host_spans": host_spans}


# ---------------------------------------------------------------------------
# interval arithmetic (ns)
# ---------------------------------------------------------------------------


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def overlap(a, b) -> float:
    """Length of the time that both ``a`` and ``b`` cover."""
    return length([(max(s, t), min(e, u)) for s, e in union(a) for t, u in union(b)
                   if min(e, u) > max(s, t)])


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in union(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Reduced:
    """One traced window: its bounds, its requests and the device's busy time."""

    def __init__(self, trace: dict, n_devices: int = 1):
        spans = trace["host_spans"]
        window = [s for s in spans if s[1] == WINDOW_SPAN]
        if not window:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        thread, _, start, dur = window[-1]
        self.lo, self.hi = start, start + dur
        self.thread = thread
        # the request path's spans: the thread that ran the window
        self.main = [(name, s, s + d) for th, name, s, d in spans
                     if th == thread and s >= self.lo and s + d <= self.hi]
        self.requests = [(s, e) for name, s, e in self.main if name == REQUEST_SPAN]
        self.n_devices = n_devices
        self.ops = [(dev, name, s, s + d) for dev, name, s, d in trace["device_ops"]
                    if s + d > self.lo and s < self.hi]
        # the union of every device's busy intervals, for lookups by time
        self._busy = union((s, e) for _, _, s, e in self.ops)
        self._busy_starts = [s for s, _ in self._busy]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips used."""
        per_dev = collections.defaultdict(list)
        for dev, _, s, e in self.ops:
            per_dev[dev].append((s, e))
        total = sum(length(clip(iv, self.lo, self.hi)) for iv in per_dev.values())
        return total * 1e-9 / self.n_devices

    def kernel_s(self) -> float | None:
        """Device seconds of the tree-evaluation kernels in the window;
        None where the trace shows none."""
        iv = [(max(s, self.lo), min(e, self.hi)) for _, name, s, e in self.ops
              if is_kernel(name)]
        return sum(e - s for s, e in iv) * 1e-9 if iv else None

    def spans(self, *prefixes: str, within=None) -> list[tuple[float, float]]:
        """Main-thread spans whose names start with ``prefixes``."""
        out = [(s, e) for name, s, e in self.main if name.startswith(prefixes)]
        if within is not None:
            out = clip(out, *within)
        return out

    def self_ms_per_request(self, outer: tuple, inner: tuple) -> float | None:
        """Mean per request of the time in ``outer`` spans that no ``inner``
        span covers; None where the window holds no request or no outer span."""
        total, seen = 0.0, False
        for r in self.requests:
            out = union(self.spans(*outer, within=r))
            if not out:
                continue
            seen = True
            total += length(out) - overlap(out, self.spans(*inner, within=r))
        return total * 1e-6 / len(self.requests) if seen else None

    def busy_within(self, lo: float, hi: float) -> list[tuple[float, float]]:
        """The device's busy intervals, clipped to ``[lo, hi]``."""
        i = max(0, bisect.bisect_right(self._busy_starts, lo) - 1)
        j = bisect.bisect_left(self._busy_starts, hi)
        return clip(self._busy[i:j], lo, hi)

    def busy_ms_per_request(self) -> float | None:
        """Mean per request of the time in which the device ran anything."""
        if not self.requests:
            return None
        return sum(length(self.busy_within(s, e)) for s, e in self.requests) \
            * 1e-6 / len(self.requests)

    def pieces(self) -> tuple[list[float], list[str]]:
        """The window cut where a request-thread span starts or ends:
        ``(edges, labels)``, ``labels[i]`` naming what the thread was doing
        in ``[edges[i], edges[i + 1])``, its innermost span (the latest to
        start; of two that start together, the first to end), or "none"."""
        spans = sorted((s, e, name) for name, s, e in self.main if name != WINDOW_SPAN)
        edges = sorted({self.lo, self.hi} | {t for s, e, _ in spans for t in (s, e)
                                             if self.lo < t < self.hi})
        labels, active, k = [], [], 0
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            while k < len(spans) and spans[k][0] <= mid:
                s, e, name = spans[k]
                heapq.heappush(active, (-s, e, name))
                k += 1
            while active and active[0][1] <= mid:   # the innermost one has ended
                heapq.heappop(active)
            labels.append(active[0][2] if active else "none")
        return edges, labels

    def idle_gaps(self, top: int = 10) -> list[list]:
        """Idle device time in the window, summed by what the host was
        doing: each gap is cut at the edges of ``pieces`` and each part goes
        to that piece's label."""
        by = collections.Counter()
        edges, labels = self.pieces()
        for s, e in gaps(self._busy, self.lo, self.hi):
            i = max(0, bisect.bisect_right(edges, s) - 1)
            while i < len(labels) and edges[i] < e:
                part = min(e, edges[i + 1]) - max(s, edges[i])
                if part > 0:
                    by[labels[i]] += part * 1e-9
                i += 1
        return [[k, v] for k, v in by.most_common(top)]

    def device_ops(self, top: int = 10) -> list[list]:
        """Device time in the window, summed by operation name."""
        by = collections.Counter()
        for _, name, s, e in self.ops:
            by[op_name(name)] += (min(e, self.hi) - max(s, self.lo)) * 1e-9
        return [[k, v] for k, v in by.most_common(top)]
