"""Streaming chunker: double-buffer host→device transfer against evaluation.

The paper's t_s(M) = σ·M + γ transmission term is paid *serially* in its
CUDA timings — copy the whole record array in, run, copy assignments out.
For segmentation-scale streams (millions of records) the copy need not
serialize: JAX dispatch is asynchronous, so submitting chunk k+1's
``device_put`` + evaluation while chunk k is still running overlaps the σ·M
wire time with compute, hiding min(t_s, T_eval) per chunk.  The chunker
submits *before* it drains — chunk k+1's dispatch is queued before the host
blocks on chunk k — and keeps at most ``inflight`` chunks pending after each
submit settles, so host memory and device queues stay bounded.

Two per-chunk measurements land in :class:`StreamStats` (and in the caller's
stats via ``on_chunk``):

* ``chunk_ms`` — submit→ready latency, the stream analogue of
  ``TreeServeEngine``'s per-wave accounting;
* ``overlap_ratio`` — the fraction of this chunk's submit→ready window
  during which the *previous* chunk was still in flight, i.e. how much of
  the pipeline actually ran double-buffered (0.0 for the first chunk).

Chunking is only a win while the overlapped transfer outweighs the fixed
per-dispatch cost; on transfer-free backends (CPU, fully resident data) it
is pure overhead.  With ``auto_coalesce`` (default) the chunker measures its
own throughput per effective chunk size and grows the size — up to
``max_coalesce``× the configured ``chunk_records`` — while bigger chunks
keep winning, retreating to the best size seen when they stop.  The first
``eval()`` always runs at the configured ``chunk_records`` (sizes are only
explored once a baseline throughput exists), so one-shot callers see
exactly the chunk geometry they asked for.
"""

from __future__ import annotations

import time
from collections import deque

import jax
import numpy as np

from repro import obs


class StreamStats:
    """Chunker accounting on a :class:`repro.obs.Registry`.

    The pre-obs dataclass fields survive: scalars as read properties over
    locked instruments, the per-chunk sequences (``chunk_ms``,
    ``overlap_ratio``) as plain lists next to their histogram twins —
    benches take medians over the lists, dashboards read the histograms.
    """

    def __init__(self, registry: obs.Registry | None = None):
        self.registry = registry if registry is not None else obs.Registry()
        r = self.registry
        self.m_chunks = r.counter("stream.chunks", "chunks drained")
        self.m_records = r.counter("stream.records", "records streamed")
        self.m_d2h_bytes = r.counter(
            "stream.d2h_bytes", "bytes of per-tree classes the drains copied device to host")
        self.m_wall_s = r.counter(
            "stream.wall_s", "submit-first → drain-last seconds, per eval()")
        self.m_chunk_ms = r.histogram(
            "stream.chunk_ms", "submit→ready latency per chunk")
        self.m_overlap = r.histogram(
            "stream.overlap_ratio",
            "fraction of each chunk's submit→ready window shared with the "
            "previous in-flight chunk",
            boundaries=obs.DEFAULT_RATIO_BOUNDARIES)
        self.g_coalesced = r.gauge(
            "stream.coalesced_chunk_records",
            "effective chunk size after throughput-feedback adaptation")
        self.m_coalesce = r.counter(
            "stream.coalesce_decisions",
            "throughput-feedback coalescing decisions", ("decision",))
        self.chunk_ms: list = []        # submit→ready per chunk
        # fraction of each chunk's submit→ready window shared with the
        # previous in-flight chunk (0.0 for the first chunk of an eval)
        self.overlap_ratio: list = []

    @property
    def chunks(self) -> int:
        return int(self.m_chunks.value)

    @property
    def records(self) -> int:
        return int(self.m_records.value)

    @property
    def wall_s(self) -> float:
        return self.m_wall_s.value

    @property
    def coalesced_chunk_records(self) -> int:
        return int(self.g_coalesced.value)


class StreamingChunker:
    """Chunked, overlap-friendly driver for a (sharded) forest evaluator.

    ``evaluator`` is any callable records → (T, m) that does *not* block on
    the device (:class:`repro.dist.ShardedForestEvaluator` by contract); the
    chunker owns synchronisation.  Sharding and divisibility padding happen
    inside the evaluator's single fused program, so each chunk costs exactly
    one asynchronous dispatch here.
    """

    def __init__(self, evaluator, *, chunk_records: int = 65536, inflight: int = 2,
                 stats: StreamStats | None = None, auto_coalesce: bool = True,
                 max_coalesce: int = 8,
                 registry: obs.Registry | None = None,
                 tracer: obs.Tracer | None = None):
        if chunk_records < 1:
            raise ValueError("chunk_records must be >= 1")
        self.evaluator = evaluator
        self.chunk_records = chunk_records
        self.inflight = max(1, inflight)
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        self.stats = stats if stats is not None else StreamStats(registry)
        self.auto_coalesce = auto_coalesce
        self.max_coalesce = max(1, int(max_coalesce))
        self._effective = chunk_records      # current adapted chunk size
        self._evals = 0
        self._tput: dict[int, float] = {}    # effective size → records/s (EMA)
        self._seen: set[int] = set()         # sizes whose compile eval is spent
        self._prev_ready: float | None = None

    def _drain_one(self, pending: deque, outs: list, on_chunk) -> None:
        out, t_submit, n = pending.popleft()
        with self.tracer.span("stream.drain", cat="stream", records=n) as dspan:
            arr = np.asarray(jax.block_until_ready(out))
        t_ready = time.perf_counter()
        latency_ms = (t_ready - t_submit) * 1e3
        window = max(t_ready - t_submit, 1e-9)
        if self._prev_ready is None:
            overlap = 0.0
        else:
            overlap = min(max((self._prev_ready - t_submit) / window, 0.0), 1.0)
        self._prev_ready = t_ready
        dspan.set(chunk_ms=round(latency_ms, 3), overlap=round(overlap, 3))
        self.stats.m_chunks.inc()
        self.stats.m_records.inc(n)
        self.stats.m_d2h_bytes.inc(arr.nbytes)
        self.stats.m_chunk_ms.observe(latency_ms)
        self.stats.m_overlap.observe(overlap)
        self.stats.chunk_ms.append(latency_ms)
        self.stats.overlap_ratio.append(overlap)
        if on_chunk is not None:
            on_chunk(latency_ms, n)
        outs.append(arr)

    def _note_eval(self, size: int, n_chunks: int, records: int, wall: float) -> None:
        """Throughput-feedback coalescing: grow the effective chunk size while
        bigger chunks keep winning, retreat to the best size seen when not."""
        if not self.auto_coalesce or records == 0 or wall <= 0.0:
            return
        if size not in self._seen:
            # the first eval at a new size pays jit compilation for the new
            # chunk shape; stay here one more eval and measure compile-free
            self._seen.add(size)
            self.stats.g_coalesced.set(self._effective)
            return
        tput = records / wall
        prev = self._tput.get(size)
        self._tput[size] = tput if prev is None else 0.5 * prev + 0.5 * tput
        best = max(self._tput, key=self._tput.get)
        if best != size:
            self._effective = best       # the explored size lost; go back
            decision = "retreat"
        else:
            cap = self.chunk_records * self.max_coalesce
            nxt = min(size * 2, cap)
            if n_chunks > 1 and nxt > size and nxt not in self._tput:
                self._effective = nxt    # current best; explore one size up
                decision = "grow"
            else:
                decision = "hold"
        self.stats.m_coalesce.labels(decision=decision).inc()
        self.tracer.instant("stream.coalesce", cat="stream", decision=decision,
                            size=size, effective=self._effective)
        self.stats.g_coalesced.set(self._effective)

    def eval(self, records, *, on_chunk=None) -> np.ndarray:
        """Evaluate a (possibly huge) record batch; returns host (T, M).

        ``on_chunk(latency_ms, n_records)`` fires as each chunk completes —
        serve engines feed their own stats through it.
        """
        rec = np.asarray(records, np.float32)
        m = rec.shape[0]
        t0 = time.perf_counter()
        pending: deque = deque()
        outs: list[np.ndarray] = []
        self._prev_ready = None
        # the first eval honours the configured chunk size exactly; adapted
        # sizes only apply once a baseline throughput has been measured
        size = self._effective if (self.auto_coalesce and self._evals > 0) else self.chunk_records
        n_chunks = 0
        with self.tracer.span("stream.eval", cat="stream", records=m,
                              chunk_records=size) as espan:
            for start in range(0, m, size):
                chunk = rec[start : start + size]
                # the evaluator copies the host chunk to the device inside its
                # dispatch (timed there as ``tune.h2d`` on one device), and
                # the evaluation is queued asynchronously
                with self.tracer.span("stream.chunk.submit", cat="stream",
                                      chunk=n_chunks, records=chunk.shape[0]):
                    out = self.evaluator(chunk)
                pending.append((out, time.perf_counter(), chunk.shape[0]))
                n_chunks += 1
                # submit-before-drain: the new chunk's dispatch is already
                # queued when the host blocks on the oldest one, so device
                # work never gaps on the drain; at most ``inflight`` stay
                # pending after it
                while len(pending) > self.inflight:
                    self._drain_one(pending, outs, on_chunk)
            while pending:
                self._drain_one(pending, outs, on_chunk)
            espan.set(chunks=n_chunks)
        wall = time.perf_counter() - t0
        self.stats.m_wall_s.inc(wall)
        self._evals += 1
        self._note_eval(size, n_chunks, m, wall)
        if not outs:
            n_trees = getattr(getattr(self.evaluator, "forest", None), "n_trees", 0)
            return np.zeros((n_trees, 0), np.int32)
        if len(outs) == 1:       # fully coalesced: no concat copy
            return outs[0]
        return np.concatenate(outs, axis=1)


def stream_eval_forest(forest, records, *, chunk_records: int = 65536, inflight: int = 2,
                       stats: StreamStats | None = None, **evaluator_kw) -> np.ndarray:
    """One-shot convenience: sharded + chunked forest evaluation.

    Args:
      forest: an ``EncodedForest`` (or list of encoded trees).
      records: (M, A) float batch, arbitrarily large — chunks of
        ``chunk_records`` stream through the sharded executor with at most
        ``inflight`` pending (double buffering at the default of 2).
      stats: optional :class:`StreamStats` to accumulate into.
      **evaluator_kw: forwarded to :class:`ShardedForestEvaluator`
        (``mesh``/``plan``/``decomposition``/``cache``/``autotune``/…).

    Returns:
      Host (T, M) int32 per-tree class assignments, bit-identical to the
      monolithic ``eval_forest_tuned`` call.
    """
    from repro.dist.executor import ShardedForestEvaluator

    ev = ShardedForestEvaluator(forest, **evaluator_kw)
    return StreamingChunker(ev, chunk_records=chunk_records, inflight=inflight,
                            stats=stats).eval(records)
