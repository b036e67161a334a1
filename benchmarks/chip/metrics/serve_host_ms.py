"""serve_host_ms: per request (one wave), the serve layer's own time.

The part of the harness's ``bench.request`` span around the engine's
``run`` call that no ``stream.*`` and no ``kernel.dispatch`` span covers:
concatenating the wave's records, bucketing, the stats, the profiler's and
re-tuner's per-wave hooks, and for a forest the majority vote
(``serve.vote``).  The program's own ``serve.wave`` span starts after the
concatenation, so it would leave that out.
"""


def read(ctx):
    return ctx.reduced.self_ms_per_request(("bench.request",), ("stream.", "kernel.dispatch"))
