"""dispatch_host_ms: per request (one wave), the host's enqueue work inside
the program's ``kernel.dispatch`` span.

The ``kernel.dispatch`` time that no ``tune.h2d`` (the host-to-device copy
of the records, up to its asynchronous return), ``kernel.wait`` (the wait
for the device, and for the copy to land) and ``kernel.d2h`` (the copy
back) covers: the dispatch's fast path, the bucket padding and the jitted
call.  These are eager JAX operations, which the profiler slows: in a
traced run this reads about half again the profiler-off time.  None where
the program does not split its dispatch into these spans.
"""


def read(ctx):
    if not ctx.reduced.spans("kernel.wait"):
        return None
    return ctx.reduced.self_ms_per_request(("kernel.dispatch",),
                                           ("tune.h2d", "kernel.wait", "kernel.d2h"))
