"""kernel_ms: per request (one wave), the device time of the Pallas kernels.

The sum of the durations of the ``tpu_custom_call`` events on the device in
the traced window, over the requests in it.
"""


def read(ctx):
    s = ctx.reduced.kernel_s()
    if s is None or not ctx.reduced.requests:
        return None
    return s * 1e3 / len(ctx.reduced.requests)
