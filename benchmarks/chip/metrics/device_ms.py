"""device_ms: per request (one wave), the time in which any operation ran on
the device: the union of the device ops' intervals inside the harness's
``bench.request`` span, from the device trace.  Unlike an idle share of the
window it does not depend on how far the traced window backs up."""


def read(ctx):
    return ctx.reduced.busy_ms_per_request()
