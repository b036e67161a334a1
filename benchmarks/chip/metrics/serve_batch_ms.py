"""serve_batch_ms: per request (one wave), the time in the program's
``serve.batch`` span: the serve layer's concatenation of the wave's records
and their cast to float32, two host copies of the frame.  None where the
program has no such span."""


def read(ctx):
    return ctx.reduced.self_ms_per_request(("serve.batch",), ())
