"""wave_mfu: the whole device step's share of the chip's peak, in percent.

The least time the published peaks allow for the traced window's requests
(``chipbench.work``), over the time in which any operation ran on the
device in that window (the union of its ops' intervals, from the device
trace).  Every op of the wave counts, not only the kernels, so this bounds
``kernel_roofline`` from below and still reads where a later change takes
the kernel off the path.  The host's share of a wave is outside it.
"""


def read(ctx):
    busy = ctx.reduced.busy_s()
    if ctx.least_s <= 0 or busy <= 0:
        return None
    return 100.0 * ctx.least_s / busy
