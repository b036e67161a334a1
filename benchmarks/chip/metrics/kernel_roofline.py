"""kernel_roofline: the least time the published peaks allow for the requests'
work (``chipbench.work``), over the kernels' device time, in percent."""


def read(ctx):
    s = ctx.reduced.kernel_s()
    if not s or ctx.least_s <= 0:
        return None
    return 100.0 * ctx.least_s / s
