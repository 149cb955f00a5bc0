"""Share of the window in which no operation runs on a device (averaged
over the chips), under the service's open-loop stream."""
UNIT = "%"


def read(ctx):
    if ctx.kind != "stream" or not ctx.trace.devices:
        return None
    busy = ctx.T.busy_s(ctx.trace, ctx.lo, ctx.hi)
    return 100.0 * (1.0 - busy / ((ctx.hi - ctx.lo) * 1e-9))
