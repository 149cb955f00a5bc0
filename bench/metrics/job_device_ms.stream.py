"""Device busy time in the window per job completed (the engine's work for
one job's two mechanism rows, plus its share of the unpack copies)."""
UNIT = "ms"


def read(ctx):
    if ctx.kind != "stream" or not ctx.batch_sizes or not ctx.trace.devices:
        return None
    return ctx.T.busy_s(ctx.trace, ctx.lo, ctx.hi) * 1e3 / len(ctx.batch_sizes)
