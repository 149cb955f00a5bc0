"""Device time of the sweep layer's grid executables per simulated epoch
of a call (every executable family of the call, all rows): the engine's
scan step (``core/simulate.py`` ``_scan_sim``). On several chips, the
busiest one."""
UNIT = "ms"


def read(ctx):
    if ctx.kind != "sweep" or not ctx.n_calls:
        return None
    T, tr = ctx.T, ctx.trace
    per_dev = [T.length(T.busy(tr, d, ctx.lo, ctx.hi, T.GRID_MODULE))
               for d in tr.devices]
    if not per_dev or max(per_dev) <= 0:
        return None
    return max(per_dev) * 1e-6 / (ctx.n_calls * ctx.n_epochs)
