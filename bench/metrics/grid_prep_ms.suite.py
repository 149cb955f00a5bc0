"""Host time of the sweep layer (``core/sweep.py`` ``run_grid``) before a
call's first grid executable runs on a device: grid points, dedup classes,
padding and stacking, host-to-device copies and dispatch. Mean over the
calls of the window."""
UNIT = "ms"


def read(ctx):
    if ctx.kind != "sweep":
        return None
    T, tr = ctx.T, ctx.trace
    grid = [o for d in tr.devices for o in tr.ops[d]
            if o.module.startswith(T.GRID_MODULE)]
    out = []
    for s, e in tr.span("call"):
        starts = [o.start for o in grid if s <= o.start <= e]
        if starts:
            out.append(min(starts) - s)
    return sum(out) / len(out) * 1e-6 if out else None
