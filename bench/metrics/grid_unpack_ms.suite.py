"""Time from the end of a call's last grid executable on a device to the
call's return: device-to-host copies and the sweep layer's per-row unpack
(``core/sweep.py`` ``_unpack_trace``). Mean over the calls of the window."""
UNIT = "ms"


def read(ctx):
    if ctx.kind != "sweep":
        return None
    T, tr = ctx.T, ctx.trace
    grid = [o for d in tr.devices for o in tr.ops[d]
            if o.module.startswith(T.GRID_MODULE)]
    out = []
    for s, e in tr.span("call"):
        ends = [o.end for o in grid if s <= o.end <= e]
        if ends:
            out.append(e - max(ends))
    return sum(out) / len(out) * 1e-6 if out else None
