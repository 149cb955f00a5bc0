"""Time from the end of a call's last grid executable on any device to the
call's return: the sweep layer's fetch of each family output array to the
host (``core/sweep.py`` ``_fetch``) and its cut of every row there
(``_unpack_trace``). Mean over the calls of the window."""
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
