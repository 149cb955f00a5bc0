"""Share of the window in which no operation runs on a device while the
service's host work is open (a ``repro.service.dispatch``, ``unpack`` or
``report`` span of ``dvfs_runtime/service.py``): the part of
``device_idle_share.stream`` that the service's own host path accounts
for; the rest is idle with no service work (waiting for arrivals). Mean
over the chips."""
from bench import spans as S

UNIT = "%"


def read(ctx):
    if ctx.kind != "stream" or not ctx.trace.devices:
        return None
    T, tr = ctx.T, ctx.trace
    work = T.union(S.named(ctx, "repro.service.dispatch",
                           "repro.service.unpack", "repro.service.report"))
    if not work:
        return None
    # work while idle = |busy or work| - |busy|
    idle = [T.length(T.union(busy + work)) - T.length(busy)
            for busy in (T.busy(tr, d, ctx.lo, ctx.hi) for d in tr.devices)]
    return 100.0 * sum(idle) / len(idle) / (ctx.hi - ctx.lo)
