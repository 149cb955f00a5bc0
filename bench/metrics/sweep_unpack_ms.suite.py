"""Host time of the sweep layer's unpack (``core/sweep.py`` ``run_grid``
building its result dict: every row's channels sliced and copied to the
host), per call: its ``repro.sweep.unpack`` span. Mean over the calls of
the window."""
from bench import spans as S

UNIT = "ms"


def read(ctx):
    if ctx.kind != "sweep":
        return None
    return S.per_call(ctx, "repro.sweep.unpack")
