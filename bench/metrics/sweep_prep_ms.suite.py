"""Host time the sweep layer (``core/sweep.py`` ``run_grid``) spends
before the device results are awaited, per call: its ``repro.sweep.prep``
spans (grid points, programs padded and stacked, operands flattened) and
``repro.sweep.dispatch`` spans (each executable family's carry build and
enqueue). Mean over the calls of the window."""
from bench import spans as S

UNIT = "ms"


def read(ctx):
    if ctx.kind != "sweep":
        return None
    return S.per_call(ctx, "repro.sweep.prep", "repro.sweep.dispatch")
