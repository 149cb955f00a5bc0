"""Time the sweep layer (``core/sweep.py`` ``run_grid``) waits for every
executable family's outputs, per call: its ``repro.sweep.wait`` span.
Mean over the calls of the window."""
from bench import spans as S

UNIT = "ms"


def read(ctx):
    if ctx.kind != "sweep":
        return None
    return S.per_call(ctx, "repro.sweep.wait")
