"""Channel arrays the sweep layer slices and copies to the host per call
(``core/sweep.py`` ``_unpack_trace``, one ``repro.sweep.copy`` span
each). Mean over the calls of the window."""
from bench import spans as S

UNIT = "copies"


def read(ctx):
    if ctx.kind != "sweep":
        return None
    return S.per_call(ctx, "repro.sweep.copy", count=True)
