"""Host time of the sweep layer's unpack (``core/sweep.py`` ``run_grid``
building its result dict: each family output array fetched to the host
once, ``_fetch``, and every row cut from it on the host,
``_unpack_trace``), per call: its ``repro.sweep.unpack`` span. Mean over
the calls of the window."""
from bench import spans as S

UNIT = "ms"


def read(ctx):
    if ctx.kind != "sweep":
        return None
    return S.per_call(ctx, "repro.sweep.unpack")
