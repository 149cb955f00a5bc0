"""Executable-family output arrays the sweep layer fetches to the host per
call (``core/sweep.py`` ``_fetch``, one ``repro.sweep.copy`` span each;
a sharded array gathers its shards in its one fetch). Mean over the calls
of the window."""
from bench import spans as S

UNIT = "copies"


def read(ctx):
    if ctx.kind != "sweep":
        return None
    return S.per_call(ctx, "repro.sweep.copy", count=True)
