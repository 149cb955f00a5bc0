"""Host time of the service turning a batch's model steps into programs
(``dvfs_runtime/service.py``, ``DVFSService._derive``: each step looked
up in the program cache, a program derived by ``telemetry.step_program``
where it misses): its ``repro.service.derive`` spans. Mean per batch in
the window."""
from bench import spans as S

UNIT = "ms"


def read(ctx):
    if ctx.kind != "model_stream":
        return None
    return S.per_batch_ms(ctx, "repro.service.derive")
