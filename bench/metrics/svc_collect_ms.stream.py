"""Host time of the service's collector once a batch's device results are
ready (``dvfs_runtime/service.py``): per-job traces copied to the host
(``repro.service.unpack``), reports built and futures resolved
(``repro.service.report``). Mean per batch in the window."""
from bench import spans as S

UNIT = "ms"


def read(ctx):
    if ctx.kind != "stream":
        return None
    return S.per_batch_ms(ctx, "repro.service.unpack",
                          "repro.service.report")
