"""Host time of the service's dispatch (``dvfs_runtime/service.py``,
``GridExecutor.dispatch``: operands stacked, padded and put on the
device, each family's carry built and enqueued): its
``repro.service.dispatch`` spans. Mean per batch in the window."""
from bench import spans as S

UNIT = "ms"


def read(ctx):
    if ctx.kind != "stream":
        return None
    return S.per_batch_ms(ctx, "repro.service.dispatch")
