"""Time the service's dispatcher (``dvfs_runtime/service.py``) waits for
an in-flight slot before it dispatches a batch: its
``repro.service.admit`` spans. Mean per batch in the window."""
from bench import spans as S

UNIT = "ms"


def read(ctx):
    if ctx.kind != "stream":
        return None
    return S.per_batch_ms(ctx, "repro.service.admit")
