"""Real jobs per dispatched micro-batch as a share of the service's
``max_batch`` (``dvfs_runtime/service.py``), from each job's reported
``batch_size``: a batch of b jobs reports b, b times."""
UNIT = "%"


def read(ctx):
    if ctx.kind != "stream" or not ctx.batch_sizes:
        return None
    batches = sum(1.0 / b for b in ctx.batch_sizes)
    return 100.0 * len(ctx.batch_sizes) / batches / ctx.max_batch
