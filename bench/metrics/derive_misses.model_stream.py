"""Programs the service derived from model steps inside the window (the
steps not found in its program cache): the service's own
``programs_derived`` counter (``DVFSService.stats``) after the window less
before it, the sum of the ``misses`` its ``repro.service.derive`` spans
carry. The trace reduction keeps no span arguments, so the count is read
from the counter."""
UNIT = "programs"


def read(ctx):
    if ctx.kind != "model_stream" or "derived_in_window" not in vars(ctx):
        return None
    return ctx.derived_in_window
