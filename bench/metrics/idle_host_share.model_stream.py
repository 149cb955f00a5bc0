"""``idle_host_share.stream`` (device idle while the service's host work is
open) in a ``model_stream`` run: the same service, dispatcher, collector and
grid executables, its requests model steps (``DVFSService.submit_step``).
The stream reader, read as it is."""
from pathlib import Path
from types import SimpleNamespace

from bench import harness

_stream = harness.load_module(Path(__file__).with_name(
    Path(__file__).name.replace(".model_stream.", ".stream.")))
UNIT = "%"


def read(ctx):
    if ctx.kind != "model_stream":
        return None
    return _stream.read(SimpleNamespace(**dict(vars(ctx), kind="stream")))
