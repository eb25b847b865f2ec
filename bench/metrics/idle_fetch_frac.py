"""Device: share of the traced window in which device 0 was idle while
the engine's host waited on a device-to-host read (inside an
``engine.fetch`` span), from the trace and the program's spans."""
from bench.metrics import _spans


def read(run):
    return _spans.idle_frac(run.trace, _spans.FETCH)
