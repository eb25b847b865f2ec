"""Scheduler: share of the traced window in which device 0 was idle
while the engine's host ran a scheduling iteration (inside an
``engine.step`` span) outside every device-to-host read (``engine.fetch``
span), from the trace and the program's spans."""
from bench.metrics import _spans


def read(run):
    return _spans.idle_frac(run.trace, _spans.STEP, outside=_spans.FETCH)
