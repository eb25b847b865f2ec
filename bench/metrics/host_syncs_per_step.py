"""Scheduler: blocking device-to-host reads per scheduling iteration:
``engine.fetch`` spans inside the ``engine.step`` spans wholly inside
the traced window, over the count of those steps (program spans)."""
from bench.metrics import _spans


def read(run):
    got = _spans.steps(run.trace)
    if not got:
        return None
    return sum(n for _, _, n in got) / len(got)
