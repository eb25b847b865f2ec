"""Scheduler: host time of a scheduling iteration less its
device-to-host reads, in ms: over the ``engine.step`` spans wholly
inside the traced window, the mean of each span's length less the part
its ``engine.fetch`` spans cover (program spans, host clock of the
trace)."""
from bench.metrics import _spans


def read(run):
    got = _spans.steps(run.trace)
    if not got:
        return None
    return sum(step - fetch for step, fetch, _ in got) / len(got) / 1e6
