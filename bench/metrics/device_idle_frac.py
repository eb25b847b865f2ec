"""Device: share of the traced window in which no operation ran (first
device), from the trace."""
from bench import tracing


def read(run):
    if not run.trace["devices"]:
        return None
    busy = tracing.busy_ns(run.trace, run.trace["devices"][0])
    return 1.0 - busy / (run.trace_seconds() * 1e9)
