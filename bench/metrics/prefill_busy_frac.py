"""Model step: device time of the prefill-chunk dispatches (the compiled
``_prefill_chunk_impl`` program) over the device's busy time, from the
trace."""
from bench import tracing


def read(run):
    ns, n = tracing.module_ns(run.trace, "prefill_chunk")
    if not n:
        return None
    return ns / tracing.busy_ns(run.trace, run.trace["devices"][0])
