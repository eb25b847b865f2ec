"""Model step: device time of the decode dispatches (the compiled
``_decode_chunk_impl`` program, ``decode_chunk`` tokens per slot) over
their count, in ms, from the trace."""
from bench import tracing


def read(run):
    ns, n = tracing.module_ns(run.trace, "decode_chunk")
    return ns / n / 1e6 if n else None
