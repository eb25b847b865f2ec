"""Kernels: the paged prefill attention kernel's share of its roofline.

Kernel time is the device time of the Pallas call (``tpu_custom_call``)
inside the compiled ``_prefill_chunk_impl`` program, which holds that one
kernel.  The work is what the algorithm needs (``cost.prefill_attn``,
rank width, valid tokens only): every chunk of every prompt whose first
token came in the traced steps, in every layer.  A prompt whose first
chunks ran before the trace began is counted whole, so the share leans
high by at most those chunks."""
from bench import cost, tracing

KERNEL, PROGRAM = "tpu_custom_call", "prefill_chunk"


def read(run):
    ns, n = tracing.op_ns(run.trace, KERNEL, module=PROGRAM)
    firsts = [x for x in run.in_trace() if x.before == 0]
    if not n or not firsts:
        return None
    d, (rk, rv) = run.dims, run.ranks
    flops = nbytes = 0
    for x in firsts:
        for start, m in cost.chunks(x.prompt_len, run.prefill_chunk):
            f, b = cost.prefill_attn(d, start, m, rk, rv)
            flops, nbytes = flops + f, nbytes + b
    L = d.n_layers
    return cost.roofline_pct(L * flops, L * nbytes, ns / 1e9, run.peaks)
