"""Kernels: the paged decode attention kernel's share of its roofline.

Kernel time is the device time of the Pallas call (``tpu_custom_call``)
inside the compiled ``_decode_chunk_impl`` program, which holds that one
kernel (the trace names the call by its HLO instruction, not by the
kernel).  The work is what the algorithm needs, at rank width from live
lengths (``cost.decode_attn``): every decode forward of the traced
steps, in every layer."""
from bench import cost, tracing

KERNEL, PROGRAM = "tpu_custom_call", "decode_chunk"


def read(run):
    ns, n = tracing.op_ns(run.trace, KERNEL, module=PROGRAM)
    got = run.in_trace()
    if not n or not got:
        return None
    d, (rk, rv) = run.dims, run.ranks
    flops = nbytes = 0
    for c in cost.decode_contexts(got):
        f, b = cost.decode_attn(d, c, rk, rv)
        flops, nbytes = flops + f, nbytes + b
    L = d.n_layers
    return cost.roofline_pct(L * flops, L * nbytes, ns / 1e9, run.peaks)
