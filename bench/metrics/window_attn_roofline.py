"""Kernels: the window layers' decode attention against its roofline.

Kernel time is the device time of the ``kq_window_paged_attention``
Pallas calls (named after the kernel) inside the compiled
``_decode_chunk_impl`` program.  The work is what the algorithm needs
(``mimo_cost.decode_attn``, rank width, ``min(ctx, 128)`` live
entries): every decode forward of the traced steps, in every window
layer.  None where the program runs no such kernel."""
from bench import tracing
from bench.configs import mimo_cost

KERNEL, PROGRAM = "kq_window_paged_attention", "decode_chunk"


def read(run):
    ns, n = tracing.op_ns(run.trace, KERNEL, module=PROGRAM)
    got = run.in_trace()
    if not n or not got or not hasattr(run.dims, "pattern"):
        return None
    d, (rk, rv) = run.dims, run.ranks
    flops = nbytes = 0
    for c in mimo_cost.decode_contexts(got):
        f, b = mimo_cost.decode_attn(d, "window", c, rk, rv)
        flops, nbytes = flops + f, nbytes + b
    L = len(d.layers("window"))
    return mimo_cost.roofline_pct(L * flops, L * nbytes, ns / 1e9,
                                  run.peaks)
