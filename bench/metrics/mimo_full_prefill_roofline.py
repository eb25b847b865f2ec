"""Kernels: the full layers' paged prefill attention kernel against its
roofline, in a ``mimo_reference`` configuration (``paged_prefill_roofline``
counted for the full layers alone; the window layers prefill without a
kernel).

Kernel time is the device time of the ``kq_prefill_paged_attention``
Pallas calls (named after the kernel) inside the compiled
``_prefill_chunk_impl`` program.  The work is what the algorithm needs
(``mimo_cost.prefill_attn``, rank width, valid tokens only): every chunk
of every prompt whose first token came in the traced steps, in every
full layer.  A prompt whose first chunks ran before the trace began is
counted whole, so the share leans high by at most those chunks.  None
where the program runs no such kernel."""
from bench import tracing
from bench.configs import mimo_cost

KERNEL, PROGRAM = "kq_prefill_paged_attention", "prefill_chunk"


def read(run):
    ns, n = tracing.op_ns(run.trace, KERNEL, module=PROGRAM)
    firsts = [x for x in run.in_trace() if x.before == 0]
    if not n or not firsts or not hasattr(run.dims, "pattern"):
        return None
    d, (rk, rv) = run.dims, run.ranks
    flops = nbytes = 0
    for x in firsts:
        for start, m in mimo_cost.chunks(x.prompt_len, run.prefill_chunk):
            f, b = mimo_cost.prefill_attn(d, start, m, rk, rv)
            flops, nbytes = flops + f, nbytes + b
    L = len(d.layers("full"))
    return mimo_cost.roofline_pct(L * flops, L * nbytes, ns / 1e9,
                                  run.peaks)
