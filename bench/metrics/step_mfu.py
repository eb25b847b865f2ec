"""Whole step: model FLOPs of the tokens processed in the traced steps,
over the traced window's time, as a percentage of the chip's bf16 peak.

Counted: a decode forward for every token delivered in the traced steps
(none after a request's last token), and the whole prompt of every
request whose first token came in them (``cost.prompt_flops``).  The
FLOPs are the compressed model's own (``cost.token_flops``)."""
from bench import cost


def read(run):
    got = run.in_trace()
    if not got:
        return None
    d, (rk, rv) = run.dims, run.ranks
    flops = sum(cost.token_flops(d, c, rk, rv)
                for c in cost.decode_contexts(got))
    flops += sum(cost.prompt_flops(d, x.prompt_len, rk, rv)
                 for x in got if x.before == 0)
    return 100.0 * flops / run.trace_seconds() / run.peaks[
        "bf16_flops_per_s"]
