"""Whole step of a ``mimo_reference`` configuration: model FLOPs of the
tokens processed in the traced steps, over the traced window's time, as
a percentage of the chip's bf16 peak.

Counted as ``step_mfu`` counts (a decode forward for every token
delivered in the traced steps but a request's last, and the whole
prompt of every request whose first token came in them), with
``mimo_cost``'s FLOPs: window layers at ``min(ctx, 128)``, expert
layers at their expected held pairs."""
from bench.configs import mimo_cost


def read(run):
    got = run.in_trace()
    if not got or not hasattr(run.dims, "pattern"):
        return None
    d, (rk, rv) = run.dims, run.ranks
    flops = sum(mimo_cost.token_flops(d, c, rk, rv)
                for c in mimo_cost.decode_contexts(got))
    flops += sum(mimo_cost.prompt_flops(d, x.prompt_len, rk, rv)
                 for x in got if x.before == 0)
    return 100.0 * flops / run.trace_seconds() / run.peaks[
        "bf16_flops_per_s"]
