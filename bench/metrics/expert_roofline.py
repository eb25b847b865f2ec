"""MoE layer: the held experts' grouped matmuls in decode against their
roofline.

Device time is that of the ``ragged-dot`` operations (the grouped
matmul kernel ``jax.lax.ragged_dot`` lowers to, and its group
metadata) inside the compiled ``_decode_chunk_impl`` program: the ops
under the ``moe_experts`` scope.  The work is ``mimo_cost.expert_work``
of what the program reports for the traced window's steps
(``_held_spans``): the (token, held expert) pairs computed, and each
held expert's weights read once in every decode iteration and layer in
which a pair touched it.  None where the program runs no such operation
or names no such span."""
from bench import tracing
from bench.configs import mimo_cost
from bench.metrics import _held_spans

OPS, PROGRAM = "ragged-dot", "decode_chunk"


def read(run):
    got = _held_spans.totals(run)
    if not got or not hasattr(run.dims, "moe_layers"):
        return None
    ns, n = tracing.op_ns(run.trace, OPS, module=PROGRAM)
    if not n:
        return None
    pairs, _, touched = got
    flops, nbytes = mimo_cost.expert_work(run.dims, pairs, touched)
    return mimo_cost.roofline_pct(flops, nbytes, ns / 1e9, run.peaks)
