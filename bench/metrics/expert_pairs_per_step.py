"""MoE layer: (token, held expert) pairs per expert layer per decode
iteration, as the program counts them.

The engine counts the pairs its held-experts layers compute in each
decode iteration and how many iterations ran, and names them in an
``engine.held_pairs=`` host span per step (``_held_spans``,
docs/SERVING.md §Tracing).  This reads the spans of the traced window:
pairs over iterations over the configuration's expert layers.  None
without such spans."""
from bench.metrics import _held_spans


def read(run):
    got = _held_spans.totals(run)
    if not got or not got[1] or not hasattr(run.dims, "moe_layers"):
        return None
    pairs, steps, _ = got
    return pairs / steps / len(run.dims.moe_layers)
