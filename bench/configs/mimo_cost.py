"""Operations and bytes of MiMo-V2-Flash's layers, from shapes alone.

Counted as ``bench/cost.py`` counts them: at the KQ-SVD ranks, from live
lengths, with no lane padding, dead batch slots or page rounding.  A
window layer attends ``min(ctx, window)`` entries.  The model's FLOPs
count the expert layer at its expected load under uniform routing: each
token routes ``top_k`` choices over ``n_experts``, so
``top_k * n_held / n_experts`` of them (0.25 at 8 of 256 held, top-8)
land on this chip's experts.  The expert layer's roofline is counted
from what the program reports instead (``expert_work``): the bytes its
kernel reads follow the experts that a call's tokens touch, which
depends on how those tokens route (an expected count under uniform
routing read 122% on the chip with a skewed router).  ``d`` is a
``mimo_reference.Dims``.
"""
from __future__ import annotations

from bench import cost


def attn_ctx(d, kind: str, ctx: int) -> int:
    """Entries a query of ``kind`` attends at context ``ctx``."""
    return ctx if kind == "full" else min(ctx, d.window)


def decode_attn(d, kind: str, ctx: int, rk: int, rv: int, bpe: int = 2):
    """(flops, bytes) of one ``kind`` layer's decode attention for one
    sequence at context ``ctx``: scores and weighted sum per query head,
    the compressed entries of every KV head read once, the query read
    and the output written."""
    n = attn_ctx(d, kind, ctx)
    flops = 2 * d.n_heads * (rk + rv) * n
    nbytes = bpe * (d.kv(kind) * (rk + rv) * n + d.n_heads * (rk + rv))
    return flops, nbytes


def prefill_attn(d, start: int, n: int, rk: int, rv: int, bpe: int = 2):
    """(flops, bytes) of one full layer's paged prefill attention for a
    chunk of ``n`` prompt tokens at positions ``start .. start + n - 1``
    (``cost.prefill_attn`` at the full layers' KV heads)."""
    pairs = n * start + n * (n + 1) // 2
    flops = 2 * d.n_heads * (rk + rv) * pairs
    nbytes = bpe * (d.kv_full * (rk + rv) * (start + n)
                    + n * d.n_heads * (rk + rv))
    return flops, nbytes


def held_pairs_per_token(d) -> float:
    """Expected (token, held expert) pairs of one token in one expert
    layer under uniform routing."""
    return d.top_k * d.n_held / d.n_experts


def expert_work(d, pairs: float, touched: float, bpe: int = 2):
    """(flops, bytes) of held-expert SwiGLU matmuls over ``pairs``
    (token, held expert) pairs that touch ``touched`` (expert, call)
    weight sets: the pairs' matmuls, each touched expert's weights read
    once a call, and the pairs' activations in and out."""
    D, F = d.d_model, d.d_expert
    return (pairs * 6 * D * F,
            bpe * (touched * 3 * D * F + 2 * pairs * D))


def token_flops(d, ctx: int, rk: int, rv: int, head: bool = True) -> float:
    """Model FLOPs of one token at context ``ctx`` through the
    compressed model: q/k/v projections, the KQ-SVD factors (C_v in
    place of W_o), attention of each layer's kind, layer 0's dense MLP,
    the router and the expected held pairs of each expert layer, and
    the head when ``head``."""
    D, H, dqk, dv = d.d_model, d.n_heads, d.d_qk, d.d_v
    total = 2 * D * d.vocab if head else 0
    for i, k in enumerate(d.pattern):
        kind = ("full", "window")[k]
        G = d.kv(kind)
        total += (2 * D * (H * dqk + G * (dqk + dv))
                  + 2 * G * (dqk * rk + dv * rv) + 2 * H * dqk * rk
                  + 2 * H * rv * D
                  + 2 * H * (rk + rv) * attn_ctx(d, kind, ctx))
        if d.dense[i]:
            total += 6 * D * d.d_ff
        else:
            total += (2 * D * d.n_experts
                      + held_pairs_per_token(d) * 6 * D * d.d_expert)
    return total


def prompt_flops(d, prompt_len: int, rk: int, rv: int) -> float:
    """Model FLOPs of prefilling one prompt: each token at its own
    context, and the head once, for the last token."""
    return (sum(token_flops(d, c, rk, rv, head=False)
                for c in range(1, prompt_len + 1))
            + 2 * d.d_model * d.vocab)


roofline_pct = cost.roofline_pct
decode_contexts = cost.decode_contexts
chunks = cost.chunks
