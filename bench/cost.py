"""Operations and bytes that the algorithm needs, from shapes alone.

Counted at the compressed ranks, from live lengths: no lane padding, no
dead batch slots, no page rounding.  So a count reads the same work
whatever implements it.  ``d`` is a ``dense_reference.Dims``; ``rk`` and
``rv`` are the KQ-SVD ranks; caches and activations are ``bpe`` bytes an
element (bfloat16: 2).
"""
from __future__ import annotations


def decode_attn(d, ctx: int, rk: int, rv: int, bpe: int = 2):
    """(flops, bytes) of one layer's paged decode attention for one
    sequence whose new token attends ``ctx`` cache entries: the scores
    and the weighted sum per query head, the compressed pages of every
    kv head read once, the query read and the output written."""
    flops = 2 * d.n_heads * (rk + rv) * ctx
    nbytes = bpe * (d.n_kv_heads * (rk + rv) * ctx + d.n_heads * (rk + rv))
    return flops, nbytes


def prefill_attn(d, start: int, n: int, rk: int, rv: int, bpe: int = 2):
    """(flops, bytes) of one layer's paged prefill attention for a chunk
    of ``n`` prompt tokens at positions ``start .. start + n - 1``; query
    ``s`` attends ``start + s + 1`` entries (causal)."""
    pairs = n * start + n * (n + 1) // 2
    flops = 2 * d.n_heads * (rk + rv) * pairs
    nbytes = bpe * (d.n_kv_heads * (rk + rv) * (start + n)
                    + n * d.n_heads * (rk + rv))
    return flops, nbytes


def token_flops(d, ctx: int, rk: int, rv: int, head: bool = True) -> int:
    """Model FLOPs of one token through the compressed model at a context
    of ``ctx`` entries: q/k/v projections, the KQ-SVD factors (keys and
    values into the cache, queries through B_q, outputs through C_v,
    which take the place of W_o), attention, the SwiGLU MLP, and the
    head when ``head`` (prefill needs logits of the last prompt token
    only)."""
    D, H, Hkv, dh, F = d.d_model, d.n_heads, d.n_kv_heads, d.d_head, d.d_ff
    per_layer = (2 * D * (H + 2 * Hkv) * dh
                 + 2 * Hkv * dh * (rk + rv) + 2 * H * dh * rk
                 + 2 * H * (rk + rv) * ctx
                 + 2 * H * rv * D
                 + 6 * D * F)
    return d.n_layers * per_layer + (2 * D * d.vocab if head else 0)


def chunks(prompt_len: int, chunk: int):
    """``(start, n)`` of each prefill chunk of a prompt."""
    return [(s, min(chunk, prompt_len - s))
            for s in range(0, prompt_len, chunk)]


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peaks: dict) -> float:
    """The least time the chip could take (the larger of the compute and
    the memory bound) as a percentage of ``seconds``."""
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def decode_contexts(deliveries):
    """The context of every decode forward behind ``deliveries``: each
    token emitted feeds one forward unless it ended its request, and the
    token at answer index i attends ``prompt + i + 1`` entries."""
    for d in deliveries:
        for j in range(d.k - (1 if d.finished else 0)):
            yield d.prompt_len + d.before + j + 1


def prompt_flops(d, prompt_len: int, rk: int, rv: int) -> int:
    """Model FLOPs of prefilling one prompt: every token at its own
    context, and the head once, for the last token."""
    base = token_flops(d, 0, rk, rv, head=False)
    attn = 2 * d.n_layers * d.n_heads * (rk + rv)
    return (prompt_len * base + attn * prompt_len * (prompt_len + 1) // 2
            + 2 * d.d_model * d.vocab)
