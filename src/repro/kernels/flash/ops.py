"""jit'd public wrapper for the flash attention kernel.

``interpret=None`` (the default) resolves from the backend at trace
time: real Mosaic compilation on TPU, interpreter everywhere else.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash.flash import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_op(q, k, v, *, causal=True, window=0, block_q=512,
                       block_k=512, interpret=None):
    """jit'd flash attention (``flash_attention``); q/k/v (B,H,T,d)."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
