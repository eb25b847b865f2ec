"""jit'd public wrapper for the SSD chunk-scan kernel.

``interpret=None`` (the default) resolves from the backend at trace
time: real Mosaic compilation on TPU, interpreter everywhere else.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.ssd.ssd import ssd_chunk_scan


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan_op(x, a, dt, B, C, *, chunk=128, interpret=None):
    """jit'd SSD chunk scan (``ssd_chunk_scan``) over chunked time."""
    return ssd_chunk_scan(x, a, dt, B, C, chunk=chunk,
                          interpret=interpret)
