"""Pallas TPU kernels for the perf-critical compute layers.

flash/      prefill/train attention (BlockSpec-tiled, causal block skip)
kq_decode/  decode attention over the KQ-SVD-compressed cache (the
            paper's runtime hot spot)
ssd/        Mamba-2 SSD chunk scan (jamba / mamba2 hot spot; inter-chunk
            state carried in VMEM scratch across the sequential grid)

Each kernel ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper) and ref.py (pure-jnp oracle); tests sweep shapes/dtypes in
interpret mode.  The model's attention runs the kq_decode kernels on a
TPU backend (``use_kernels``); elsewhere it runs the lax twins in
repro.models.attention, which are also the kernels' test references.
"""

import jax
import jax.numpy as jnp

LANE = 128          # TPU lane count: Mosaic trailing-axis multiple


def use_kernels() -> bool:
    """Whether model attention dispatches the Pallas kernels: decided by
    the platform, never by a setting — on TPU the kernels are the path,
    elsewhere the lax twins run.  Read at trace time through the module
    attribute, so a test can patch it to drive the interpret-mode
    kernels through the whole model on CPU."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Kernel-wrapper default for ``interpret``: Mosaic-compile on TPU,
    interpreter everywhere else (CPU/GPU backends cannot lower TPU
    Pallas kernels)."""
    return jax.default_backend() != "tpu"


def pad_to_lane(x, mult: int = LANE):
    """Zero-pad the trailing axis up to a multiple of ``mult``."""
    r = x.shape[-1] % mult
    if r == 0:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, mult - r)]
    return jnp.pad(x, pad)
