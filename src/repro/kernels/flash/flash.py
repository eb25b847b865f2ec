"""Pallas TPU flash attention (prefill/train hot spot).

Tiling: grid (B, H, Nq, Nk) — TPU executes the grid sequentially
minor-to-major, so the (m, l, acc) online-softmax statistics live in VMEM
scratch and persist across the Nk-minor steps of one q block.  Block
shapes: q (bq, dh), k/v (bk, dh) staged HBM->VMEM by BlockSpec; dh is
lane-aligned (128 for the assigned archs), bq/bk default 512 (MXU-aligned
multiples of 128).  GQA is handled by the k/v index_map (kv head = query
head // group size).  Causal block skipping: whole (i, j) tiles with
j > i are skipped via ``pl.when`` — the kernel-level version of the
triangular packing used by the lax path (attention.py).

Validated in interpret mode against ``ref.py``; on TPU the same
pallas_call lowers to Mosaic.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, bq: int, bk: int, causal: bool,
                  window: int):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level skip: causal upper triangle and out-of-window tiles
    run = jnp.bool_(True)
    if causal:
        run = run & (j * bk <= i * bq + bq - 1)
    if window:
        run = run & (j * bk + bk - 1 >= i * bq - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.bool_(True)
        if causal:
            mask = kpos <= qpos
        if window:
            mask = mask & (qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    scale=None, interpret: Optional[bool] = None):
    """q: (B,H,S,dh); k/v: (B,Hkv,S,dh) -> (B,H,S,dh).  ``interpret``
    defaults to the backend: Mosaic on TPU, the interpreter elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    B, H, S, dh = q.shape
    Hkv = k.shape[1]
    dv = v.shape[-1]
    m = H // Hkv
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = scale or 1.0 / math.sqrt(dh)
    grid = (B, H, S // bq, S // bk)

    kernel = functools.partial(_flash_kernel, scale=scale, bq=bq, bk=bk,
                               causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, i, j: (b, h // m, j, 0)),
            pl.BlockSpec((1, 1, bk, dv),
                         lambda b, h, i, j: (b, h // m, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max
            pltpu.VMEM((bq,), jnp.float32),       # running denominator
            pltpu.VMEM((bq, dv), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
