"""Pallas TPU kernel: Mamba-2 SSD chunk scan (jamba/mamba2 hot spot).

Grid (B, nh, n_chunks) with n_chunks minor — TPU executes it
sequentially, so the inter-chunk state h (d_state, head_dim) lives in
VMEM scratch across a head's chunks.  Per chunk the kernel computes the
intra-chunk quadratic term (the (Lc x Lc) decay-masked score matrix stays
in VREGs; Lc defaults to 128, lane-aligned), the carried-state
contribution, and the state update — one pass over x/B/C/dt per token,
which is the bandwidth floor of SSD (the lax path in models/ssm.py, its
dry-run twin, re-materializes the chunk state to HBM each scan step).

Layout notes: x (B, nh, S, hd); B/C are per-GROUP (n_groups) and the
index_map maps head -> group (h // (nh/groups)) like GQA in the flash
kernel; a = dt * A and dt come in precomputed as (B, nh, S) f32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret


def _ssd_kernel(x_ref, a_ref, dt_ref, b_ref, c_ref, y_ref, h_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)           # (Lc, hd)
    a = a_ref[0, 0].astype(jnp.float32)           # (Lc,)  = dt * A <= 0
    dt = dt_ref[0, 0].astype(jnp.float32)         # (Lc,)
    Bm = b_ref[0, 0].astype(jnp.float32)          # (Lc, n)
    Cm = c_ref[0, 0].astype(jnp.float32)          # (Lc, n)
    cum = jnp.cumsum(a)

    # intra-chunk quadratic term (mask BEFORE exp — see models/ssm.py)
    diff = cum[:, None] - cum[None, :]
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = jnp.where(lj <= li, diff, -1e30)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    w = scores * jnp.exp(diff) * dt[None, :]
    y = jax.lax.dot(w, x, preferred_element_type=jnp.float32)

    # carried-state contribution: y += (C * exp(cum)) @ h
    h = h_ref[...]                                 # (n, hd)
    y = y + jax.lax.dot(Cm * jnp.exp(cum)[:, None], h,
                        preferred_element_type=jnp.float32)

    # state update: h = h * exp(cum[-1]) + (B * wj)^T @ x
    wj = jnp.exp(cum[-1] - cum) * dt               # (Lc,)
    h_ref[...] = h * jnp.exp(cum[-1]) + jax.lax.dot_general(
        Bm * wj[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0, 0, :, :] = y.astype(y_ref.dtype)


def ssd_chunk_scan(x, a, dt, B, C, *, chunk: int = 128,
                   interpret: Optional[bool] = None):
    """x: (B,nh,S,hd); a=dt*A, dt: (B,nh,S); B/C: (B,G,S,n) -> y like x.
    ``interpret`` defaults to the backend: Mosaic on TPU, the
    interpreter elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    Bsz, nh, S, hd = x.shape
    G, n = B.shape[1], B.shape[-1]
    rep = nh // G
    ck = min(chunk, S)
    assert S % ck == 0, (S, ck)
    grid = (Bsz, nh, S // ck)
    kernel = functools.partial(_ssd_kernel, chunk=ck)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, ck, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ck), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, ck), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, ck, n),
                         lambda b, h, c: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, ck, n),
                         lambda b, h, c: (b, h // rep, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, ck, hd),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, nh, S, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, hd), jnp.float32)],
        interpret=interpret,
        name="ssd_chunk_scan",
    )(x, a, dt, B, C)
